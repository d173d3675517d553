"""The benchmark's own tests run on the CPU: `python3 -m pytest benchmarks/tests -q`
from the root of the repo. Both variables are read when jax is first
imported, which is after this file."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["RAY_TPU_LOG_TO_DRIVER"] = "0"
