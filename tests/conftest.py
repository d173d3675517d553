"""Test fixtures (reference analog: `python/ray/tests/conftest.py`).

CI runs on CPU JAX with a forced 8-device host platform so multi-chip SPMD
logic is exercised without TPUs (SURVEY.md §4 "fake mesh" requirement).
"""

import os

# CI runs on a fake 8-device CPU mesh (SURVEY.md §4); both variables are read
# when jax is first imported, which is after this file.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["RAY_TPU_LOG_TO_DRIVER"] = "0"  # keep worker logs out of test output
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import contextlib  # noqa: E402
import threading  # noqa: E402

import pytest  # noqa: E402

import ray_tpu  # noqa: E402


def _drop_stray_runtime():
    """A thread an earlier test left behind (a prefetcher still pulling)
    that calls the API after that test's shutdown boots a fresh default
    runtime (`api._global_runtime` auto-inits). Without this, the next
    fixture's `init` then raises "called twice", skips its own teardown, and
    every later test of the file errors the same way (seen once: 23 of
    `tests/test_data.py` after its first test, six workers, PR 24)."""
    ray_tpu.shutdown()      # a no-op when there is none


@contextlib.contextmanager
def _paged_jits_with_tile(keys):
    import jax

    from ray_tpu.models import gpt
    from ray_tpu.ops import paged_attention

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(paged_attention, "_ATTN_TILE_KEYS", keys)
        yield (
            jax.jit(lambda *a: gpt.prefill_paged(*a),
                    static_argnums=(6,), donate_argnums=(5,)),
            jax.jit(lambda *a: gpt.decode_step_paged(*a),
                    static_argnums=(5,), donate_argnums=(4,)),
            jax.jit(lambda *a: gpt.verify_step_paged(*a),
                    static_argnums=(6,), donate_argnums=(5,)),
        )


@pytest.fixture(scope="session")
def tile_keys():
    """`with tile_keys(n) as (prefill, decode, verify):` the paged programs
    jitted anew (and pool-donating, as the engine's are) and traced with
    `ops.paged_attention._ATTN_TILE_KEYS = n`, the keys a trip of the paged
    key loop covers: a table of at most n keys is attended in one shot,
    a wider one in tiles. The constant is read while tracing, so calls
    belong inside the `with`. Each program is wrapped in a function of its
    own: jit's cache of traces is keyed by the function, and would hand
    back the other form's."""
    return _paged_jits_with_tile


@pytest.fixture
def local_runtime():
    """In-process runtime (reference analog: `ray_start_regular` local-mode)."""
    _drop_stray_runtime()
    ray_tpu.init(local_mode=True, ignore_reinit_error=False)
    yield
    ray_tpu.shutdown()


@pytest.fixture
def cluster_runtime():
    """Full multiprocess runtime on this machine."""
    _drop_stray_runtime()
    ray_tpu.init(num_cpus=4)
    yield
    ray_tpu.shutdown()


@pytest.fixture
def shutdown_only():
    yield
    ray_tpu.shutdown()


class _MetricSink:
    """What `util.metrics` handed a backend, and from which thread."""

    def __init__(self):
        self.sent = []      # (thread name, name, kind, value, tags, extra)
        self.pruned = []    # the tags of each prune
        self.down = False

    def record_metric(self, name, kind, value, tags, **extra):
        self.sent.append((threading.current_thread().name, name, kind, value,
                          dict(tags), extra))

    def prune_metrics(self, tags):
        self.pruned.append(dict(tags))

    def shutdown(self):
        self.down = True

    def series(self, name, **tags):
        """The messages of one series, in the order they arrived."""
        want = {k: str(v) for k, v in tags.items()}
        return [m for m in self.sent if m[1] == name and m[4] == want]


@pytest.fixture
def metric_sink(monkeypatch):
    """This process connected to a runtime whose backend only notes the
    metric messages it is handed: the real pending table and the real
    flusher thread of `util.metrics`, no cluster."""
    import types

    from ray_tpu.core import api
    from ray_tpu.util import metrics

    _drop_stray_runtime()
    sink = _MetricSink()
    rt = types.SimpleNamespace(backend=sink, shutdown=sink.shutdown)
    monkeypatch.setattr(api, "_runtime", rt)
    yield sink
    if api._runtime is rt:
        metrics.flush()     # nothing of this test stays pending for the next


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "cluster: test boots the multiprocess cluster plane"
    )
    config.addinivalue_line(
        "markers",
        "chaos: kill-based fault-injection test (SIGKILL/OOM of live "
        "workers or nodes); tier-1-safe quick variants stay unmarked",
    )
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 `-m 'not slow'` gate (long bench "
        "or multi-minute integration runs; keep the gate under its 870s "
        "window)",
    )
