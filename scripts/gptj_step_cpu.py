"""Execute ONE GPT-J-6B train step on a virtual CPU mesh (north-star dry-fit).

VERDICT r4 #10: go beyond lowering — actually run the 6.05B-param sharded
train step. 8 virtual CPU devices, fsdp=2 x tp=2 x dp=2, remat, adafactor. On the 125 GiB host this materializes the full optimizer
state (~60 GiB) and executes fwd+bwd+update once; loss and step wall time
print as evidence for MULTICHIP_r05.

Run ALONE (the transient update peak approaches host RAM):
    python scripts/gptj_step_cpu.py
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.models import gptj_6b, init_params, make_train_step, param_shardings
from ray_tpu.parallel import MeshSpec


def main():
    B, S = 4, 256
    # fsdp=4 x tp=2: NO dp axis — on a virtual single-host mesh dp
    # REPLICATES state per device (8 x per-device footprint shares one
    # RAM), which is what OOM-killed the dp=2 variants.
    mesh = MeshSpec(fsdp=4, tp=2).build(jax.devices()[:8])
    cfg = gptj_6b(max_seq=S, attn_impl="ref", remat=True)
    shardings = param_shardings(cfg, mesh)

    import jax.numpy as jnp

    t0 = time.perf_counter()
    # bf16 resident params for the CPU dry-fit: the f32-master + f32-grad
    # peak OOM-killed the 125 GiB host twice (XLA CPU holds looser
    # transients than TPU). One bf16 step is the execution evidence; the
    # precision recipe on real chips stays f32 masters (bench.py).
    params = jax.jit(
        lambda k: jax.tree.map(
            lambda a: a.astype(jnp.bfloat16), init_params(k, cfg)
        ),
        out_shardings={k: shardings[k] for k in shardings},
    )(jax.random.PRNGKey(0))
    jax.block_until_ready(params)
    t_init = time.perf_counter() - t0

    # Adafactor: factored second moments, no first moment — full adamw
    # state (f32 nu + transient f32 grads) OOM-killed the 125 GiB host
    # (exit 137). Same optimizer the gpt2-xl single-chip bench uses.
    opt = optax.adafactor(1e-4)
    opt_state = jax.jit(opt.init)(params)
    jax.block_until_ready(opt_state)

    tokens = jax.device_put(
        jax.random.randint(jax.random.PRNGKey(1), (B, S + 1), 0, cfg.vocab_size),
        NamedSharding(mesh, P(("dp", "fsdp"), None)),
    )
    step = jax.jit(make_train_step(cfg, opt), donate_argnums=(0,))

    t0 = time.perf_counter()
    state, metrics = step((params, opt_state), {"tokens": tokens})
    loss = float(metrics["loss"])
    gnorm = float(metrics["grad_norm"])
    t_step = time.perf_counter() - t0

    assert loss == loss and loss > 0, f"bad 6B loss {loss}"
    assert gnorm > 0, "6B gradients are zero"
    print(json.dumps({
        "probe": "gptj_6b_step_executed_cpu_mesh",
        "params_b": round(cfg.n_params / 1e9, 2),
        "mesh": {"fsdp": 4, "tp": 2},
        "batch": B, "seq": S,
        "loss": round(loss, 4), "grad_norm": round(gnorm, 4),
        "init_s": round(t_init, 1),
        "step_s": round(t_step, 1),  # compile + one step
    }), flush=True)


if __name__ == "__main__":
    main()
