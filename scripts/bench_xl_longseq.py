"""Round-3 bench sweeps: gpt2-xl (1.5B) single-chip training and
long-sequence flash attention (VERDICT item 6: bigger model + 8k-16k
sequence coverage; the headline bench.py number stays gpt2-large).

One JSON line per probe. gpt2-xl uses adafactor (factored second moments):
adamw's 2x fp32 moments for 1.56B params (~12.5 GiB) + fp32 params do not
fit a 16G chip — adafactor is the standard big-model-on-small-chip
optimizer and keeps the MFU math honest. Long-sequence probes run the
flash-attention kernel fwd+bwd standalone at S=8k/16k (what ring attention
executes per shard on every chip of an SP mesh; the ring collectives
themselves need multiple chips — see tests/test_parallel.py for the 8-way
CPU-mesh equivalence checks).
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import peak_flops_per_chip  # noqa: E402


def report(**kw):
    print(json.dumps(kw), flush=True)


def bench_xl():
    import jax
    import optax

    from ray_tpu.models import gpt2_xl, init_params, make_train_step

    import jax.numpy as jnp

    B, S = 4, 1024
    cfg = gpt2_xl(max_seq=S, attn_impl="flash", remat=True)
    # bf16 MASTER weights: f32 masters for 1.56B params put params+grads+
    # updates at ~18G — over the 16G chip no matter the batch. bf16 masters
    # + adafactor is the standard single-small-chip recipe (multi-chip FSDP
    # is the production path for this model; see the 8-dev dryrun).
    params = jax.jit(
        lambda key: jax.tree.map(
            lambda a: a.astype(jnp.bfloat16), init_params(key, cfg)
        )
    )(jax.random.PRNGKey(0))
    opt = optax.adafactor(3e-4)
    opt_state = jax.jit(opt.init)(params)
    step = jax.jit(make_train_step(cfg, opt), donate_argnums=(0,))
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (B, S + 1), 0, cfg.vocab_size
    )
    batch = {"tokens": tokens}
    state = (params, opt_state)
    for _ in range(2):
        state, metrics = step(state, batch)
    _ = float(metrics["loss"])
    n = 8
    t0 = time.perf_counter()
    for _ in range(n):
        state, metrics = step(state, batch)
    _ = float(metrics["loss"])
    dt = (time.perf_counter() - t0) / n
    tok_s = B * S / dt
    mfu = cfg.flops_per_token(S) * tok_s / peak_flops_per_chip(jax.devices()[0].device_kind)
    report(
        metric="gpt2_xl_train_tokens_per_sec_per_chip",
        value=round(tok_s, 1), unit="tokens/s/chip",
        extra={"mfu": round(mfu, 4), "params_b": round(cfg.n_params / 1e9, 2),
               "batch": B, "seq": S, "optimizer": "adafactor",
               "master_dtype": "bfloat16",
               "step_ms": round(dt * 1000, 1)},
    )


def bench_long_seq_attention(seq: int):
    # Chained-fori_loop protocol (see scripts/bench_flash.py docstring).
    from scripts.bench_flash import bench_flash_grad

    ms, tf, pct = bench_flash_grad(seq, 1024, 1024)
    report(
        metric=f"flash_attention_s{seq}_fwd_bwd",
        value=round(tf, 2), unit="TFLOP/s",
        extra={"seq": seq, "heads": 16, "d_head": 64,
               "ms": round(ms, 2), "pct_peak": round(pct, 1),
               "block_q": 1024, "block_k": 1024},
    )


def bench_long_ctx_train():
    """Full gpt2-large training step at 4k context (remat + flash)."""
    import jax
    import optax

    from ray_tpu.models import gpt2_large, init_params, make_train_step

    # remat_policy="attn" saves flash's (out, lse) so backward skips the
    # VPU-bound forward rerun — at 4k attention dominates, worth ~14% MFU
    # (0.408 -> 0.465 measured r4); fits comfortably at B=2.
    B, S = 2, 4096
    cfg = gpt2_large(max_seq=S, attn_impl="flash", remat=True,
                     remat_policy="attn")
    params = jax.jit(lambda key: init_params(key, cfg))(jax.random.PRNGKey(0))
    opt = optax.adamw(3e-4, weight_decay=0.1)
    opt_state = jax.jit(opt.init)(params)
    step = jax.jit(make_train_step(cfg, opt), donate_argnums=(0,))
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (B, S + 1), 0, cfg.vocab_size
    )
    state = (params, opt_state)
    for _ in range(2):
        state, metrics = step(state, {"tokens": tokens})
    _ = float(metrics["loss"])
    n = 6
    t0 = time.perf_counter()
    for _ in range(n):
        state, metrics = step(state, {"tokens": tokens})
    _ = float(metrics["loss"])
    dt = (time.perf_counter() - t0) / n
    tok_s = B * S / dt
    mfu = cfg.flops_per_token(S) * tok_s / peak_flops_per_chip(jax.devices()[0].device_kind)
    report(
        metric="gpt2_large_s4096_train_tokens_per_sec_per_chip",
        value=round(tok_s, 1), unit="tokens/s/chip",
        extra={"mfu": round(mfu, 4), "batch": B, "seq": S,
               "step_ms": round(dt * 1000, 1)},
    )


def bench_ring_16k_functional():
    """16k context via RING attention on the 8-way host mesh: the per-shard
    flash kernel sees 2048 tokens — the production path for 16k+ sequences
    (single-chip full attention at 16k exceeds the kernel's VMEM window by
    design; SP exists so no chip ever holds the full context)."""
    import subprocess
    import sys as _sys

    code = """
import os, time, json
import jax
import numpy as np
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from ray_tpu.ops.attention import ring_attention, attention_reference
from ray_tpu.parallel import make_mesh, shard_fn
mesh = make_mesh(sp=8)
B, H, S, D = 1, 4, 16384, 32
q = jax.random.normal(jax.random.PRNGKey(0), (B, H, S, D), jnp.float32)
k = jax.random.normal(jax.random.PRNGKey(1), (B, H, S, D), jnp.float32)
v = jax.random.normal(jax.random.PRNGKey(2), (B, H, S, D), jnp.float32)
import functools
fn = jax.jit(shard_fn(
    functools.partial(ring_attention, axis="sp", causal=True),
    mesh,
    in_specs=(P(None, None, "sp", None),) * 3,
    out_specs=P(None, None, "sp", None),
))
out = fn(q, k, v); jax.block_until_ready(out)
t0 = time.perf_counter(); out = fn(q, k, v); jax.block_until_ready(out)
dt = time.perf_counter() - t0
ref = attention_reference(q[:, :, :2048], k[:, :, :2048], v[:, :, :2048], True,
                          1.0 / (D ** 0.5))
ok = bool(jnp.allclose(out[:, :, :2048], ref, atol=2e-2))
print(json.dumps({"metric": "ring_attention_s16384_8shard",
                  "value": round(dt * 1000, 1), "unit": "ms (8-way host mesh)",
                  "extra": {"seq": 16384, "per_shard_seq": 2048,
                            "matches_reference_prefix": ok}}))
"""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
    out = subprocess.run(
        [_sys.executable, "-c", code], env=env, capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=600,
    )
    for line in out.stdout.splitlines():
        if line.startswith("{"):
            print(line, flush=True)


def main():
    bench_xl()
    bench_long_ctx_train()
    # The r4 streamed-KV kernel holds O(block) in VMEM, so single-chip
    # full attention runs at 16k+ (the r3 whole-KV layout capped at 8k).
    bench_long_seq_attention(8192)
    bench_long_seq_attention(16384)
    bench_ring_16k_functional()


if __name__ == "__main__":
    sys.exit(main())
