"""Flash-attention kernel block-size sweep on the real TPU.

Repeats the op inside one jit (lax.scan with data dependency) so the
per-dispatch overhead amortizes away. Prints ms/op and achieved TFLOP/s.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import _flash, attention_reference

B, H, S, D = 16, 16, 1024, 64
REPS = 8


def fence(x):
    _ = float(jnp.asarray(x).ravel()[0])


def time_fn(f, *args):
    out = f(*args)
    fence(out)
    t0 = time.perf_counter()
    out = f(*args)
    fence(out)
    return (time.perf_counter() - t0) * 1000


def bench_attn(mode, bq, bk):
    q = jax.random.normal(jax.random.PRNGKey(0), (B, H, S, D), jnp.bfloat16)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, H, S, D), jnp.bfloat16)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, H, S, D), jnp.bfloat16)
    scale = D**-0.5

    if mode == "fwd":
        def one(q):
            return _flash(q, k, v, True, scale, bq, bk)
    elif mode == "ref_fwd":
        def one(q):
            return attention_reference(q, k, v, True, scale)
    elif mode == "bwd":
        def one(q):
            return jax.grad(lambda q_: _flash(q_, k, v, True, scale, bq, bk).astype(jnp.float32).sum())(q)
    else:  # ref_bwd
        def one(q):
            return jax.grad(lambda q_: attention_reference(q_, k, v, True, scale).astype(jnp.float32).sum())(q)

    @jax.jit
    def many(q):
        def body(x, _):
            return one(x).astype(jnp.bfloat16), None
        out, _ = jax.lax.scan(body, q, None, length=REPS)
        return out

    ms = time_fn(many, q) / REPS
    # fwd flops (causal): 2 matmuls * B*H*S^2*D * 2 / 2
    flops = 2 * 2 * B * H * S * S * D / 2
    if mode in ("bwd", "ref_bwd"):
        flops *= 3.5  # fwd recompute (custom vjp does not re-run fwd; dq+dkv ~ 2.5x) — rough
    return {"mode": mode, "bq": bq, "bk": bk, "ms": round(ms, 2),
            "tflops": round(flops / (ms / 1000) / 1e12, 1)}


def main():
    for mode in ("fwd", "bwd"):
        for bq, bk in [(128, 128), (256, 256), (256, 512), (512, 512), (512, 1024), (256, 1024), (1024, 1024)]:
            try:
                print(json.dumps(bench_attn(mode, bq, bk)), flush=True)
            except Exception as e:  # noqa: BLE001
                print(json.dumps({"mode": mode, "bq": bq, "bk": bk, "error": repr(e)[:150]}), flush=True)
    print(json.dumps(bench_attn("ref_fwd", 0, 0)), flush=True)
    print(json.dumps(bench_attn("ref_bwd", 0, 0)), flush=True)


if __name__ == "__main__":
    main()
