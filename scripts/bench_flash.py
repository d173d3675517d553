"""Standalone flash-attention kernel benchmark (real TPU).

Measurement protocol (see also scripts/bench_protocol.md): each config
runs ONE jitted program containing a `lax.fori_loop` of N chained grad steps
(real data dependency — outputs feed inputs, so XLA cannot DCE or overlap
iterations), fenced by a scalar host read; device ms/iter is the DIFFERENCE
between two chain lengths, which cancels the fixed dispatch+read cost.

Usage: python scripts/bench_flash.py [--seqs 8192,16384] [--sweep]
"""

from __future__ import annotations

import argparse
import json
import sys
import time

sys.path.insert(0, ".")


def chain_ms(make_body, init_args, n1=4, n2=16, reps=2):
    """Device ms/iter of body() via two chained fori_loop lengths."""
    import jax

    ts = {}
    for n in (n1, n2):
        @jax.jit
        def run(args, n=n):
            return jax.lax.fori_loop(0, n, make_body, args)

        out = run(init_args)
        _ = float(jax.tree_util.tree_leaves(out)[0].reshape(-1)[0])
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            out = run(init_args)
            _ = float(jax.tree_util.tree_leaves(out)[0].reshape(-1)[0])
            best = min(best, time.perf_counter() - t0)
        ts[n] = best
    return (ts[n2] - ts[n1]) / (n2 - n1) * 1000


def bench_flash_grad(seq: int, block_q: int, block_k: int,
                     B: int = 1, H: int = 16, D: int = 64):
    import jax
    import jax.numpy as jnp

    from bench import peak_flops_per_chip
    from ray_tpu.ops.attention import flash_attention

    q = jax.random.normal(jax.random.PRNGKey(0), (B, H, seq, D), jnp.bfloat16)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, H, seq, D), jnp.bfloat16)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, H, seq, D), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(
            q, k, v, causal=True, block_q=block_q, block_k=block_k
        ).astype(jnp.float32).sum()

    def body(i, a):
        g = jax.grad(loss, argnums=(0, 1, 2))(*a)
        # Chain: next iteration's inputs depend on this one's grads.
        return (a[0] + g[0] * 1e-6, a[1] + g[1] * 1e-6, a[2] + g[2] * 1e-6)

    # Chain lengths scale inversely with seq so the measured difference
    # stays well above dispatch jitter even at short contexts.
    scale = max(1, 16384 // seq)
    ms = chain_ms(body, (q, k, v), n1=4 * scale, n2=16 * scale)
    # Causal fwd+bwd ≈ 3.5 × (4·B·H·S²·D / 2) MACs→FLOPs.
    flops = 3.5 * 4 * B * H * seq * seq * D / 2
    tf = flops / (ms / 1000) / 1e12
    pct = 100 * tf / (peak_flops_per_chip(jax.devices()[0].device_kind) / 1e12)
    return ms, tf, pct


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seqs", default="8192,16384")
    ap.add_argument("--sweep", action="store_true",
                    help="sweep block sizes instead of the tuned default")
    args = ap.parse_args()
    blocks = (
        [(512, 512), (1024, 512), (512, 1024), (1024, 1024), (2048, 512),
         (256, 512), (512, 256)]
        if args.sweep else [(1024, 1024)]  # kernel default (r5: 60 TFLOP/s,
        # 30.5% of peak at 8k AND 16k; ≥2048 blocks fail to compile on v5e)
    )
    for seq in [int(s) for s in args.seqs.split(",")]:
        for bq, bk in blocks:
            ms, tf, pct = bench_flash_grad(seq, bq, bk)
            print(json.dumps({
                "metric": f"flash_attention_s{seq}_fwd_bwd",
                "value": round(tf, 2), "unit": "TFLOP/s",
                "extra": {"ms": round(ms, 2), "pct_peak": round(pct, 1),
                          "block_q": bq, "block_k": bk},
            }), flush=True)


if __name__ == "__main__":
    main()
