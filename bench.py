"""Headline benchmark: GPT training throughput on the local TPU chip.

Prints ONE JSON line:
    {"metric": ..., "value": tokens/s/chip, "unit": ..., "vs_baseline": ...}

vs_baseline = achieved MFU / 0.40 — the north-star target from BASELINE.md
(GPT-J pretraining ≥40% MFU through the Train API). The model here is the
largest GPT-2-family config that trains comfortably on one v5e chip; the
per-chip MFU is the quantity the multi-chip sharding is designed to hold.

A run that finds no TPU fails. `RAY_TPU_BENCH_SMALL=1` is a logic check of
the same code at a tiny size on any backend; it prints counts, never a rate.
"""

from __future__ import annotations

import json
import os
import sys
import time

# bf16 peak FLOP/s of one chip, keyed by jax's `device_kind`. Source: Google
# Cloud documentation, "TPU v5e" (197 TFLOP/s bf16). A device that is not
# listed is an error, never a default.
PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12}


def peak_flops_per_chip(device_kind: str) -> float:
    try:
        return PEAK_BF16_FLOPS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak FLOP/s recorded for device_kind {device_kind!r}; add it "
            "to PEAK_BF16_FLOPS with its source"
        ) from None


def main():
    from ray_tpu.util.accelerators.tpu import place_compile_cache

    place_compile_cache()  # read by jax at import

    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models import gpt2_large, init_params, make_train_step
    from ray_tpu.ops.attention import flash_kernels_in

    dev = jax.devices()[0]
    small = bool(os.environ.get("RAY_TPU_BENCH_SMALL"))
    if small:
        from ray_tpu.models import GPTConfig

        B, S = 2, 128
        cfg = GPTConfig(
            vocab_size=512, n_layers=2, d_model=128, n_heads=4, d_head=32,
            d_mlp=256, max_seq=S, attn_impl="ref", remat=False,
        )
    else:
        if dev.platform != "tpu":
            raise SystemExit(
                f"bench.py measures a TPU chip; JAX found only {dev.platform!r}"
            )
        peak = peak_flops_per_chip(dev.device_kind)
        # gpt2_large w/ flash blocks (1024,1024). bf16 adam moments
        # (mu_dtype) free 1.5 GB of HBM, which unlocks remat_policy="attn"
        # (attention fwd runs ONCE per step — its residuals are saved, the
        # rest of the block remats). B=14 also fits on the v5e under
        # jax 0.9.0; B=16 fails to compile (CHANGES.md, PR 21).
        B, S = 13, 1024
        cfg = gpt2_large(
            max_seq=S, attn_impl="flash", remat=True, remat_policy="attn"
        )

    # Initialize on-device (jit) — host-side random init of 774M params
    # costs tens of seconds.
    params = jax.jit(lambda key: init_params(key, cfg))(jax.random.PRNGKey(0))
    # bf16 first moments: half the m-state HBM (and its read-modify-write
    # traffic) for negligible update error — the variance stays f32.
    opt = optax.adamw(3e-4, weight_decay=0.1, mu_dtype=jnp.bfloat16)
    state = (params, opt.init(params))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S + 1), 0, cfg.vocab_size)
    batch = {"tokens": tokens}

    t0 = time.perf_counter()
    step = (
        jax.jit(make_train_step(cfg, opt), donate_argnums=(0,))
        .lower(state, batch)
        .compile()
    )
    if not small:
        kernels = flash_kernels_in(step.as_text())
        if not all(kernels.values()):
            raise SystemExit(
                f"compiled step lacks Mosaic flash kernels {kernels}: this is "
                "the XLA reference path, not the benchmark"
            )
    for _ in range(2):  # warm-up
        state, metrics = step(state, batch)
    jax.block_until_ready(metrics)
    setup_s = time.perf_counter() - t0

    n_steps = 10
    t0 = time.perf_counter()
    for _ in range(n_steps):
        state, metrics = step(state, batch)
    jax.block_until_ready(metrics)
    dt = (time.perf_counter() - t0) / n_steps

    final_loss = round(float(metrics["loss"]), 3)
    device = {"platform": dev.platform, "device": dev.device_kind,
              "device_count": len(jax.devices())}
    if small:
        print(json.dumps({
            "metric": "train_step_logic_check",
            "steps": n_steps + 2,
            "tokens_per_step": B * S,
            "final_loss": final_loss,
            **device,
        }))
        return

    tok_s = B * S / dt
    mfu = cfg.flops_per_token(S) * tok_s / peak
    print(
        json.dumps(
            {
                "metric": "gpt2_large_train_tokens_per_sec_per_chip",
                "value": round(tok_s, 1),
                "unit": "tokens/s/chip",
                "vs_baseline": round(mfu / 0.40, 3),
                "extra": {
                    "mfu": round(mfu, 4),
                    "step_ms": round(dt * 1000, 2),
                    "setup_s": round(setup_s, 1),
                    "params_m": round(cfg.n_params / 1e6, 1),
                    "batch": B,
                    "seq": S,
                    "final_loss": final_loss,
                    **device,
                    "compile_cache": os.environ["JAX_COMPILATION_CACHE_DIR"],
                },
            }
        )
    )


if __name__ == "__main__":
    sys.exit(main())
