"""Device-feed overhead: Data.iter_jax_batches vs a resident batch.

Verdict-r3 item 10 (reference prefetch contract:
`python/ray/data/_internal/block_batching/iter_batches.py` — batches are
formatted + pinned in background threads so the trainer never waits on the
input pipeline). Here the equivalent is `iter_jax_batches`: collate +
`jax.device_put` run in the prefetch thread, double-buffered ahead of the
consumer, so the async dispatch of step N overlaps the H2D copy of batch N+1.

Measures the SAME train step as bench.py (gpt2-large, B=12, S=1024 on the
real chip) two ways:
  resident — one device batch reused every step (pure compute, bench.py's
             number);
  fed      — every step's batch pulled from a ray_tpu Dataset through
             iter_jax_batches.
Prints one JSON line with both step times and the feed overhead fraction
(target <5%).

Timing follows scripts/bench_protocol.md: chained dispatch, one host
transfer of the loss at the end fences the stream.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    small = bool(os.environ.get("RAY_TPU_BENCH_SMALL"))
    if small:
        os.environ["JAX_PLATFORMS"] = "cpu"  # logic smoke; read at jax import
    import jax
    import numpy as np
    import optax

    import ray_tpu
    import ray_tpu.data  # noqa: F401 — attribute registration
    from ray_tpu.models import GPTConfig, gpt2_large, init_params, make_train_step
    if small:  # logic smoke on CPU
        B, S = 4, 64
        cfg = GPTConfig(
            vocab_size=256, n_layers=2, d_model=64, n_heads=2, d_head=32,
            d_mlp=128, max_seq=S, attn_impl="ref", remat=False,
        )
        n_steps = 4
    else:
        B, S = 12, 1024
        cfg = gpt2_large(max_seq=S, attn_impl="flash", remat=True)
        n_steps = 10

    params = jax.jit(lambda key: init_params(key, cfg))(jax.random.PRNGKey(0))
    opt = optax.adamw(3e-4, weight_decay=0.1)
    state = (params, opt.init(params))
    step = jax.jit(make_train_step(cfg, opt), donate_argnums=(0,))

    rng = np.random.default_rng(0)
    rows = rng.integers(0, cfg.vocab_size, (B * (n_steps + 2), S + 1), dtype=np.int32)

    # ----------------------------------------------------------- resident
    resident = {"tokens": jax.device_put(rows[:B])}
    for _ in range(2):
        state, metrics = step(state, resident)
    _ = float(metrics["loss"])
    t0 = time.perf_counter()
    for _ in range(n_steps):
        state, metrics = step(state, resident)
    _ = float(metrics["loss"])
    dt_resident = (time.perf_counter() - t0) / n_steps

    # ---------------------------------------------------------------- fed
    # local_mode: blocks are served in-process, so the measurement isolates
    # the iterator's collate+device_put pipeline (what this bench is about),
    # not the 1-vCPU box's scheduler noise.
    ray_tpu.init(local_mode=True, ignore_reinit_error=True)
    ds = ray_tpu.data.from_numpy(rows)
    it = ds.iter_jax_batches(batch_size=B, drop_last=True)
    batches = ({"tokens": b["data"]} for b in it)
    for _ in range(2):  # warmup steps from the fed path too
        state, metrics = step(state, next(batches))
    _ = float(metrics["loss"])
    t0 = time.perf_counter()
    for _ in range(n_steps):
        state, metrics = step(state, next(batches))
    _ = float(metrics["loss"])
    dt_fed = (time.perf_counter() - t0) / n_steps
    ray_tpu.shutdown()

    overhead = (dt_fed - dt_resident) / dt_resident
    print(
        json.dumps(
            {
                "metric": "data_feed_overhead_frac",
                "value": round(overhead, 4),
                "unit": "fraction of step time",
                "vs_baseline": min(round(0.05 / max(overhead, 5e-4), 2), 100.0),
                "extra": {
                    "step_ms_resident": round(dt_resident * 1000, 2),
                    "step_ms_fed": round(dt_fed * 1000, 2),
                    "batch": B,
                    "seq": S,
                    "target": "<0.05",
                },
            }
        )
    )


if __name__ == "__main__":
    sys.exit(main())
