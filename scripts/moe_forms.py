"""Time the two forms of the dropless expert layer (`ops/moe.py`
`dropless_experts`: dense over every expert, or a loop over the experts the
step's tokens chose) on the chip, at a preset's widths, inside a scan over
the layers as the paged programs run them: `python3 -m scripts.moe_forms
[--model smallthinker-21b-a3b] [--n-layers 12] [--tokens 1,2,4,8,16,64,512]`.

For each token count: milliseconds a pass over all layers, and the share of
the HBM roofline of reading the experts that were chosen (819 GB/s, v5e).
The rule in `models/gpt.py` `_dropless_mlp` (loop while tokens x top_k <
experts) was set from this table (PERF.md §6, PR 28). A chip run or nothing:
on the CPU it prints counts only."""

from __future__ import annotations

import argparse
import json
import sys
import time


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--model", default="smallthinker-21b-a3b")
    ap.add_argument("--n-layers", type=int, default=12)
    ap.add_argument("--tokens", default="1,2,4,8,16,64,512")
    ap.add_argument("--reps", type=int, default=20)
    a = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.gpt import CONFIGS
    from ray_tpu.ops import moe

    cfg = CONFIGS[a.model](n_layers=a.n_layers)
    L, X, D, F, k = cfg.n_layers, cfg.moe_experts, cfg.d_model, cfg.d_mlp, cfg.moe_top_k
    dev = jax.devices()[0]
    on_chip = dev.platform == "tpu"
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    mk = jax.jit(lambda key, shape: (jax.random.normal(key, shape, jnp.float32) * 0.02
                                     ).astype(jnp.bfloat16), static_argnums=1)
    stacks = (mk(keys[0], (L, X, D, F)), mk(keys[1], (L, X, D, F)), mk(keys[2], (L, X, F, D)))
    router = mk(keys[3], (L, D, X))

    def make(n, loop):
        def run(x, stacks, router):
            def layer(carry, inp):
                x, touched = carry
                l, r, sl = inp
                logits = x.astype(jnp.float32) @ r.astype(jnp.float32)
                idx, w = moe.dropless_route(logits, k)
                combine = moe.dropless_combine(idx, w, X)
                if loop:
                    y = moe.dropless_experts(x, combine, *stacks, cfg.activation,
                                             layer=l, touched_k=k)
                else:
                    y = moe.dropless_experts(x, combine, *sl, cfg.activation)
                return (x + y, touched + moe.dropless_load(combine)[0]), None

            xs = (jnp.arange(L), router, None if loop else stacks)
            (x, touched), _ = jax.lax.scan(layer, (x, jnp.float32(0)), xs)
            return x, touched / L

        return jax.jit(run)

    rows = []
    for n in (int(t) for t in a.tokens.split(",")):
        x = mk(jax.random.PRNGKey(n), (n, D)) * 50
        row = {"tokens": n}
        for name, loop in (("dense", False), ("loop", True)):
            if loop and n * k >= 4 * X:
                continue
            fn = make(n, loop)
            out = fn(x, stacks, router)
            jax.block_until_ready(out)
            t = time.perf_counter()
            for _ in range(a.reps if on_chip else 1):
                out = fn(x, stacks, router)
            jax.block_until_ready(out)
            ms = 1e3 * (time.perf_counter() - t) / (a.reps if on_chip else 1)
            touched = float(out[1])
            row["experts_touched"] = touched
            if on_chip:
                row[name + "_ms"] = ms
                row[name + "_roofline_of_touched"] = (
                    100 * L * touched * 3 * D * F * 2 / 819e9 / (ms * 1e-3))
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({"device": {"platform": dev.platform, "kind": dev.device_kind},
                      "model": a.model, "n_layers": L, "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
