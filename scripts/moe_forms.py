"""Time the two forms of the dropless expert layer (`ops/moe.py`
`dropless_experts`: dense over every held expert, the reference, or the
assignments grouped by expert into row tiles, what every served step takes) on
the chip, at a preset's widths, inside a scan over the layers as the paged
programs run them: `python3 -m scripts.moe_forms [--model
smallthinker-21b-a3b | ax-k1] [--n-layers 12] [--held 0:12] [--tokens
1,2,4,8,16,64,512]` (`--n-layers`: the EXPERT layers; `--held first:count`: the
range of the router's experts this chip holds, all of them if not given).

For each token count: milliseconds a pass over all layers in each form, the
share of the HBM roofline of reading the experts that were chosen (819 GB/s,
v5e), and for the grouped form the rows its tiles compute against the rows
the routing wants (the assignments on held experts). That the served programs
take the grouped form at every token count was set from this table (PERF.md
§6, PR 35 and PR 39; a third form, a loop over the chosen experts, was timed
here until PR 39 and lost at every count). A chip run or nothing: on the CPU
it prints counts only."""

from __future__ import annotations

import argparse
import json
import sys
import time

FORMS = ("dense", "grouped")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--model", default="smallthinker-21b-a3b")
    ap.add_argument("--n-layers", type=int, default=12)
    ap.add_argument("--held", default="")
    ap.add_argument("--tokens", default="1,2,4,8,16,64,512")
    ap.add_argument("--forms", default=",".join(FORMS))
    ap.add_argument("--reps", type=int, default=20)
    a = ap.parse_args()
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.gpt import CONFIGS
    from ray_tpu.ops import moe

    cfg = CONFIGS[a.model]()
    L, X, D, F, k = a.n_layers, cfg.moe_experts, cfg.d_model, cfg.d_mlp, cfg.moe_top_k
    first, held = (int(v) for v in a.held.split(":")) if a.held else (0, X)
    dev = jax.devices()[0]
    on_chip = dev.platform == "tpu"
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    mk = jax.jit(lambda key, shape: (jax.random.normal(key, shape, jnp.float32) * 0.02
                                     ).astype(jnp.bfloat16), static_argnums=1)
    stacks = (mk(keys[0], (L, held, D, F)), mk(keys[1], (L, held, D, F)),
              mk(keys[2], (L, held, F, D)))
    router = mk(keys[3], (L, D, X))

    def make(n, form):
        cut = form == "dense"       # the scan cuts a layer's experts out of the stacks

        def run(x, stacks, router):
            def layer(carry, inp):
                x, touched, wanted, tiles = carry
                l, r, sl = inp
                logits = x.astype(jnp.float32) @ r.astype(jnp.float32)
                idx, w = moe.dropless_route(logits, k, cfg.moe_scoring, cfg.moe_route_scale)
                combine = moe.dropless_combine(idx, w, X)[:, first:first + held]
                y = moe.dropless_experts(
                    x, combine, *(sl if cut else stacks), cfg.activation,
                    layer=None if cut else l, grouped_k=k if form == "grouped" else 0)
                if form == "grouped":
                    tiles += moe.dropless_groups(combine, k, moe.GROUP_ROWS)[3]
                return (x + y, touched + moe.dropless_load(combine)[0],
                        wanted + (combine > 0).sum(), tiles), None

            xs = (jnp.arange(L), router, stacks if cut else None)
            (x, touched, wanted, tiles), _ = jax.lax.scan(
                layer, (x, jnp.float32(0), jnp.int32(0), jnp.int32(0)), xs)
            return x, touched / L, wanted / L, tiles * moe.GROUP_ROWS / L

        return jax.jit(run)

    rows = []
    for n in (int(t) for t in a.tokens.split(",")):
        x = mk(jax.random.PRNGKey(n), (n, D)) * 50
        row = {"tokens": n}
        for form in a.forms.split(","):
            fn = make(n, form)
            out = fn(x, stacks, router)
            jax.block_until_ready(out)
            t = time.perf_counter()
            for _ in range(a.reps if on_chip else 1):
                out = fn(x, stacks, router)
            jax.block_until_ready(out)
            ms = 1e3 * (time.perf_counter() - t) / (a.reps if on_chip else 1)
            touched = float(out[1])
            row["experts_touched"] = touched
            row["rows_wanted"] = float(out[2])
            if form == "grouped":
                row["rows_computed"] = float(out[3])
            if on_chip:
                row[form + "_ms"] = ms
                row[form + "_roofline_of_touched"] = (
                    100 * L * touched * 3 * D * F * 2 / 819e9 / (ms * 1e-3))
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({"device": {"platform": dev.platform, "kind": dev.device_kind},
                      "model": a.model, "n_layers": L, "held": [first, held],
                      "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
