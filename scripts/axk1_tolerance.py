"""The readings behind `token_tolerance` of `ax-k1`
(`benchmarks/configs/ax-k1.json`), taken on the chip at the published widths,
in one process: `python3 -m scripts.axk1_tolerance [--seeds
3400000001,3400000002] [--parts wrong,float8]`.

Every reading is the number the benchmark itself would print:
`benchmarks.runners.serve.BenchReplica.bench_check_tokens`, the harness's own
function, called on a stand-in that holds what it reads of a replica (the
parameter tree and `generate`), with the cell's own engine options, prompt
length and count of new tokens. For each seed:

- `sound`: the engine's greedy tokens (chunked paged prefill over the latent
  pool, then paged decode) held to the plain float32 reference;
- `wrong`: the same engine held to five WRONG references, which a sound
  program must fail: a softmax over the router's logits for the sigmoid, no
  mscale in the attention scale, no shared expert, the held range of experts
  shifted by one, the rotation on the wrong 64 columns;
- `float8`: the engine serving the weights rounded to float8's mantissa
  (e4m3: three bits; the nearest precision below the bfloat16 the
  configuration states), held to the reference with the weights as they are.

On the CPU (`--rehearse`) the same at the configuration's tiny preset:
control flow only."""

from __future__ import annotations

import sys

from .smallthinker_tolerance import readings

WRONG = {
    "softmax_for_sigmoid": {"scoring": "softmax"},
    "no_mscale_in_the_scale": {"attn_mscale": False},
    "no_shared_expert": {"shared_expert": False},
    "held_range_shifted_by_one": None,          # from the dims: see `main`
    "rotary_on_the_wrong_columns": {"rope_cols": "nope"},
}


def main(argv=None) -> int:
    return readings(
        "ax-k1",
        lambda m, opts: {
            **WRONG, "held_range_shifted_by_one": {"held_start": m["held_start"] + 1}},
        lambda stats: {"moe_assign": [stats["moe_assign_held"], stats["moe_assign_total"]]},
        argv, __doc__)


if __name__ == "__main__":
    sys.exit(main())
