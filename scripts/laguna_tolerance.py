"""The readings behind `token_tolerance` of `laguna-xs2`
(`benchmarks/configs/laguna-xs2.json`), taken on the chip at the published
widths, in one process: `python3 -m scripts.laguna_tolerance [--seeds
5000000001,5000000002] [--parts wrong,float8,growth]`.

Every reading is the number the benchmark itself would print:
`benchmarks.runners.serve.BenchReplica.bench_check_tokens`, the harness's own
function, called on a stand-in that holds what it reads of a replica (the
parameter tree and `generate`), with the cell's own engine options, prompt
length (5,000 tokens: ten chunks, ten windows deep, past YaRN's 4,096 original
positions, the full layers' tables past one tile) and count of new tokens. For
each seed:

- `sound`: the engine's greedy tokens (chunked paged prefill over five block
  tables, then paged decode) held to the plain float32 reference;
- `wrong`: the same engine held to five WRONG references, which a sound
  program must fail: the window one block too wide, the two kinds' rotary
  tables swapped, the gate left out, the shared expert left out, top-7 for
  top-8;
- `float8`: the engine serving the weights rounded to float8's mantissa
  (e4m3: three bits; the nearest precision below the bfloat16 the
  configuration states), held to the reference with the weights as they are;
- `growth`: how far a perturbation of 1e-3 at the embedding has grown at the
  float32 reference's logits (`smallthinker_tolerance.growth`).

On the CPU (`--rehearse`) the same at the configuration's tiny preset: control
flow only. On the CPU WITHOUT it and with `--sizes hidden_size=512,...` (a
quarter of the widths) the gains are settled (`--init-gains`)."""

from __future__ import annotations

import sys

from .smallthinker_tolerance import readings


def main(argv=None) -> int:
    return readings(
        "laguna-xs2",
        lambda m, opts: {
            "window_one_block_wide": {"window": m["window"] + opts.block_size},
            "rotary_tables_swapped": {"rope_swapped": True},
            "no_gate": {"gate": False},
            "no_shared_expert": {"shared_expert": False},
            "top_k_minus_one": {"top_k": m["top_k"] - 1},
        },
        lambda stats: {"window_blocks_released": stats["window_blocks_released"]},
        argv, __doc__)


if __name__ == "__main__":
    sys.exit(main())
