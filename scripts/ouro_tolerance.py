"""The readings behind `token_tolerance` of `ouro-2.6b`
(`benchmarks/configs/ouro-2.6b.json`), taken on the chip at the published
widths, in one process: `python3 -m scripts.ouro_tolerance [--seeds
3000000001,3000000002] [--parts wrong,float8]`.

Every reading is the number the benchmark itself would print:
`benchmarks.runners.serve.BenchReplica.bench_check_tokens`, the harness's own
function, called on a stand-in that holds what it reads of a replica (the
parameter tree and `generate`), with the cell's own engine options, prompt
length and count of new tokens. For each seed:

- `sound`: the engine's greedy tokens (chunked paged prefill, then paged
  decode) held to the plain float32 reference;
- `wrong`: the same engine held to five WRONG references, which a sound
  program must fail: three passes for four, every pass attending over the
  first pass's keys and values, no norm between passes, no post-norms, and
  one block of the prompt (its second) unseen by every later query, as under
  a block table with one wrong entry;
- `float8`: the engine serving the weights rounded to float8's mantissa
  (e4m3: three bits; the nearest precision below the bfloat16 the
  configuration states), held to the reference with the weights as they are.

On the CPU (`--rehearse`) the same at the configuration's tiny preset:
control flow only."""

from __future__ import annotations

import sys

from .smallthinker_tolerance import readings


def main(argv=None) -> int:
    return readings(
        "ouro-2.6b",
        lambda m, opts: {
            "three_passes_for_four": {"ut_steps": m["ut_steps"] - 1},
            "every_pass_on_the_first_pass_cache": {"cache_of_pass_one": True},
            "no_norm_between_passes": {"norm_between_passes": False},
            "no_post_norms": {"post_norms": False},
            "second_block_unseen": {"keys_unseen": (opts.block_size, 2 * opts.block_size)},
        },
        lambda stats: {"ut_passes": [stats["ut_passes_run"], stats["ut_passes_full"]]},
        argv, __doc__)


if __name__ == "__main__":
    sys.exit(main())
