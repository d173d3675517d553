"""The readings behind `token_tolerance` of `jamba2-3b`
(`benchmarks/configs/jamba2-3b.json`), taken on the chip at the published
widths, in one process: `python3 -m scripts.jamba_tolerance [--seeds
4000000001,4000000002] [--parts wrong,float8,faults]`.

Every reading is the number the benchmark itself would print:
`benchmarks.runners.serve.BenchReplica.bench_check_tokens`, the harness's own
function, called on a stand-in that holds what it reads of a replica (the
parameter tree and `generate`), with the cell's own engine options, prompt
length (three chunks, the last one padded) and count of new tokens. For each
seed:

- `sound`: the engine's greedy tokens (chunked paged prefill over the state
  slots and the two-layer pool, then paged decode) held to the plain float32
  reference;
- `wrong`: the same engine held to four WRONG references, which a sound
  program must fail: the scan's state zeroed at every chunk boundary, the
  convolution's tail zeroed at every chunk boundary, the state held in
  bfloat16, the three inner norms left out;
- `faults`: a WRONG program held to the right reference: the scan and the
  convolution run over a chunk's padding as over its tokens (the mask
  dropped), so 68 padding tokens advance the state the decode steps continue;
- `float8`: the engine serving the weights rounded to float8's mantissa
  (e4m3: three bits; the nearest precision below the bfloat16 the
  configuration states), held to the reference with the weights as they are.

On the CPU (`--rehearse`) the same at the configuration's tiny preset:
control flow only. On the CPU WITHOUT it and with `--sizes
hidden_size=640,num_attention_heads=5,intermediate_size=2048,mamba_dt_rank=40`
(a quarter of the widths, all 28 layers, the whole vocabulary) the readings
come within a fifth of the chip's at two minutes a seed, and `--parts growth`
says how far a rounding grows through the layers: gains are settled there."""

from __future__ import annotations

import contextlib
import sys

from .smallthinker_tolerance import readings


@contextlib.contextmanager
def padding_advances_the_state():
    """`ops/ssm.py` with every token taken for a real one; the engine's
    programs are traced anew inside and outside."""
    import jax.numpy as jnp

    from ray_tpu.ops import ssm
    from ray_tpu.serve.engine import engine

    scan, conv = ssm.selective_scan, ssm.causal_conv
    ssm.selective_scan = lambda d, x, A, B, C, s0, valid, kernel=None: scan(
        d, x, A, B, C, s0, jnp.ones_like(valid), kernel)
    ssm.causal_conv = lambda u, tail, w, b, valid: conv(
        u, tail, w, b, jnp.ones_like(valid))
    engine._JITS = None
    try:
        yield
    finally:
        ssm.selective_scan, ssm.causal_conv = scan, conv
        engine._JITS = None


def main(argv=None) -> int:
    return readings(
        "jamba2-3b",
        lambda m, opts: {
            "state_zeroed_at_chunk_edges": {"state_reset_every": opts.prefill_chunk_tokens},
            "tail_zeroed_at_chunk_edges": {"tail_reset_every": opts.prefill_chunk_tokens},
            "state_in_bfloat16": {"state_bf16": True},
            "no_inner_norms": {"inner_norms": False},
        },
        lambda stats: {"ssm_tokens": [stats["ssm_tokens_masked"], stats["ssm_tokens_scanned"]],
                       "state_slots_claimed": stats["state_slots_claimed"]},
        argv, __doc__,
        faults={"padding_advances_the_state": padding_advances_the_state})


if __name__ == "__main__":
    sys.exit(main())
