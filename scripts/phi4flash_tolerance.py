"""The readings behind `token_tolerance` of `phi4-mini-flash`
(`benchmarks/configs/phi4-mini-flash.json`), taken on the chip at the published
widths, in one process: `python3 -m scripts.phi4flash_tolerance [--seeds
4000000001,4000000002] [--parts wrong,float8,faults,growth] [--check
prompt:new --curve 32,64,128]`.

Every reading is the number the benchmark itself would print:
`benchmarks.runners.serve.BenchReplica.bench_check_tokens`, the harness's own
function, called on a stand-in that holds what it reads of a replica (the
parameter tree and `generate`), with the cell's own engine options, prompt
length (three chunks, the last one padded) and count of new tokens
(`scripts/smallthinker_tolerance.py` `readings`). For each seed:

- `sound`: the engine's greedy tokens (chunked paged prefill with the
  cross-decoder on one token a chunk, over the state slots and the nine tables,
  then paged decode) held to the plain float32 reference's full forward pass;
- `wrong`: the same engine held to WRONG references, which a sound program must
  fail: `lambda` left out (P1 V alone), the pair's RMSNorm left out, the factor
  (1 - lambda_init) left out, one lambda_init for all layers, the window a block
  too wide, `m` taken after the gate, `m` without the skip term D c, the memory
  units fed their own layer's input in `m`'s place, the cross layers reading the
  last WINDOW layer's rows, the scan's state zeroed at every chunk boundary, the
  convolution's tail zeroed at every chunk boundary, LayerNorm's bias left out,
  the state held in bfloat16;
- `faults`: a WRONG program held to the right reference: the cross-decoder run
  on a chunk's last SLOT, not its last real token;
- `float8`: the engine serving the weights rounded to float8's mantissa (e4m3;
  the nearest precision below the bfloat16 the configuration states), held to
  the reference with the weights as they are.

On the CPU (`--rehearse`) the same at the configuration's tiny preset: control
flow only. On the CPU WITHOUT it and with `--sizes
hidden_size=640,num_attention_heads=10,num_key_value_heads=4,intermediate_size=2560`
`--parts growth` says how far a rounding grows through the layers at a quarter
of the widths: gains are settled there, not on the chip."""

from __future__ import annotations

import contextlib
import sys

from .smallthinker_tolerance import readings


@contextlib.contextmanager
def cross_decoder_on_the_last_slot():
    """`models/gpt.py` `_sambay_paged` with a chunk's cross-decoder run on the
    chunk's last slot, whatever its real length; the engine's programs are
    traced anew inside and outside."""
    import jax.numpy as jnp

    from ray_tpu.models import gpt
    from ray_tpu.serve.engine import engine

    sound = gpt._sambay_paged

    def wrong(params, tokens, pos, valid, tables, kv, cfg, slots, last=None):
        if last is not None:
            last = jnp.full_like(last, tokens.shape[1] - 1)
        return sound(params, tokens, pos, valid, tables, kv, cfg, slots, last)

    gpt._sambay_paged = wrong
    engine._JITS = None
    try:
        yield
    finally:
        gpt._sambay_paged = sound
        engine._JITS = None


def main(argv=None) -> int:
    return readings(
        "phi4-mini-flash",
        lambda m, opts: {
            "no_lambda": {"no_lambda": True},
            "no_subln": {"no_subln": True},
            "no_lambda_scale": {"no_lambda_scale": True},
            "one_lambda_init": {"one_lambda_init": True},
            "window_one_block_wide": {"window_extra": opts.block_size},
            "m_after_gate": {"m_after_gate": True},
            "m_without_skip": {"m_without_skip": True},
            "gmu_own_input": {"gmu_own_input": True},
            "cross_reads_window_rows": {"cross_reads_pair": m["n_layers"] // 4 - 1},
            "state_zeroed_at_chunk_edges": {"state_reset_every": opts.prefill_chunk_tokens},
            "tail_zeroed_at_chunk_edges": {"tail_reset_every": opts.prefill_chunk_tokens},
            "no_ln_bias": {"no_ln_bias": True},
            "state_in_bfloat16": {"state_bf16": True},
        },
        lambda stats: {"ssm_tokens": [stats["ssm_tokens_masked"], stats["ssm_tokens_scanned"]],
                       "cross_decoder_tokens": [stats["cross_decoder_tokens"],
                                                stats["prefill_tokens"]],
                       "window_blocks_released": stats["window_blocks_released"]},
        argv, __doc__,
        faults={"cross_decoder_on_the_last_slot": cross_decoder_on_the_last_slot})


if __name__ == "__main__":
    sys.exit(main())
