"""The readings behind `token_tolerance` of `nemotron3-nano-30b-a3b`
(`benchmarks/configs/nemotron3-nano-30b-a3b.json`), taken on the chip at the
published widths, in one process: `python3 -m scripts.nemotron_h_tolerance
[--seeds 4000000001,4000000002] [--parts wrong,float8,faults]`.

Every reading is the number the benchmark itself would print:
`benchmarks.runners.serve.BenchReplica.bench_check_tokens`, the harness's own
function, called on a stand-in that holds what it reads of a replica (the
parameter tree and `generate`), with the cell's own engine options, prompt
length (three chunks, the last one padded) and count of new tokens. For each
seed:

- `sound`: the engine's greedy tokens (chunked paged prefill over the state
  slots and the two-block pool, then paged decode) held to the plain float32
  reference;
- `wrong`: the same engine held to ten WRONG references, which a sound program
  must fail: the state zeroed at every chunk boundary of the engine, the
  convolution's tail zeroed there, the state held in bfloat16, `D x` left out,
  the gate after the norm, one group for the gated norm in place of 8, relu for
  relu^2, the selection bias left out, top-5 for top-6, a rotary term put in;
- `faults`: a WRONG program held to the right reference: the scan and the
  convolution run over a chunk's padding as over its tokens (the mask
  dropped), so the padding advances the state the decode steps continue;
- `float8`: the engine serving the weights rounded to float8's mantissa
  (e4m3: three bits; the nearest precision below the bfloat16 the
  configuration states), held to the reference with the weights as they are.

On the CPU (`--rehearse`) the same at the configuration's tiny preset: control
flow only. On the CPU WITHOUT it and with `--sizes
hidden_size=672,num_attention_heads=8,mamba_num_heads=16,moe_intermediate_size=464,moe_shared_expert_intermediate_size=928
[--check 700:48] [--init-gains name=gain,...] --parts wrong,float8,growth` (a
quarter of the widths, all 13 blocks, all 64 held experts) gains and lengths are
settled before the chip is asked (ROADMAP D18d)."""

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

    scan, conv = ssm.ssd_scan, ssm.causal_conv
    ssm.ssd_scan = lambda x, dt, A, B, C, s0, valid, *a, **kw: scan(
        x, dt, A, B, C, s0, jnp.ones_like(valid), *a, **kw)
    ssm.causal_conv = lambda u, tail, w, b, valid: conv(
        u, tail, w, b, jnp.ones_like(valid))
    engine._JITS = None
    try:
        yield
    finally:
        ssm.ssd_scan, ssm.causal_conv = scan, conv
        engine._JITS = None


def main(argv=None) -> int:
    return readings(
        "nemotron3-nano-30b-a3b",
        lambda m, opts: {
            "state_zeroed_at_chunk_edges": {"state_reset_every": opts.prefill_chunk_tokens},
            "tail_zeroed_at_chunk_edges": {"tail_reset_every": opts.prefill_chunk_tokens},
            "state_in_bfloat16": {"state_bf16": True},
            "no_skip_term": {"no_skip": True},
            "gate_after_norm": {"gate_after_norm": True},
            "one_norm_group": {"norm_groups": 1},
            "relu_for_relu2": {"expert_act": "relu"},
            "no_selection_bias": {"no_select_bias": True},
            "top_k_minus_one": {"top_k_wrong": m["top_k"] - 1},
            "rotary_put_in": {"rotary": 10000.0},
        },
        lambda stats: {"ssm_tokens": [stats["ssm_tokens_masked"], stats["ssm_tokens_scanned"]],
                       "state_slots_claimed": stats["state_slots_claimed"],
                       "moe_assign": [stats["moe_assign_held"], stats["moe_assign_total"]]},
        argv, __doc__,
        faults={"padding_advances_the_state": padding_advances_the_state})


if __name__ == "__main__":
    sys.exit(main())
