"""The readings behind `token_tolerance` of `qwen3-next-80b-a3b`
(`benchmarks/configs/qwen3-next-80b-a3b.json`), taken on the chip at the published
widths, in one process: `python3 -m scripts.qwen3next_tolerance [--seeds
4000000001,4000000002] [--parts wrong,float8,faults,growth] [--check
prompt:new --curve 32,64,128]`.

Every reading is the number the benchmark itself would print:
`benchmarks.runners.serve.BenchReplica.bench_check_tokens`, the harness's own
function, called on a stand-in that holds what it reads of a replica (the
parameter tree and `generate`), with the cell's own engine options, prompt
length (three chunks, the last one padded, not a multiple of the delta rule's
chunk) and count of new tokens (`scripts/smallthinker_tolerance.py` `readings`).
For each seed:

- `sound`: the engine's greedy tokens (chunked paged prefill over the state slots
  and the block table, the delta rule in its chunk form; then paged decode, the
  rule one token a lane) held to the plain float32 reference's full forward pass,
  whose delta rule is the token recurrence;
- `wrong`: the same engine held to WRONG references, which a sound program must
  fail: the delta term dropped (Mamba-2's update), no decay, beta fixed at 1, q and
  k not normalised, the gate before the delta net's norm, one scalar gate a head
  for the elementwise one, a plain gain for the zero-centred one, the rotary term
  over the whole head, top-9 for top-10, the shared expert ungated, the state in
  bfloat16, the state zeroed at every chunk boundary of the engine, the
  convolution's tail zeroed there;
- `faults`: a WRONG program held to the right reference: padding that advances
  the state and the tail;
- `float8`: the engine serving the weights rounded to float8's mantissa (e4m3;
  the nearest precision below the bfloat16 the configuration states), held to
  the reference with the weights as they are.

On the CPU (`--rehearse`) the same at the configuration's tiny preset: control
flow only. On the CPU WITHOUT it and with `--sizes
hidden_size=512,head_dim=64,linear_num_key_heads=4,linear_num_value_heads=8`
`--parts growth` says how far a rounding grows through the layers at a quarter
of the widths: gains are settled there, not on the chip."""

from __future__ import annotations

import contextlib
import sys

from .smallthinker_tolerance import readings


@contextlib.contextmanager
def padding_advances_the_state():
    """`ops/delta.py` with every token taken for a real one; the engine's
    programs are traced anew inside and outside."""
    import jax.numpy as jnp

    from ray_tpu.ops import delta
    from ray_tpu.serve.engine import engine

    scan, conv = delta.delta_scan, delta.causal_conv
    delta.delta_scan = lambda q, k, v, g, beta, s0, valid, *a, **kw: scan(
        q, k, v, g, beta, s0, jnp.ones_like(valid), *a, **kw)
    delta.causal_conv = lambda u, tail, w, b, valid: conv(
        u, tail, w, b, jnp.ones_like(valid))
    engine._JITS = None
    try:
        yield
    finally:
        delta.delta_scan, delta.causal_conv = scan, conv
        engine._JITS = None


def main(argv=None) -> int:
    return readings(
        "qwen3-next-80b-a3b",
        lambda m, opts: {
            "no_delta_term": {"no_delta": True},
            "no_decay": {"no_decay": True},
            "beta_fixed_at_one": {"beta_one": True},
            "q_and_k_not_normalised": {"no_qk_norm": True},
            "gate_before_the_norm": {"gate_before_norm": True},
            "one_scalar_gate_a_head": {"head_gate_scalar": True},
            "plain_rmsnorm_gain": {"plain_norm": True},
            "rotary_over_the_whole_head": {"rotary_whole": True},
            "top_k_minus_one": {"top_k_wrong": m["top_k"] - 1},
            "shared_expert_ungated": {"shared_ungated": True},
            "state_in_bfloat16": {"state_bf16": True},
            "state_zeroed_at_chunk_edges": {"state_reset_every": opts.prefill_chunk_tokens},
            "tail_zeroed_at_chunk_edges": {"tail_reset_every": opts.prefill_chunk_tokens},
        },
        lambda stats: {"state_tokens": [stats["ssm_tokens_masked"], stats["ssm_tokens_scanned"]],
                       "moe_assign": [stats["moe_assign_held"], stats["moe_assign_total"]]},
        argv, __doc__,
        faults={"padding_advances_the_state": padding_advances_the_state})


if __name__ == "__main__":
    sys.exit(main())
