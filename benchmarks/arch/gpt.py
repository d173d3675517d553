"""The GPT-2 / GPT-J family: dense pre-LayerNorm blocks, learned or partly
rotary positions, tied or untied head, one block or attention and MLP in
parallel. Sizes from the published keys, the program model they select, the
plain reference (`benchmarks/reference.py`), and the operations and bytes."""

from __future__ import annotations

from .. import peaks


def dims(config: dict, rehearse: bool) -> dict:
    """The published keys of a GPT-2/GPT-J `config.json` as the sizes the
    program's `GPTConfig` and the arithmetic below use."""
    c = dict(config)
    if rehearse:
        c.update(config["rehearsal"]["sizes"])
    pad = config["assumed"]["vocab_pad_multiple"]
    E, H = c["n_embd"], c["n_head"]
    return {
        "n_layers": c["n_layer"], "d_model": E, "n_heads": H,
        "d_head": E // H, "d_mlp": c.get("n_inner") or 4 * E,
        "max_seq": c["n_positions"],
        "vocab_size": -(-c["vocab_size"] // pad) * pad,
        "pos": "rotary" if c.get("rotary_dim") else "learned",
        "rotary_dim": c.get("rotary_dim") or 64,
        "parallel_block": bool(config["assumed"].get("parallel_block", False)),
        "tie_embeddings": bool(c.get("tie_word_embeddings", True)),
    }


def program(config: dict, m: dict) -> tuple:
    """(name in `ray_tpu.models.gpt.CONFIGS`, overrides): the sizes above
    are `GPTConfig`'s own field names."""
    return config["program_model"], dict(m)


def make_logits(m: dict):
    from .. import reference

    return reference.make_logits(m)


def make_loss(m: dict):
    from .. import reference

    return reference.make_loss(m)


def n_matmul_params(m: dict) -> int:
    """Parameters that take part in a matrix multiplication per token: the
    layers' four projections and the output head (tied or not). Embedding
    look-ups and norms cost no matmul FLOPs."""
    E, L, F, V = m["d_model"], m["n_layers"], m["d_mlp"], m["vocab_size"]
    Hd = m["n_heads"] * m["d_head"]
    per_layer = E * 3 * Hd + Hd * E + E * F + F * E
    return L * per_layer + E * V


def train_flops_per_token(m: dict, seq: int) -> float:
    """FLOPs the forward and backward passes REQUIRE per trained token:
    6 per matmul parameter, plus causal attention (QK^T and PV, forward 1x +
    backward 2x, half the square). Recomputed operations are not counted."""
    attn = 6.0 * m["n_layers"] * m["n_heads"] * m["d_head"] * seq  # 12*S*Hd/2
    return 6.0 * n_matmul_params(m) + attn


def weight_bytes(m: dict, bytes_per_param: int = 4) -> int:
    """Bytes of weights a decode step streams (f32 masters as the engine
    holds them; tied head counted once)."""
    E, V = m["d_model"], m["vocab_size"]
    n = n_matmul_params(m) + (0 if m["tie_embeddings"] else E * V)
    return n * bytes_per_param


def kv_block_bytes(m: dict, block_size: int) -> int:
    """One block of the paged pool: K and V of every layer and head, bf16."""
    return 2 * m["n_layers"] * m["n_heads"] * m["d_head"] * block_size * 2


def kernel_costs(m: dict, batch: int, seq: int, chips: int) -> dict:
    """The Mosaic kernels the train step calls, each once a layer on
    q, k, v of [batch x heads / chips, seq, head] (bf16), causal."""
    bh = batch * m["n_heads"] // chips
    return {"flash_fwd": peaks.flash_fwd_cost(bh, seq, m["d_head"]),
            "flash_bwd_dq": peaks.flash_bwd_dq_cost(bh, seq, m["d_head"]),
            "flash_bwd_dkv": peaks.flash_bwd_dkv_cost(bh, seq, m["d_head"])}
