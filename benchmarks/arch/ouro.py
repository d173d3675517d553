"""The Ouro family (ByteDance, arXiv:2510.25741; `model_type: "ouro"`): a dense
multi-head block under sandwich norms whose whole layer stack runs
`total_ut_steps` times over the same weights, the final norm closing each
pass and an exit gate read after it. Sizes from the published keys, the
program model they select, the plain reference (`ouro_reference.py`), and the
operations and bytes."""

from __future__ import annotations

BYTES_PER_PARAM = 2     # the published checkpoint and the program's tree: bfloat16


def dims(config: dict, rehearse: bool) -> dict:
    """The published keys of an Ouro `config.json` as sizes."""
    c = dict(config)
    if rehearse:
        c.update(config["rehearsal"]["sizes"])
    return {
        "n_layers": c["num_hidden_layers"], "d_model": c["hidden_size"],
        "n_heads": c["num_attention_heads"], "n_kv_heads": c["num_key_value_heads"],
        "d_head": c["head_dim"], "d_mlp": c["intermediate_size"],
        "max_seq": c["max_position_embeddings"], "vocab_size": c["vocab_size"],
        "rope_theta": float(c["rope_theta"]), "norm_eps": c["rms_norm_eps"],
        "ut_steps": c["total_ut_steps"],
        "exit_threshold": float(c["early_exit_threshold"]),
    }


def program(config: dict, m: dict) -> tuple:
    """(name in `ray_tpu.models.gpt.CONFIGS`, overrides in `GPTConfig`'s own
    field names). A program that lacks the model (a checkout from before the
    PR that brought it) is refused HERE, in the parent process and at once:
    left to the replica's constructor it would fail over and over until the
    deployment's start-up limit. So is an exit threshold under which a token
    would leave early: the program acts on no gate."""
    from ray_tpu.models.gpt import CONFIGS

    if config["program_model"] not in CONFIGS:
        raise SystemExit(
            f"the program has no model {config['program_model']!r} "
            f"(ray_tpu.models.gpt.CONFIGS has {sorted(CONFIGS)}): this "
            "configuration cannot run on this checkout")
    if m["exit_threshold"] != 1.0:
        raise SystemExit(
            f"early_exit_threshold {m['exit_threshold']}: the program runs every "
            "pass for every token, which is the published model at 1.0 only")
    return config["program_model"], {
        "n_layers": m["n_layers"], "d_model": m["d_model"], "n_heads": m["n_heads"],
        "n_kv_heads": m["n_kv_heads"], "d_head": m["d_head"], "rotary_dim": m["d_head"],
        "d_mlp": m["d_mlp"], "max_seq": m["max_seq"], "vocab_size": m["vocab_size"],
        "rope_theta": m["rope_theta"], "ut_steps": m["ut_steps"],
    }


def make_logits(m: dict):
    from . import ouro_reference

    return ouro_reference.make_logits(m)


def make_loss(m: dict):
    from . import ouro_reference

    return ouro_reference.make_loss(m)


def layer_params(m: dict) -> int:
    """Matmul parameters of one layer: four attention projections and the
    three matrices of the gated MLP."""
    E, Hq, Hkv = m["d_model"], m["n_heads"] * m["d_head"], m["n_kv_heads"] * m["d_head"]
    return E * Hq + 2 * E * Hkv + Hq * E + 3 * E * m["d_mlp"]


def train_flops_per_token(m: dict, seq: int) -> float:
    """FLOPs forward and backward REQUIRE per token: 6 per matmul parameter
    of every layer in every pass and of the head, plus causal attention in
    every (pass, layer) pair."""
    pairs = m["ut_steps"] * m["n_layers"]
    return (6.0 * (pairs * layer_params(m) + m["d_model"] * m["vocab_size"])
            + 6.0 * pairs * m["n_heads"] * m["d_head"] * seq)


def weight_bytes(m: dict) -> int:
    """EXACTLY the weight bytes a decode step must stream: every layer once a
    pass (the stack does not stay on chip between passes, so no pass can
    reuse another's read) and the head once, at 2 bytes. The embedding's
    rows are looked up, not streamed."""
    n = m["ut_steps"] * m["n_layers"] * layer_params(m) + m["d_model"] * m["vocab_size"]
    return n * BYTES_PER_PARAM


def kv_block_bytes(m: dict, block_size: int) -> int:
    """One block of the paged pool as built: K and V rows of `block_size`
    tokens for every (pass, layer) pair, bf16."""
    pairs = m["ut_steps"] * m["n_layers"]
    return 2 * pairs * m["n_kv_heads"] * m["d_head"] * block_size * 2


def kernel_costs(m: dict, batch: int, seq: int, chips: int) -> dict:
    """No Mosaic kernel runs in this family's serving path."""
    return {}
