"""The Jamba family (AI21, `model_type: "jamba"`): a pre-norm residual stack
whose layer i is an attention layer (grouped-query, no positional term) where
i % `attn_layer_period` == `attn_layer_offset` and a Mamba-1 state-space layer
elsewhere, every layer with the dense SiLU-gated MLP (`num_experts` 1), tied
head, no bias. Sizes from the published keys, the program model they select,
the plain reference (`jamba_reference.py`), and the operations and bytes."""

from __future__ import annotations

BYTES_PER_PARAM = 2     # the published checkpoint and the program's tree: bfloat16


def dims(config: dict, rehearse: bool) -> dict:
    """The published keys of a Jamba `config.json` as sizes."""
    c = dict(config)
    if rehearse:
        c.update(config["rehearsal"]["sizes"])
    if c["num_experts"] != 1 or not c["tie_word_embeddings"] or c["mamba_proj_bias"] \
            or not c["mamba_conv_bias"] or c["hidden_act"] != "silu" \
            or c.get("sliding_window") is not None:
        raise SystemExit(
            "jamba: written for a dense MLP in every layer (num_experts 1), a tied "
            "head, a bias on the convolution alone, silu and no window; the "
            "configuration states otherwise")
    return {
        "n_layers": c["num_hidden_layers"], "d_model": c["hidden_size"],
        "n_heads": c["num_attention_heads"], "n_kv_heads": c["num_key_value_heads"],
        "d_head": c["hidden_size"] // c["num_attention_heads"],
        "d_mlp": c["intermediate_size"],
        "attn_period": c["attn_layer_period"], "attn_offset": c["attn_layer_offset"],
        "d_state": c["mamba_d_state"], "d_conv": c["mamba_d_conv"],
        "expand": c["mamba_expand"], "dt_rank": c["mamba_dt_rank"],
        "max_seq": c["max_position_embeddings"], "vocab_size": c["vocab_size"],
        "norm_eps": c["rms_norm_eps"],
    }


def mamba_layers(m: dict) -> int:
    return sum(1 for i in range(m["n_layers"]) if i % m["attn_period"] != m["attn_offset"])


def program(config: dict, m: dict) -> tuple:
    """(name in `ray_tpu.models.gpt.CONFIGS`, overrides in `GPTConfig`'s own
    field names). A program that lacks the model (a checkout from before the
    PR that brought it) is refused HERE, in the parent process and at once:
    left to the replica's constructor it would fail over and over until the
    deployment's start-up limit."""
    from ray_tpu.models.gpt import CONFIGS

    if config["program_model"] not in CONFIGS:
        raise SystemExit(
            f"the program has no model {config['program_model']!r} "
            f"(ray_tpu.models.gpt.CONFIGS has {sorted(CONFIGS)}): this "
            "configuration cannot run on this checkout")
    if m["norm_eps"] != 1e-6:
        raise SystemExit(f"rms_norm_eps {m['norm_eps']}: the program's RMSNorm is 1e-6")
    return config["program_model"], {
        "n_layers": m["n_layers"], "d_model": m["d_model"], "n_heads": m["n_heads"],
        "n_kv_heads": m["n_kv_heads"], "d_head": m["d_head"], "d_mlp": m["d_mlp"],
        "ssm_layout": [int(i % m["attn_period"] != m["attn_offset"])
                       for i in range(m["n_layers"])],
        "ssm_state": m["d_state"], "ssm_conv": m["d_conv"], "ssm_expand": m["expand"],
        "ssm_dt_rank": m["dt_rank"], "max_seq": m["max_seq"],
        "vocab_size": m["vocab_size"],
    }


def make_logits(m: dict):
    from . import jamba_reference

    return jamba_reference.make_logits(m)


def make_loss(m: dict):
    from . import jamba_reference

    return jamba_reference.make_loss(m)


def mamba_params(m: dict) -> int:
    """Every parameter of one Mamba mixer: in, convolution and its bias, x,
    the three inner norms, dt and its bias, A_log, D, out."""
    E, Di, N, R = m["d_model"], m["expand"] * m["d_model"], m["d_state"], m["dt_rank"]
    return (E * 2 * Di + Di * m["d_conv"] + Di + Di * (R + 2 * N) + R + 2 * N
            + R * Di + Di + Di * N + Di + Di * E)


def attention_params(m: dict) -> int:
    E, Hq, Hkv = m["d_model"], m["n_heads"] * m["d_head"], m["n_kv_heads"] * m["d_head"]
    return E * Hq + 2 * E * Hkv + Hq * E


def tree_params(m: dict) -> int:
    """Every parameter of the tree: the two mixers' stacks, the MLP and two
    norms of every layer, the tied embedding ONCE, the final norm."""
    n_m = mamba_layers(m)
    return (n_m * mamba_params(m) + (m["n_layers"] - n_m) * attention_params(m)
            + m["n_layers"] * (3 * m["d_model"] * m["d_mlp"] + 2 * m["d_model"])
            + m["vocab_size"] * m["d_model"] + m["d_model"])


def train_flops_per_token(m: dict, seq: int) -> float:
    """FLOPs forward and backward REQUIRE per token: 6 per matmul parameter
    (the tied embedding as the head), causal attention in the attention
    layers, and the scan's 7 a channel a state forward, thrice that with its
    backward pass. The program does not train the model; no cell reads this."""
    n_m = mamba_layers(m)
    Di = m["expand"] * m["d_model"]
    return (6.0 * tree_params(m)
            + 6.0 * (m["n_layers"] - n_m) * m["n_heads"] * m["d_head"] * seq
            + 3.0 * 7.0 * n_m * Di * m["d_state"])


def weight_bytes(m: dict) -> int:
    """EXACTLY the bytes of the tree a decode step streams: every parameter
    once (the tied embedding is the head, read whole), at 2 bytes."""
    return tree_params(m) * BYTES_PER_PARAM


def kv_block_bytes(m: dict, block_size: int) -> int:
    """One block of the paged pool: K and V rows of `block_size` tokens for
    the ATTENTION layers alone, bf16. A Mamba layer keeps no row."""
    rows = m["n_layers"] - mamba_layers(m)
    return 2 * rows * m["n_kv_heads"] * m["d_head"] * block_size * 2


def state_bytes(m: dict) -> int:
    """What one sequence's state slot holds, whatever its length: in every
    Mamba layer the scan's state in float32 and the convolution's last
    d_conv - 1 inputs in bf16."""
    Di = m["expand"] * m["d_model"]
    return mamba_layers(m) * (Di * m["d_state"] * 4 + Di * (m["d_conv"] - 1) * 2)


def kernel_costs(m: dict, batch: int, seq: int, chips: int) -> dict:
    """One call of `ssm_scan` (`ray_tpu/ops/ssm.py`) over `batch` lanes of
    `seq` tokens: a token of a channel of a state is a multiply for the
    exponent, the exponential, two multiply-adds into the state and one out
    of it (7 operations); the call reads the step and the input and writes
    the output (float32 each, a token a channel), reads B and C, reads and
    writes the state once and reads A once."""
    Di, N = m["expand"] * m["d_model"], m["d_state"]
    n = batch * seq * Di
    return {"ssm_scan": {
        "flops": 7.0 * n * N,
        "bytes": 4.0 * (3 * n + 2 * batch * seq * N + 2 * batch * N * Di + N * Di)}}
