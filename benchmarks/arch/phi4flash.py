"""The SambaY family as Phi-4-mini-flash-reasoning publishes it (Microsoft,
`model_type: "phi4flash"`, arXiv:2507.06607): a decoder-hybrid-decoder of
`num_hidden_layers` N layers under LayerNorms with bias and a SiLU-gated MLP,
the mixer by the layer's index (`mb_per_layer` 2: even layers are of the Mamba
class): Mamba-1 (even l <= N/2; layer N/2 hands out its scan output), window
differential attention (odd l < N/2), ONE full differential attention layer (N/2
+ 1), then gated memory units over that scan output (even l > N/2) and
differential cross attention over the full layer's rows (odd l > N/2 + 1); tied
head, no positional term. Sizes from the published keys and the `assumed_sizes`
the config leaves to the modelling file's defaults, the program model they
select, the plain reference (`phi4flash_reference.py`), the operations and bytes."""

from __future__ import annotations

BYTES_PER_PARAM = 2     # the published checkpoint and the program's tree: bfloat16


def dims(config: dict, rehearse: bool) -> dict:
    """The published keys of a phi4flash `config.json` as sizes."""
    c = {**config, **config["assumed_sizes"]}
    dep = dict(config["deployment"])
    if rehearse:
        c.update(config["rehearsal"]["sizes"])
        dep.update(config["rehearsal"].get("deployment", {}))
    if c["mb_per_layer"] != 2 or not c["tie_word_embeddings"] or c["mlp_bias"] \
            or c["lm_head_bias"] or c["hidden_act"] != "silu" or c["num_hidden_layers"] % 4 \
            or c["num_attention_heads"] % 2 or c["num_key_value_heads"] % 2 \
            or not c["sliding_window"]:
        raise SystemExit(
            "phi4flash: written for every second layer of the Mamba class (mb_per_layer "
            "2) over a whole number of four-layer periods, a tied head without bias, an "
            "MLP without bias under silu, head counts that pair up and a window; the "
            "configuration states otherwise")
    return {
        "n_layers": c["num_hidden_layers"], "d_model": c["hidden_size"],
        "n_heads": c["num_attention_heads"], "n_kv_heads": c["num_key_value_heads"],
        "d_head": c["hidden_size"] // c["num_attention_heads"],
        "d_mlp": c["intermediate_size"], "window": c["sliding_window"],
        "d_state": c["mamba_d_state"], "d_conv": c["mamba_d_conv"],
        "expand": c["mamba_expand"], "dt_rank": c["mamba_dt_rank"],
        "max_seq": dep["served_positions"], "vocab_size": c["vocab_size"],
        "norm_eps": c["layer_norm_eps"],
    }


def pairs(m: dict) -> tuple:
    """(self-decoder pairs of (Mamba, attention) layers, the last one (layer N/2,
    the full layer); cross-decoder pairs of (memory unit, cross attention))."""
    return m["n_layers"] // 4 + 1, m["n_layers"] // 4 - 1


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
    P, C = pairs(m)
    return config["program_model"], {
        "n_layers": m["n_layers"], "layer_pattern": "mw" * (P - 1) + "mf" + "gc" * C,
        "d_model": m["d_model"], "n_heads": m["n_heads"], "n_kv_heads": m["n_kv_heads"],
        "d_head": m["d_head"], "d_mlp": m["d_mlp"], "sliding_window": m["window"],
        "ssm_state": m["d_state"], "ssm_conv": m["d_conv"], "ssm_expand": m["expand"],
        "ssm_dt_rank": m["dt_rank"], "norm_eps": m["norm_eps"], "max_seq": m["max_seq"],
        "vocab_size": m["vocab_size"],
    }


def make_logits(m: dict):
    from . import phi4flash_reference

    return phi4flash_reference.make_logits(m)


def make_loss(m: dict):
    from . import phi4flash_reference

    return phi4flash_reference.make_loss(m)


def layer_params(m: dict) -> int:
    """What every layer has: two LayerNorms (weight and bias) and the MLP's fused
    gate-and-up matrix and its down matrix, no bias."""
    E, F = m["d_model"], m["d_mlp"]
    return 4 * E + E * 2 * F + F * E


def mamba_params(m: dict) -> dict:
    """One Mamba-1 mixer, term by term: in, the convolution and its bias, x, dt
    and its bias, A_log, D, out. No inner norm."""
    E, Di, N, R = m["d_model"], m["expand"] * m["d_model"], m["d_state"], m["dt_rank"]
    return {"in_proj": E * 2 * Di, "conv": Di * m["d_conv"] + Di, "x_proj": Di * (R + 2 * N),
            "dt_proj": R * Di + Di, "A_log": Di * N, "D": Di, "out_proj": Di * E}


def attention_params(m: dict, cross: bool) -> dict:
    """One differential attention mixer: the q | k | v projection (a cross layer's:
    q alone) and the output projection, each with bias; four `lambda` vectors of a
    head; the pair norm's gain of two heads."""
    E, Hq, Hkv, d = m["d_model"], m["n_heads"] * m["d_head"], m["n_kv_heads"] * m["d_head"], m["d_head"]
    width = Hq if cross else Hq + 2 * Hkv
    return {"qkv_proj": E * width + width, "out_proj": Hq * E + E, "lambda": 4 * d,
            "subln": 2 * d}


def memory_params(m: dict) -> int:
    """One gated memory unit: the gate's and the output's matrix, no bias."""
    return 2 * m["d_model"] * m["expand"] * m["d_model"]


def tree_params(m: dict) -> int:
    """Every parameter of the tree: every layer's norms and MLP, the mixers by
    kind, the tied embedding ONCE, the final norm (weight and bias)."""
    P, C = pairs(m)
    return (m["n_layers"] * layer_params(m)
            + P * (sum(mamba_params(m).values()) + sum(attention_params(m, False).values()))
            + C * (memory_params(m) + sum(attention_params(m, True).values()))
            + m["vocab_size"] * m["d_model"] + 2 * m["d_model"])


def train_flops_per_token(m: dict, seq: int) -> float:
    """FLOPs forward and backward REQUIRE per token: 6 per matmul parameter (the
    tied embedding as the head), the attention layers' scores and values (window
    layers over at most the window, the full layer and the cross layers over the
    causal half), the scan's 7 a channel a state thrice. The program does not
    train the model; no cell reads this."""
    P, C = pairs(m)
    Di, Hd = m["expand"] * m["d_model"], m["n_heads"] * m["d_head"]
    keys = (P - 1) * min(m["window"], seq / 2) + (1 + C) * seq / 2
    return 6.0 * tree_params(m) + 3.0 * 2 * 2 * 2 * Hd * keys + 3.0 * 7.0 * P * Di * m["d_state"]


def weight_bytes(m: dict) -> int:
    """EXACTLY the bytes of the tree a decode step streams: every parameter once
    (the tied embedding is the head, read whole), at 2 bytes."""
    return tree_params(m) * BYTES_PER_PARAM


def kv_block_bytes(m: dict, block_size: int) -> int:
    """One block of the paged pool: K and V rows of `block_size` tokens of ONE
    layer, bf16 (`KVLayout`: nine groups of one layer, a pool one layer deep). A
    Mamba layer, a memory unit and a cross layer keep no row."""
    return 2 * m["n_kv_heads"] * m["d_head"] * block_size * 2


def state_bytes(m: dict) -> int:
    """What one sequence's state slot holds, whatever its length: in every Mamba
    layer the scan's state in float32 and the convolution's last d_conv - 1
    inputs in bf16."""
    Di = m["expand"] * m["d_model"]
    return pairs(m)[0] * (Di * m["d_state"] * 4 + Di * (m["d_conv"] - 1) * 2)


def kernel_costs(m: dict, batch: int, seq: int, chips: int) -> dict:
    """One call of `ssm_scan` (`ray_tpu/ops/ssm.py`) over `batch` lanes of `seq`
    tokens, as `jamba.kernel_costs` counts it (the same kernel at the same inner
    width): 7 operations a token a channel a state; the step, the input and the
    output in float32 a token a channel, B and C, the state read and written, A."""
    Di, N = m["expand"] * m["d_model"], m["d_state"]
    n = batch * seq * Di
    return {"ssm_scan": {
        "flops": 7.0 * n * N,
        "bytes": 4.0 * (3 * n + 2 * batch * seq * N + 2 * batch * N * Di + N * Di)}}
