"""The SmallThinker family (PowerInfer, 2025): grouped-query attention, global
layers without positional encoding beside rotary layers with a sliding window,
and in every layer ReLU-gated experts chosen top-k without drops by a router
that reads the layer's input. Sizes from the published keys, the program
model they select, the plain reference (`smallthinker_reference.py`), and
the operations and bytes."""

from __future__ import annotations

import math

BYTES_PER_PARAM = 2     # the published checkpoint and the program's tree: bfloat16


def dims(config: dict, rehearse: bool) -> dict:
    """The published keys of a SmallThinker `config.json` as sizes. The
    per-layer layouts are cut to the layers that are run."""
    c = dict(config)
    if rehearse:
        c.update(config["rehearsal"]["sizes"])
    L = c["num_hidden_layers"]
    return {
        "n_layers": L, "d_model": c["hidden_size"],
        "n_heads": c["num_attention_heads"], "n_kv_heads": c["num_key_value_heads"],
        "d_head": c["head_dim"], "d_expert": c["moe_ffn_hidden_size"],
        "n_experts": c["moe_num_primary_experts"],
        "top_k": c["moe_num_active_primary_experts"],
        "max_seq": c["max_position_embeddings"], "vocab_size": c["vocab_size"],
        "rope_theta": float(c["rope_theta"]), "norm_eps": c["rms_norm_eps"],
        "rope_layout": [int(v) for v in c["rope_layout"][:L]],
        "window_layout": [int(v) for v in c["sliding_window_layout"][:L]],
        "window": c["sliding_window_size"],
    }


def program(config: dict, m: dict) -> tuple:
    """(name in `ray_tpu.models.gpt.CONFIGS`, overrides in `GPTConfig`'s own
    field names). A program that lacks the model (a checkout from before the
    PR that brought it) is refused HERE, in the parent process and at once:
    left to the replica's constructor it would fail over and over until the
    deployment's start-up limit, a quarter of an hour later."""
    from ray_tpu.models.gpt import CONFIGS

    if config["program_model"] not in CONFIGS:
        raise SystemExit(
            f"the program has no model {config['program_model']!r} "
            f"(ray_tpu.models.gpt.CONFIGS has {sorted(CONFIGS)}): this "
            "configuration cannot run on this checkout")
    return config["program_model"], {
        "n_layers": m["n_layers"], "d_model": m["d_model"], "n_heads": m["n_heads"],
        "n_kv_heads": m["n_kv_heads"], "d_head": m["d_head"], "rotary_dim": m["d_head"],
        "d_mlp": m["d_expert"], "moe_experts": m["n_experts"], "moe_top_k": m["top_k"],
        "max_seq": m["max_seq"], "vocab_size": m["vocab_size"],
        "rope_theta": m["rope_theta"], "rope_layout": m["rope_layout"],
        "sliding_window_layout": m["window_layout"], "sliding_window": m["window"],
    }


def make_logits(m: dict):
    from . import smallthinker_reference

    return smallthinker_reference.make_logits(m)


def make_loss(m: dict):
    from . import smallthinker_reference

    return smallthinker_reference.make_loss(m)


def layer_params(m: dict, experts: int) -> int:
    """Matmul parameters of one layer with `experts` experts counted."""
    E, Hq, Hkv = m["d_model"], m["n_heads"] * m["d_head"], m["n_kv_heads"] * m["d_head"]
    attention = E * Hq + 2 * E * Hkv + Hq * E
    router = E * m["n_experts"]
    return attention + router + experts * 3 * E * m["d_expert"]


def train_flops_per_token(m: dict, seq: int) -> float:
    """FLOPs forward and backward REQUIRE per trained token: 6 per ACTIVE
    matmul parameter (top_k experts a layer, the head), plus causal
    attention over what each layer sees (a window layer at most its window)."""
    active = m["n_layers"] * layer_params(m, m["top_k"]) + m["d_model"] * m["vocab_size"]
    seen = sum(min(seq, m["window"]) if w else seq for w in m["window_layout"])
    return 6.0 * active + 6.0 * m["n_heads"] * m["d_head"] * seen


def weight_bytes(m: dict) -> int:
    """A TRUE LOWER BOUND of the weight bytes any decode step streams:
    everything outside the experts, the head, and top_k experts a layer (one
    lane's choice; more lanes touch more), at 2 bytes. The embedding's rows
    are looked up, not streamed. A step that reads every expert reads more
    than this, so its share of this roofline stays under 100%."""
    n = m["n_layers"] * layer_params(m, m["top_k"]) + m["d_model"] * m["vocab_size"]
    return n * BYTES_PER_PARAM


def kv_group_layers(m: dict) -> int:
    """Layers that share one block of the pool: the layers are dealt into
    groups of one kind and equal size (global | window), the largest size
    that divides both counts; all of them when there is one kind."""
    n_win = sum(1 for w in m["window_layout"] if w)
    n_glob = m["n_layers"] - n_win
    return math.gcd(n_glob, n_win) if n_glob and n_win else m["n_layers"]


def kv_block_bytes(m: dict, block_size: int) -> int:
    """One block of the paged pool as built: K and V rows of `block_size`
    tokens for the layers of one group, Hkv heads, bf16."""
    return 2 * kv_group_layers(m) * m["n_kv_heads"] * m["d_head"] * block_size * 2


def kernel_costs(m: dict, batch: int, seq: int, chips: int) -> dict:
    """No Mosaic kernel runs in this family's serving path."""
    return {}
