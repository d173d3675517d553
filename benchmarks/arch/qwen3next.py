"""The Qwen3-Next family (Qwen, `model_type: "qwen3_next"`): a pre-norm residual
stack under zero-centred RMSNorms whose layer l is a mixer then the expert MLP,
the mixer gated attention (a gate an element, a norm a query and a key head,
rotary over a quarter of a head) where (l + 1) % `full_attention_interval` == 0
and else a gated delta net (linear attention whose state is a matrix a head under
a delta rule); every layer's MLP softmax-routed SiLU-gated experts beside one
shared expert under a sigmoid gate; untied head, no bias. Served as ONE CHIP'S
SHARE of a stated deployment: the first `num_hidden_layers` layers (a pipeline
stage), a range of each layer's routed experts (`num_experts` in the file is the
count held; the router keeps the published width, `deployment.router_experts`) and
a slice of the vocabulary. Sizes from the published keys, the program model they
select, the plain reference (`qwen3next_reference.py`), and the operations and
bytes."""

from __future__ import annotations

BYTES_PER_PARAM = 2     # the published checkpoint and the program's tree: bfloat16


def dims(config: dict, rehearse: bool) -> dict:
    """The published keys of a Qwen3-Next `config.json`, and the deployment's
    share of them, as sizes."""
    c = dict(config)
    dep = dict(config["deployment"])
    if rehearse:
        c.update(config["rehearsal"]["sizes"])
        dep.update(config["rehearsal"].get("deployment", {}))
    if c["hidden_act"] != "silu" or not c["norm_topk_prob"] or c["tie_word_embeddings"] \
            or c["decoder_sparse_step"] != 1 or c["mlp_only_layers"] \
            or c.get("rope_scaling") is not None or c.get("use_sliding_window") \
            or c["shared_expert_intermediate_size"] != c["moe_intermediate_size"] \
            or c["num_hidden_layers"] % c["full_attention_interval"] \
            or c["linear_num_value_heads"] % c["linear_num_key_heads"]:
        raise SystemExit(
            "qwen3next: written for SiLU-gated experts in EVERY layer beside ONE shared "
            "expert of an expert's width, normalised top-k, an untied head, an unscaled "
            "rotary term, no window, whole periods of full_attention_interval layers and "
            "value heads a multiple of the key heads; the configuration states otherwise")
    return {
        "n_layers": c["num_hidden_layers"], "interval": c["full_attention_interval"],
        "d_model": c["hidden_size"], "n_heads": c["num_attention_heads"],
        "n_kv_heads": c["num_key_value_heads"], "d_head": c["head_dim"],
        "rotary_dim": int(c["head_dim"] * c["partial_rotary_factor"]),
        "rope_theta": float(c["rope_theta"]),
        "key_heads": c["linear_num_key_heads"], "key_dim": c["linear_key_head_dim"],
        "value_heads": c["linear_num_value_heads"], "value_dim": c["linear_value_head_dim"],
        "d_conv": c["linear_conv_kernel_dim"], "chunk": dep["delta_chunk"],
        "d_expert": c["moe_intermediate_size"],
        "n_experts": dep["router_experts"], "top_k": c["num_experts_per_tok"],
        "held_start": dep["held_experts_start"], "held_count": c["num_experts"],
        "max_seq": dep["served_positions"], "vocab_size": c["vocab_size"],
        "norm_eps": c["rms_norm_eps"],
    }


def layers(m: dict) -> dict:
    """{"delta", "attention"}: layers of each mixer this chip runs."""
    n_attn = m["n_layers"] // m["interval"]
    return {"delta": m["n_layers"] - n_attn, "attention": n_attn}


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
        "n_layers": m["n_layers"], "gdn_interval": m["interval"], "d_model": m["d_model"],
        "n_heads": m["n_heads"], "n_kv_heads": m["n_kv_heads"], "d_head": m["d_head"],
        "rotary_dim": m["rotary_dim"], "rope_theta": m["rope_theta"],
        "ssm_heads": m["value_heads"], "ssm_head_dim": m["value_dim"],
        "ssm_groups": m["key_heads"], "ssm_state": m["key_dim"], "ssm_conv": m["d_conv"],
        "ssm_chunk": m["chunk"], "d_mlp": m["d_expert"],
        "moe_experts": m["n_experts"], "moe_top_k": m["top_k"],
        "moe_held": [m["held_start"], m["held_count"]],
        "norm_eps": m["norm_eps"], "max_seq": m["max_seq"], "vocab_size": m["vocab_size"],
    }


def make_logits(m: dict):
    from . import qwen3next_reference

    return qwen3next_reference.make_logits(m)


def make_loss(m: dict):
    from . import qwen3next_reference

    return qwen3next_reference.make_loss(m)


def conv_width(m: dict) -> int:
    """Channels under the delta net's convolution: q, k and v together."""
    return 2 * m["key_heads"] * m["key_dim"] + m["value_heads"] * m["value_dim"]


def delta_params(m: dict) -> dict:
    """One gated delta net, term by term: `in_proj_qkvz`, `in_proj_ba`, the
    convolution (no bias), `dt_bias` and `A_log`, the gated norm's gain,
    `out_proj`."""
    E, H, Dv = m["d_model"], m["value_heads"], m["value_heads"] * m["value_dim"]
    return {"in_proj_qkvz": E * (conv_width(m) + Dv), "in_proj_ba": E * 2 * H,
            "conv": conv_width(m) * m["d_conv"], "heads": 2 * H,
            "gated_norm": m["value_dim"], "out_proj": Dv * E}


def attention_params(m: dict) -> dict:
    """One gated attention mixer: `q_proj` twice as wide (query | gate), k and v,
    `o_proj`, the two head norms."""
    E, Hq, Hkv = m["d_model"], m["n_heads"] * m["d_head"], m["n_kv_heads"] * m["d_head"]
    return {"q_gate": E * 2 * Hq, "kv": 2 * E * Hkv, "o": Hq * E, "qk_norms": 2 * m["d_head"]}


def mlp_params(m: dict, experts: int) -> dict:
    """One layer's MLP with `experts` routed experts counted: each expert's three
    matrices, the shared expert's three, the router at its published width, the
    shared expert's gate; beside them the layer's two norms."""
    E, F = m["d_model"], m["d_expert"]
    return {"routed": experts * 3 * E * F, "shared": 3 * E * F,
            "router": E * m["n_experts"], "shared_gate": E, "norms": 2 * E}


def tree_params(m: dict, experts=None) -> int:
    """Every parameter this chip holds: its mixers by kind, every layer's MLP
    (`experts` routed experts a layer, the held count by default) and norms,
    embedding and head over the held rows, the final norm."""
    n = layers(m)
    held = m["held_count"] if experts is None else experts
    return (n["delta"] * sum(delta_params(m).values())
            + n["attention"] * sum(attention_params(m).values())
            + m["n_layers"] * sum(mlp_params(m, held).values())
            + 2 * m["vocab_size"] * m["d_model"] + m["d_model"])


def train_flops_per_token(m: dict, seq: int) -> float:
    """FLOPs forward and backward REQUIRE per trained token ON THIS CHIP: 6 per
    active matmul parameter (of a token's top_k assignments the held range sees
    held / experts in the mean; the embedding is looked up), causal attention in
    the attention layers, and the delta rule's chunk form (a token of a value head
    meets `chunk` keys of K + V features inside its chunk, twice for the
    triangular system, and the carried state of K x V three times). The program
    does not train the model; no cell reads this."""
    n = layers(m)
    share = m["top_k"] * m["held_count"] / m["n_experts"]
    active = (tree_params(m, 0) - m["vocab_size"] * m["d_model"]
              + m["n_layers"] * share * 3 * m["d_model"] * m["d_expert"])
    K, V = m["key_dim"], m["value_dim"]
    delta = m["value_heads"] * (2 * m["chunk"] * (K + V) + 3 * K * V)
    return (6.0 * active + 6.0 * n["attention"] * m["n_heads"] * m["d_head"] * seq
            + 6.0 * n["delta"] * delta)


def weight_bytes(m: dict) -> int:
    """A TRUE LOWER BOUND of the weight bytes any decode step streams:
    everything outside the routed experts and the held embedding, NO routed
    expert (a step whose lanes chose none of the held experts reads none), at 2
    bytes. The embedding's rows are looked up, not streamed. A step that touches
    held experts reads more (of 128 held a layer, a lane's 2.5 in the mean), and
    reads and writes its lanes' state besides, so its share of this roofline
    stays under 100%."""
    return (tree_params(m, 0) - m["vocab_size"] * m["d_model"]) * BYTES_PER_PARAM


def kv_block_bytes(m: dict, block_size: int) -> int:
    """One block of the paged pool: K and V rows of `block_size` tokens for the
    ATTENTION layers alone, bf16. A delta layer keeps no row."""
    return 2 * layers(m)["attention"] * m["n_kv_heads"] * m["d_head"] * block_size * 2


def state_bytes(m: dict) -> int:
    """What one sequence's state slot holds, whatever its length: in every delta
    layer the float32 matrix [value heads, key, value] and the convolution's last
    d_conv - 1 inputs in bf16."""
    state = m["value_heads"] * m["key_dim"] * m["value_dim"] * 4
    return layers(m)["delta"] * (state + (m["d_conv"] - 1) * conv_width(m) * 2)


def kernel_costs(m: dict, batch: int, seq: int, chips: int) -> dict:
    """No train step runs this family and it brings no Pallas kernel of its own (the
    delta rule is plain XLA products: PERF.md §7): no kernel's cost is asked for."""
    return {}
