"""The A.X-K1 family (SK Telecom, `model_type: "axk1"`; the layer is
DeepSeek-V3's): latent attention over one compressed cache row a token, YaRN
rotary, leading dense layers, then sigmoid-routed experts beside a shared
expert, served as ONE CHIP'S SHARE of a stated deployment: the chip holds a
range of each layer's routed experts (`n_routed_experts` in the file is the
count held; the router keeps the published width, `deployment.router_experts`)
and a slice of the vocabulary. Sizes from the published keys, the program
model they select, the plain reference (`axk1_reference.py`), and the
operations and bytes."""

from __future__ import annotations

BYTES_PER_PARAM = 2     # the published checkpoint and the program's tree: bfloat16
_YARN = ("beta_fast", "beta_slow", "factor", "mscale", "mscale_all_dim",
         "original_max_position_embeddings")


def dims(config: dict, rehearse: bool) -> dict:
    """The published keys of an A.X-K1 `config.json`, and the deployment's
    share of them, as sizes."""
    c = dict(config)
    dep = dict(config["deployment"])
    if rehearse:
        c.update(config["rehearsal"]["sizes"])
        dep.update(config["rehearsal"].get("deployment", {}))
    scaling = c["rope_scaling"]
    if scaling.get("type") != "yarn" or c["topk_method"] != "none" \
            or c["scoring_func"] != "sigmoid" or not c["norm_topk_prob"] \
            or c["qk_nope_head_dim"] != c["v_head_dim"]:
        raise SystemExit(
            "axk1: written for YaRN rotary, topk_method 'none', sigmoid scoring "
            "with normalised top-k and qk_nope_head_dim = v_head_dim; the "
            "configuration states otherwise")
    return {
        "n_layers": c["num_hidden_layers"], "dense_layers": c["first_k_dense_replace"],
        "d_model": c["hidden_size"], "n_heads": c["num_attention_heads"],
        "q_lora": c["q_lora_rank"], "kv_lora": c["kv_lora_rank"],
        "d_nope": c["qk_nope_head_dim"], "d_rope": c["qk_rope_head_dim"],
        "d_v": c["v_head_dim"], "d_dense": c["intermediate_size"],
        "d_expert": c["moe_intermediate_size"],
        "n_experts": dep["router_experts"], "top_k": c["num_experts_per_tok"],
        "held_start": dep["held_experts_start"], "held_count": c["n_routed_experts"],
        "n_shared": c["n_shared_experts"], "route_scale": float(c["routed_scaling_factor"]),
        "max_seq": dep["served_positions"], "vocab_size": c["vocab_size"],
        "rope_theta": float(c["rope_theta"]), "norm_eps": c["rms_norm_eps"],
        "yarn": {k: float(scaling[k]) for k in _YARN},
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
        "n_layers": m["n_layers"], "dense_layers": m["dense_layers"],
        "d_model": m["d_model"], "n_heads": m["n_heads"], "d_head": m["d_nope"],
        "rotary_dim": m["d_rope"], "q_lora_rank": m["q_lora"],
        "kv_lora_rank": m["kv_lora"], "d_mlp": m["d_expert"],
        "d_dense_mlp": m["d_dense"], "moe_experts": m["n_experts"],
        "moe_top_k": m["top_k"], "moe_shared": m["n_shared"],
        "moe_route_scale": m["route_scale"],
        "moe_held": [m["held_start"], m["held_count"]],
        "max_seq": m["max_seq"], "vocab_size": m["vocab_size"],
        "rope_theta": m["rope_theta"], "rope_scaling": m["yarn"],
    }


def make_logits(m: dict):
    from . import axk1_reference

    return axk1_reference.make_logits(m)


def make_loss(m: dict):
    from . import axk1_reference

    return axk1_reference.make_loss(m)


def attention_params(m: dict) -> int:
    """Matmul parameters of one layer's latent attention: `q_a`, `q_b`,
    `kv_a` (the latent and the shared rotary key), `kv_b` (keys and values),
    `o`."""
    E, H = m["d_model"], m["n_heads"]
    return (E * m["q_lora"] + m["q_lora"] * H * (m["d_nope"] + m["d_rope"])
            + E * (m["kv_lora"] + m["d_rope"])
            + m["kv_lora"] * H * (m["d_nope"] + m["d_v"]) + H * m["d_v"] * E)


def layer_params(m: dict, experts: int) -> int:
    """Matmul parameters of one EXPERT layer with `experts` routed experts
    counted: attention, the shared expert(s), the router at its published
    width, the experts."""
    E, F = m["d_model"], m["d_expert"]
    return (attention_params(m) + m["n_shared"] * 3 * E * F + E * m["n_experts"]
            + experts * 3 * E * F)


def dense_layer_params(m: dict) -> int:
    return attention_params(m) + 3 * m["d_model"] * m["d_dense"]


def held_params(m: dict) -> int:
    """Every matmul parameter this chip holds: the leading dense layers, the
    expert layers with the held range, embedding and head over the held rows."""
    return (m["dense_layers"] * dense_layer_params(m)
            + (m["n_layers"] - m["dense_layers"]) * layer_params(m, m["held_count"])
            + 2 * m["d_model"] * m["vocab_size"])


def train_flops_per_token(m: dict, seq: int) -> float:
    """FLOPs forward and backward REQUIRE per trained token ON THIS CHIP: 6
    per active matmul parameter (of a token's top_k assignments the held range
    sees held / experts in the mean), plus causal attention in its expanded
    form. The program does not train the model; no cell reads this."""
    share = m["top_k"] * m["held_count"] / m["n_experts"]
    expert_layers = m["n_layers"] - m["dense_layers"]
    active = (m["dense_layers"] * dense_layer_params(m)
              + expert_layers * layer_params(m, 0)
              + expert_layers * share * 3 * m["d_model"] * m["d_expert"]
              + m["d_model"] * m["vocab_size"])
    per_key = m["n_heads"] * (m["d_nope"] + m["d_rope"] + m["d_v"])
    return 6.0 * active + 6.0 * m["n_layers"] * per_key * seq / 2


def weight_bytes(m: dict) -> int:
    """A TRUE LOWER BOUND of the weight bytes any decode step streams:
    everything outside the routed experts (attention, shared expert, router,
    the dense layers' MLP) and the held head, NO routed expert (a step whose
    lanes chose none of the held experts reads none), at 2 bytes. The
    embedding's rows are looked up, not streamed. A step that touches held
    experts reads more, so its share of this roofline stays under 100%."""
    n = (m["dense_layers"] * dense_layer_params(m)
         + (m["n_layers"] - m["dense_layers"]) * layer_params(m, 0)
         + m["d_model"] * m["vocab_size"])
    return n * BYTES_PER_PARAM


def kv_block_bytes(m: dict, block_size: int) -> int:
    """One block of the paged pool AS HELD: one row a token a layer, the
    latent beside the shared rotary key, padded to a whole number of
    128-column tiles (576 -> 640: `models.gpt.KVLayout`), bf16, every layer."""
    row = -(-(m["kv_lora"] + m["d_rope"]) // 128) * 128
    return m["n_layers"] * row * block_size * 2


def kernel_costs(m: dict, batch: int, seq: int, chips: int) -> dict:
    """No Mosaic kernel runs in this family's serving path."""
    return {}
