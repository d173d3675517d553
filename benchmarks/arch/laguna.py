"""The Laguna family (poolside, `model_type: "laguna"`): a pre-norm residual
stack whose layer l is a full-attention layer or a sliding-window layer
(`layer_types`), the two kinds with their own count of query heads
(`num_attention_heads_per_layer`) over the same K/V heads and their own rotary
table (`rope_parameters`), one gate a head on the attention output (`gating`),
leading dense MLP layers (`mlp_layer_types`) and then sigmoid-routed experts
beside a shared expert, untied head, no bias. Served as ONE PIPELINE STAGE of a
stated deployment: `num_hidden_layers` in the file is the stage's depth. Sizes
from the published keys, the program model they select, the plain reference
(`laguna_reference.py`), and the operations and bytes."""

from __future__ import annotations

BYTES_PER_PARAM = 2     # the published checkpoint and the program's tree: bfloat16
_KINDS = ("full_attention", "sliding_attention")
_YARN = ("attention_factor", "beta_fast", "beta_slow", "factor",
         "original_max_position_embeddings")


def dims(config: dict, rehearse: bool) -> dict:
    """The published keys of a Laguna `config.json`, cut to the stage's
    depth, as sizes. What the program was not written for is refused here."""
    c = dict(config)
    dep = dict(config["deployment"])
    if rehearse:
        c.update(config["rehearsal"]["sizes"])
        dep.update(config["rehearsal"].get("deployment", {}))
    L = c["num_hidden_layers"]
    kinds, mlps = c["layer_types"][:L], c["mlp_layer_types"][:L]
    heads = c["num_attention_heads_per_layer"][:L]
    dense = next((l for l, kind in enumerate(mlps) if kind != "dense"), L)
    by_kind = {k: {h for h, kind in zip(heads, kinds) if kind == k} for k in _KINDS}
    rope = {k: dict(c["rope_parameters"][k]) for k in _KINDS}
    if (set(kinds) != set(_KINDS) or "dense" in mlps[dense:] or not 0 < dense < L
            or any(kind != _KINDS[0] for kind in kinds[:dense])
            or any(len(hs) != 1 for hs in by_kind.values())
            or by_kind[_KINDS[0]] != {c["num_attention_heads"]}
            or c["attention_bias"] or c["tie_word_embeddings"] or c["gating"] is not True
            or c["moe_apply_router_weight_on_input"]
            or rope[_KINDS[0]]["rope_type"] != "yarn"
            or rope[_KINDS[1]]["rope_type"] != "default"):
        raise SystemExit(
            "laguna: written for full and sliding layers that each have ONE count of "
            "query heads (the full layers' num_attention_heads), leading dense MLP "
            "layers of the full kind and experts behind them, gating true, no bias, "
            "an untied head, the router's weight on the output, YaRN rotary on the "
            "full layers and plain rotary on the sliding ones; the configuration "
            "states otherwise")
    return {
        "n_layers": L, "dense_layers": dense, "d_model": c["hidden_size"],
        "n_heads": c["num_attention_heads"], "n_heads_window": by_kind[_KINDS[1]].pop(),
        "n_kv_heads": c["num_key_value_heads"], "d_head": c["head_dim"],
        "window_layout": [int(kind == _KINDS[1]) for kind in kinds],
        "window": c["sliding_window"], "rope": rope,
        "d_dense": c["intermediate_size"], "d_expert": c["moe_intermediate_size"],
        "d_shared": c["shared_expert_intermediate_size"],
        "n_experts": c["num_experts"], "top_k": c["num_experts_per_tok"],
        "route_scale": float(c["moe_routed_scaling_factor"]),
        "max_seq": dep["served_positions"], "vocab_size": c["vocab_size"],
        "norm_eps": c["rms_norm_eps"],
    }


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
    if m["d_shared"] % m["d_expert"]:
        raise SystemExit("the program's shared expert is a whole number of routed "
                         "experts wide (moe_shared x d_mlp)")
    full, sliding = (m["rope"][k] for k in _KINDS)
    return config["program_model"], {
        "n_layers": m["n_layers"], "dense_layers": m["dense_layers"],
        "d_model": m["d_model"], "n_heads": m["n_heads"],
        "n_heads_window": m["n_heads_window"], "n_kv_heads": m["n_kv_heads"],
        "d_head": m["d_head"], "d_mlp": m["d_expert"], "d_dense_mlp": m["d_dense"],
        "sliding_window_layout": m["window_layout"], "sliding_window": m["window"],
        "rotary_dim": int(m["d_head"] * full["partial_rotary_factor"]),
        "rope_theta": float(full["rope_theta"]),
        "rope_scaling": {k: float(full[k]) for k in _YARN},
        "window_rotary_dim": int(m["d_head"] * sliding["partial_rotary_factor"]),
        "window_rope_theta": float(sliding["rope_theta"]),
        "moe_experts": m["n_experts"], "moe_top_k": m["top_k"],
        "moe_shared": m["d_shared"] // m["d_expert"],
        "moe_route_scale": m["route_scale"],
        "max_seq": m["max_seq"], "vocab_size": m["vocab_size"],
    }


def make_logits(m: dict):
    from . import laguna_reference

    return laguna_reference.make_logits(m)


def make_loss(m: dict):
    from . import laguna_reference

    return laguna_reference.make_loss(m)


def attention_params(m: dict, window: int) -> int:
    """One layer's attention: q and o of the kind's own head count, k and v
    of the shared K/V heads, the gate's one column a head."""
    E, Dh = m["d_model"], m["d_head"]
    H = m["n_heads_window"] if window else m["n_heads"]
    return 2 * E * H * Dh + 2 * E * m["n_kv_heads"] * Dh + E * H


def expert_params(m: dict) -> int:
    return 3 * m["d_model"] * m["d_expert"]


def layer_params(m: dict, l: int, experts: int) -> int:
    """Every parameter of layer l with `experts` routed experts counted:
    attention, two norms, and the dense MLP or router + shared expert +
    experts."""
    E = m["d_model"]
    n = attention_params(m, m["window_layout"][l]) + 2 * E
    if l < m["dense_layers"]:
        return n + 3 * E * m["d_dense"]
    return n + E * m["n_experts"] + 3 * E * m["d_shared"] + experts * expert_params(m)


def tree_params(m: dict) -> int:
    """Every parameter of the tree the stage holds: its layers with ALL their
    experts, the embedding, the final norm, the head."""
    return (sum(layer_params(m, l, m["n_experts"]) for l in range(m["n_layers"]))
            + 2 * m["d_model"] * m["vocab_size"] + m["d_model"])


def train_flops_per_token(m: dict, seq: int) -> float:
    """FLOPs forward and backward REQUIRE per trained token: 6 per ACTIVE
    matmul parameter (top_k routed experts a layer, the head), plus causal
    attention over what each kind sees. The program does not train the model;
    no cell reads this."""
    active = (sum(layer_params(m, l, m["top_k"]) for l in range(m["n_layers"]))
              + m["d_model"] * m["vocab_size"])
    keys = sum((m["n_heads_window"] * min(seq, 2 * m["window"]) if w
                else m["n_heads"] * seq) for w in m["window_layout"])
    return 6.0 * active + 6.0 * m["d_head"] * keys


def weight_bytes(m: dict) -> int:
    """A TRUE LOWER BOUND of the weight bytes any decode step streams: what a
    step of ONE lane must read: every layer's attention and norms, the dense
    MLP, the router, the shared expert and top_k routed experts a layer, and
    the head, at 2 bytes. The embedding's rows are looked up, not streamed. A
    step of more lanes touches more experts and reads more, so its share of
    this roofline stays under 100%."""
    n = (sum(layer_params(m, l, m["top_k"]) for l in range(m["n_layers"]))
         + m["d_model"] * m["vocab_size"] + m["d_model"])
    return n * BYTES_PER_PARAM


def kv_block_bytes(m: dict, block_size: int) -> int:
    """One block of the paged pool: ONE layer's K and V rows of `block_size`
    tokens, bf16. Full and sliding layers do not divide into equal groups of
    more than one layer (`models.gpt.kv_layout`: gcd of their counts 1), so
    every layer is a group of its own and draws blocks of one layer's rows."""
    import math

    glob = m["window_layout"].count(0)
    per_group = math.gcd(glob, m["n_layers"] - glob)
    return 2 * per_group * m["n_kv_heads"] * m["d_head"] * block_size * 2


def kernel_costs(m: dict, batch: int, seq: int, chips: int) -> dict:
    """No train step runs this family: no kernel's cost is asked for."""
    return {}
