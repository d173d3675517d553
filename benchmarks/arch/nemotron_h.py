"""The Nemotron-H family (NVIDIA, `model_type: "nemotron_h"`): a pre-norm
residual stack whose block l is ONE mixer under one RMSNorm, its kind the l-th
character of `hybrid_override_pattern`: "M" a Mamba-2 mixer, "E" sigmoid-routed
experts of two matrices (squared ReLU, no gate) beside a shared expert, "*"
grouped-query attention without a positional term; untied head, no bias but the
convolution's. Served as ONE CHIP'S SHARE of a stated deployment: the first
`num_hidden_layers` characters of the pattern (a pipeline stage), a range of each
expert block's routed experts (`n_routed_experts` in the file is the count held;
the router keeps the published width, `deployment.router_experts`) and a slice of
the vocabulary. Sizes from the published keys, the program model they select, the
plain reference (`nemotron_h_reference.py`), and the operations and bytes."""

from __future__ import annotations

BYTES_PER_PARAM = 2     # the published checkpoint and the program's tree: bfloat16


def dims(config: dict, rehearse: bool) -> dict:
    """The published keys of a Nemotron-H `config.json`, and the deployment's
    share of them, as sizes."""
    c = dict(config)
    dep = dict(config["deployment"])
    if rehearse:
        c.update(config["rehearsal"]["sizes"])
        dep.update(config["rehearsal"].get("deployment", {}))
    if c["mlp_hidden_act"] != "relu2" or c["mamba_hidden_act"] != "silu" \
            or not c["norm_topk_prob"] or c["n_group"] != 1 or c["topk_group"] != 1 \
            or c["tie_word_embeddings"] or not c["use_conv_bias"] or c["n_shared_experts"] != 1 \
            or any(c[k] for k in ("attention_bias", "mlp_bias", "use_bias", "mamba_proj_bias")) \
            or c.get("sliding_window") is not None \
            or c["moe_shared_expert_intermediate_size"] % c["moe_intermediate_size"] \
            or set(c["hybrid_override_pattern"]) - set("ME*"):
        raise SystemExit(
            "nemotron_h: written for squared-ReLU experts without a gate beside ONE "
            "shared expert (a whole multiple of an expert's width), normalised top-k "
            "without a group limit, silu in the Mamba-2 mixer, a bias on the convolution alone, an untied head, no window and "
            "a pattern of M, E and *; the configuration states otherwise")
    pattern = c["hybrid_override_pattern"][:c["num_hidden_layers"]]
    return {
        "pattern": pattern, "n_layers": len(pattern), "d_model": c["hidden_size"],
        "n_heads": c["num_attention_heads"], "n_kv_heads": c["num_key_value_heads"],
        "d_head": c["head_dim"],
        "ssm_heads": c["mamba_num_heads"], "ssm_head_dim": c["mamba_head_dim"],
        "ssm_groups": c["n_groups"], "d_state": c["ssm_state_size"],
        "d_conv": c["conv_kernel"], "chunk": c["chunk_size"],
        "d_expert": c["moe_intermediate_size"],
        "d_shared": c["moe_shared_expert_intermediate_size"],
        "n_experts": dep["router_experts"], "top_k": c["num_experts_per_tok"],
        "held_start": dep["held_experts_start"], "held_count": c["n_routed_experts"],
        "route_scale": float(c["routed_scaling_factor"]),
        "max_seq": dep["served_positions"], "vocab_size": c["vocab_size"],
        "norm_eps": c["norm_eps"],
    }


def blocks(m: dict) -> dict:
    """{"M", "E", "*"}: blocks of each kind this chip runs."""
    return {kind: m["pattern"].count(kind) for kind in "ME*"}


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
        "block_pattern": m["pattern"], "n_layers": m["n_layers"], "d_model": m["d_model"],
        "n_heads": m["n_heads"], "n_kv_heads": m["n_kv_heads"], "d_head": m["d_head"],
        "ssm_heads": m["ssm_heads"], "ssm_head_dim": m["ssm_head_dim"],
        "ssm_groups": m["ssm_groups"], "ssm_state": m["d_state"], "ssm_conv": m["d_conv"],
        "ssm_chunk": m["chunk"], "d_mlp": m["d_expert"],
        "moe_shared": m["d_shared"] // m["d_expert"],
        "moe_experts": m["n_experts"], "moe_top_k": m["top_k"],
        "moe_route_scale": m["route_scale"],
        "moe_held": [m["held_start"], m["held_count"]],
        "norm_eps": m["norm_eps"], "max_seq": m["max_seq"], "vocab_size": m["vocab_size"],
    }


def make_logits(m: dict):
    from . import nemotron_h_reference

    return nemotron_h_reference.make_logits(m)


def make_loss(m: dict):
    from . import nemotron_h_reference

    return nemotron_h_reference.make_loss(m)


def conv_width(m: dict) -> int:
    """Channels under the Mamba-2 convolution: x, B and C together."""
    return m["ssm_heads"] * m["ssm_head_dim"] + 2 * m["ssm_groups"] * m["d_state"]


def mamba_params(m: dict) -> dict:
    """One Mamba-2 block, term by term: `in_proj` (z | xBC | dt), the
    convolution and its bias, `A_log`, `D` and `dt_bias`, the gated norm's
    gain, `out_proj`, the block's norm."""
    E, H, Di, Dc = m["d_model"], m["ssm_heads"], m["ssm_heads"] * m["ssm_head_dim"], conv_width(m)
    return {"in_proj": E * (Di + Dc + H), "conv": Dc * m["d_conv"] + Dc, "heads": 3 * H,
            "gated_norm": Di, "out_proj": Di * E, "norm": E}


def expert_params(m: dict, experts: int) -> dict:
    """One expert block with `experts` routed experts counted: each expert's
    two matrices, the shared expert's two, the router at its published
    width, the selection bias, the block's norm."""
    E = m["d_model"]
    return {"routed": experts * 2 * E * m["d_expert"], "shared": 2 * E * m["d_shared"],
            "router": E * m["n_experts"], "select_bias": m["n_experts"], "norm": E}


def attention_params(m: dict) -> dict:
    E, Hq, Hkv = m["d_model"], m["n_heads"] * m["d_head"], m["n_kv_heads"] * m["d_head"]
    return {"q": E * Hq, "kv": 2 * E * Hkv, "o": Hq * E, "norm": E}


def tree_params(m: dict, experts=None) -> int:
    """Every parameter this chip holds: its blocks by kind (`experts` routed
    experts a block, the held count by default), embedding and head over the
    held rows, the final norm."""
    n = blocks(m)
    held = m["held_count"] if experts is None else experts
    return (n["M"] * sum(mamba_params(m).values())
            + n["E"] * sum(expert_params(m, held).values())
            + n["*"] * sum(attention_params(m).values())
            + 2 * m["vocab_size"] * m["d_model"] + m["d_model"])


def train_flops_per_token(m: dict, seq: int) -> float:
    """FLOPs forward and backward REQUIRE per trained token ON THIS CHIP: 6 per
    active matmul parameter (of a token's top_k assignments the held range sees
    held / experts in the mean; the embedding is looked up), causal attention
    in the attention blocks, and the chunked scan's products (a token of a head
    meets `chunk` keys of N + P features inside its chunk and the carried state
    of P x N twice). The program does not train the model; no cell reads this."""
    n = blocks(m)
    share = m["top_k"] * m["held_count"] / m["n_experts"]
    active = (tree_params(m, 0) - m["vocab_size"] * m["d_model"]
              + n["E"] * share * 2 * m["d_model"] * m["d_expert"])
    P, N = m["ssm_head_dim"], m["d_state"]
    scan = m["ssm_heads"] * (m["chunk"] * (N + P) + 2 * P * N)
    return (6.0 * active + 6.0 * n["*"] * m["n_heads"] * m["d_head"] * seq
            + 6.0 * n["M"] * scan)


def weight_bytes(m: dict) -> int:
    """A TRUE LOWER BOUND of the weight bytes any decode step streams:
    everything outside the routed experts and the held head, NO routed expert
    (a step whose lanes chose none of the held experts reads none), at 2
    bytes. The embedding's rows are looked up, not streamed. A step that
    touches held experts reads more (all 64 of 5 blocks at tens of lanes), and
    reads and writes its lanes' state besides, so its share of this roofline
    stays under 100%."""
    return (tree_params(m, 0) - m["vocab_size"] * m["d_model"]) * BYTES_PER_PARAM


def kv_block_bytes(m: dict, block_size: int) -> int:
    """One block of the paged pool: K and V rows of `block_size` tokens for the
    ATTENTION blocks alone, bf16. No other block keeps a row."""
    return 2 * blocks(m)["*"] * m["n_kv_heads"] * m["d_head"] * block_size * 2


def state_bytes(m: dict) -> int:
    """What one sequence's state slot holds, whatever its length: in every
    Mamba-2 block the float32 state [heads, head_dim, state] and the
    convolution's last d_conv - 1 inputs in bf16."""
    state = m["ssm_heads"] * m["ssm_head_dim"] * m["d_state"] * 4
    return blocks(m)["M"] * (state + (m["d_conv"] - 1) * conv_width(m) * 2)


def kernel_costs(m: dict, batch: int, seq: int, chips: int) -> dict:
    """One call of the two grouped-expert kernels the cell runs
    (`ray_tpu/ops/moe.py` `_grouped_pallas_ungated`) over `batch` x `seq`
    tokens, in the MEAN of the routing: the held range sees top_k x held /
    experts assignments a token; every expert they touch is read once a row
    tile (up [E, F] in the hidden kernel, down [F, E] in the down kernel: TWO
    matrices, no gate), at most all the held experts or one a tile. The hidden
    kernel also picks each tile's rows out of the tokens and writes the hidden
    rows; the down kernel reads them back and adds into the tokens' float32
    sums. A call's true cost follows its routing: a mean, for a time share's
    sanity and not a bound."""
    E, F, rows = m["d_model"], m["d_expert"], 128
    tokens = batch * seq
    lanes = -(-tokens // rows) * rows                        # the tokens as whole lane tiles
    assign = tokens * m["top_k"] * m["held_count"] / m["n_experts"]
    tiles = min(assign, m["held_count"] + assign / rows)     # an expert's partial tile counts whole
    return {
        "moe_grouped_hidden_ungated": {
            "flops": 2.0 * tiles * rows * (lanes + F) * E,   # the rows picked, then up
            "bytes": 2.0 * (tiles * E * F + lanes * E + tiles * rows * F)},
        "moe_grouped_down": {
            "flops": 2.0 * tiles * rows * (F + 2 * lanes) * E,   # down, then two terms back
            "bytes": 2.0 * tiles * (F * E + rows * F) + 4.0 * lanes * E},
    }
