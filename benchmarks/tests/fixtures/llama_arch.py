"""Test fixture, not a benchmark configuration: an architecture family that
is not GPT, brought as files alone. A pre-RMSNorm block with a gated MLP
(`w_gate`), rotary positions on the whole head and an untied head, run by the
program's existing `llama-7b` preset at the sizes of `llama-tiny.json`. It
shows what a `model_config` PR adds under `benchmarks/`: this module (sizes,
program model, plain float32 reference, costs) and a configuration file that
names it, with no edit to a file the benchmark has."""

from __future__ import annotations

from benchmarks import peaks

_LAYER_KEYS = ("w_qkv", "b_qkv", "w_o", "b_o", "w_in", "b_in", "w_gate", "w_out",
               "b_out", "ln1_w", "ln2_w")


def dims(config: dict, rehearse: bool) -> dict:
    c = dict(config)
    if rehearse:
        c.update(config["rehearsal"]["sizes"])
    return {"layers": c["num_hidden_layers"], "hidden": c["hidden_size"],
            "heads": c["num_attention_heads"],
            "head": c["hidden_size"] // c["num_attention_heads"],
            "inner": c["intermediate_size"], "positions": c["max_position_embeddings"],
            "vocab_size": c["vocab_size"], "eps": c["rms_norm_eps"],
            "theta": c["rope_theta"]}


def program(config: dict, m: dict) -> tuple:
    return config["program_model"], {
        "n_layers": m["layers"], "d_model": m["hidden"], "n_heads": m["heads"],
        "d_head": m["head"], "d_mlp": m["inner"], "max_seq": m["positions"],
        "vocab_size": m["vocab_size"], "rotary_dim": m["head"]}


def _reference(m: dict):
    import jax
    import jax.numpy as jnp

    def rms(x, w):
        return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + m["eps"]) * w

    def rotate(x):
        """x [S, H, Dh]: the whole head, pairs (x[i], x[i + Dh/2]) as the
        program pairs them."""
        S, _, Dh = x.shape
        inv = 1.0 / m["theta"] ** (jnp.arange(0, Dh, 2, dtype=jnp.float32) / Dh)
        ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
        c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        x1, x2 = x[..., : Dh // 2], x[..., Dh // 2:]
        return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)

    def logits_one(params, tokens):
        p32 = {k: v.astype(jnp.float32) for k, v in params.items()}
        S = tokens.shape[0]
        x = p32["tok_embed"][tokens]
        causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
        for l in range(m["layers"]):
            p = {k: p32[k][l] for k in _LAYER_KEYS}
            h = rms(x, p["ln1_w"])
            qkv = jnp.einsum("se,ethd->tshd", h, p["w_qkv"]) + p["b_qkv"][:, None]
            q, k, v = rotate(qkv[0]), rotate(qkv[1]), qkv[2]
            att = jnp.einsum("shd,thd->hst", q, k) * m["head"] ** -0.5
            att = jax.nn.softmax(jnp.where(causal[None], att, -jnp.inf), -1)
            a = jnp.einsum("hst,thd->shd", att, v)
            x = x + jnp.einsum("shd,hde->se", a, p["w_o"]) + p["b_o"]
            h = rms(x, p["ln2_w"])
            up = h @ p["w_in"] + p["b_in"]
            x = x + (jax.nn.silu(h @ p["w_gate"]) * up) @ p["w_out"] + p["b_out"]
        return rms(x, p32["ln_f_w"]) @ p32["lm_head"]

    return logits_one


def make_logits(m: dict):
    import jax

    ref = _reference(m)

    def fn(params, tokens):
        with jax.default_matmul_precision("highest"):
            return ref(params, tokens)

    return jax.jit(fn)


def make_loss(m: dict):
    import jax
    import jax.numpy as jnp

    ref = _reference(m)

    def loss(params, tokens):
        with jax.default_matmul_precision("highest"):
            lg = ref(params, tokens[:-1])
        logp = jax.nn.log_softmax(lg, -1)
        return -jnp.take_along_axis(logp, tokens[1:, None], -1).sum()

    return jax.jit(loss)


def n_matmul_params(m: dict) -> int:
    E, Hd = m["hidden"], m["heads"] * m["head"]
    per_layer = E * 3 * Hd + Hd * E + 3 * E * m["inner"]     # gate, up, down
    return m["layers"] * per_layer + E * m["vocab_size"]


def train_flops_per_token(m: dict, seq: int) -> float:
    return 6.0 * n_matmul_params(m) + 6.0 * m["layers"] * m["heads"] * m["head"] * seq


def weight_bytes(m: dict) -> int:
    return 4 * n_matmul_params(m)                            # the head is not tied


def kv_block_bytes(m: dict, block_size: int) -> int:
    return 2 * m["layers"] * m["heads"] * m["head"] * block_size * 2


def kernel_costs(m: dict, batch: int, seq: int, chips: int) -> dict:
    bh = batch * m["heads"] // chips
    return {"flash_fwd": peaks.flash_fwd_cost(bh, seq, m["head"]),
            "flash_bwd_dq": peaks.flash_bwd_dq_cost(bh, seq, m["head"]),
            "flash_bwd_dkv": peaks.flash_bwd_dkv_cost(bh, seq, m["head"])}
