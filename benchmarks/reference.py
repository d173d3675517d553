"""Plain reference of the GPT-2 / GPT-J forward pass and loss: float32
`jax.numpy`, no kernels, no remat, no cache, no batching tricks, matmuls at
`highest` precision. Independent of `ray_tpu/models/gpt.py`: it shares only
the layout of the parameter tree, which is the interface under test.

Follows the published descriptions (GPT-2: pre-LayerNorm blocks, learned
positions, tanh GELU, tied head; GPT-J: one LayerNorm feeding attention and
MLP in parallel, rotary positions on the first `rotary_dim` dims of each
head, untied head). Departure, noted: GPT-J rotates interleaved pairs
(x[2i], x[2i+1]); the program rotates the two halves of the rotary dims
(x[i], x[i+rd/2]), which is the same model under a fixed permutation of the
q/k weight columns. With seeded random weights the reference has to use the
program's pairing to see the same numbers, so it does."""

from __future__ import annotations

import jax
import jax.numpy as jnp

_LAYER_KEYS = ("w_qkv", "b_qkv", "w_o", "b_o", "w_in", "b_in", "w_out",
               "b_out", "ln1_w", "ln1_b", "ln2_w", "ln2_b")


def _ln(x, w, b, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654 * (x + 0.044715 * x ** 3)))


def _rotary(x, rd, theta=10000.0):
    """x [S, H, Dh]: rotate the first rd dims by position (half-split pairs)."""
    S = x.shape[0]
    inv = 1.0 / theta ** (jnp.arange(0, rd, 2, dtype=jnp.float32) / rd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., : rd // 2], x[..., rd // 2: rd], x[..., rd:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, rest], -1)


def logits_one(params, tokens, m: dict):
    """tokens [S] int32 -> logits [S, V] float32, one sequence."""
    S = tokens.shape[0]
    f32 = lambda a: a.astype(jnp.float32)
    x = f32(params["tok_embed"])[tokens]
    if m["pos"] == "learned":
        x = x + f32(params["pos_embed"])[:S]
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    scale = m["d_head"] ** -0.5

    def layer(x, p):
        p = {k: f32(v) for k, v in p.items()}
        h = _ln(x, p["ln1_w"], p["ln1_b"])
        qkv = jnp.einsum("se,ethd->tshd", h, p["w_qkv"]) + p["b_qkv"][:, None]
        q, k, v = qkv[0], qkv[1], qkv[2]                      # [S, H, Dh]
        if m["pos"] == "rotary":
            q, k = _rotary(q, m["rotary_dim"]), _rotary(k, m["rotary_dim"])
        att = jnp.einsum("shd,thd->hst", q, k) * scale
        att = jax.nn.softmax(jnp.where(causal[None], att, -jnp.inf), -1)
        a = jnp.einsum("hst,thd->shd", att, v)
        a = jnp.einsum("shd,hde->se", a, p["w_o"]) + p["b_o"]
        if m["parallel_block"]:
            u = _gelu_tanh(h @ p["w_in"] + p["b_in"]) @ p["w_out"] + p["b_out"]
            return x + a + u, None
        x = x + a
        h2 = _ln(x, p["ln2_w"], p["ln2_b"])
        u = _gelu_tanh(h2 @ p["w_in"] + p["b_in"]) @ p["w_out"] + p["b_out"]
        return x + u, None

    stack = {k: params[k] for k in _LAYER_KEYS if k in params}
    x, _ = jax.lax.scan(layer, x, stack)
    x = _ln(x, f32(params["ln_f_w"]), f32(params["ln_f_b"]))
    head = f32(params["tok_embed"]).T if m["tie_embeddings"] else f32(params["lm_head"])
    return x @ head


def make_loss(m: dict):
    """jit(params, tokens [S+1]) -> summed next-token cross-entropy of one
    sequence (float32); the caller averages over the batch's tokens."""

    def loss(params, tokens):
        with jax.default_matmul_precision("highest"):
            lg = logits_one(params, tokens[:-1], m)
        logp = jax.nn.log_softmax(lg, -1)
        return -jnp.take_along_axis(logp, tokens[1:, None], -1).sum()

    return jax.jit(loss)


def make_logits(m: dict):
    def fn(params, tokens):
        with jax.default_matmul_precision("highest"):
            return logits_one(params, tokens, m)

    return jax.jit(fn)
