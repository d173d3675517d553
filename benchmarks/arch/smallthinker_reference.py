"""Plain reference of the SmallThinker layer stack: float32 `jax.numpy`,
matmuls at `highest` precision, no cache, no paging, no batching tricks, one
sequence at a time. Independent of `ray_tpu/models/gpt.py` and
`ray_tpu/ops/moe.py`: it shares only the layout of the parameter tree, which
is the interface under test.

The layer, as the published config and the family's description give it (x is
the residual stream [T, E], layer l):

1. r = x . W_router: one logit an expert, from the layer's INPUT, before the
   input norm ("router placed before attention"), in float32.
2. h = RMSNorm(x); q = h Wq (H heads), k = h Wk, v = h Wv (Hkv heads); no
   projection biases.
3. Where rope_layout[l] is 1, q and k are rotated over the whole head; where
   it is 0 the layer has no positional encoding at all.
4. Causal attention, scale 1/sqrt(Dh), each K/V head shared by H/Hkv query
   heads (query head i reads K/V head i // (H/Hkv)); where
   sliding_window_layout[l] is 1, query i sees keys j with i - window < j <= i.
   x = x + attn Wo.
5. m = RMSNorm(x); the top_k largest of r, by a plain sort; weights = softmax
   over those logits; y = sum_e w_e W_down,e(relu(W_gate,e m) * (W_up,e m));
   x = x + y. Every layer is an expert layer; no token is ever dropped.
6. Final RMSNorm, untied head.

Departures, noted. (a) Rotary pairs: the published model rotates the two
halves of a head (x[i], x[i + Dh/2]); so do the program and this reference
(no permutation is involved, unlike GPT-J's interleaved pairs). (b) The
program's tree carries an output-projection bias `b_o` and norm biases that
the published model does not have; they are zero at initialisation and the
reference adds `b_o` as the tree gives it, so a tree that had them non-zero
would still be held to the same function. (c) Fitting the chip: attention is
computed a block of queries at a time, the experts one expert at a time over
all tokens, the head a block of rows and of vocabulary columns at a time,
and `make_logits` hands back a HOST array: [T, V] float32 is 2.8 GB at T =
4,608 and V = 151,936, which does not fit beside the weights and the pool.
None of these changes a sum's terms, only where they are held."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 256          # queries a block of attention
ROW_BLOCK = 512        # rows a block of the head
COL_BLOCK = 32768      # vocabulary columns a block of the head


def _f32(a):
    return a.astype(jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _rotate(x, theta):
    """x [T, heads, Dh] at positions 0..T-1: the whole head, half-split pairs."""
    T, _, Dh = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, Dh, 2, dtype=jnp.float32) / Dh)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : Dh // 2], x[..., Dh // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _attention(q, k, v, window, m):
    """q [T, H, Dh], k and v [T, Hkv, Dh]; `window`: keys a query sees,
    itself included (T + 1 on a global layer). A block of queries at a time."""
    T, H, Dh = q.shape
    rep = H // m["n_kv_heads"]
    pad = -T % Q_BLOCK
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, Q_BLOCK, H, Dh)
    j = jnp.arange(T)[None, :]

    def block(args):
        q_blk, i0 = args
        i = (i0 + jnp.arange(Q_BLOCK))[:, None]
        see = (j <= i) & (j > i - window)
        kk, vv = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
        att = jnp.einsum("shd,thd->hst", q_blk, kk) * Dh ** -0.5
        att = jax.nn.softmax(jnp.where(see[None], att, -jnp.inf), -1)
        return jnp.einsum("hst,thd->shd", att, vv)

    out = jax.lax.map(block, (qb, jnp.arange(qb.shape[0]) * Q_BLOCK))
    return out.reshape(-1, H, Dh)[:T]


def _experts(mlp_in, r, p, m):
    """Step 5 for all tokens: per-token top-k by a plain sort, then one
    expert at a time over all tokens under its gate weight (0 for a token
    that did not choose it)."""
    T, X = r.shape
    order = jnp.argsort(-r, axis=-1)[:, : m["top_k"]]           # [T, k]
    kept = jnp.take_along_axis(r, order, axis=-1)
    w = jax.nn.softmax(kept, axis=-1)
    gate = jnp.zeros((T, X), jnp.float32).at[jnp.arange(T)[:, None], order].set(w)

    def one(y, e):
        wg, wu, wd = (_f32(p[k][e]) for k in ("moe_w_gate", "moe_w_in", "moe_w_out"))
        h = jax.nn.relu(mlp_in @ wg) * (mlp_in @ wu)
        return y + gate[:, e, None] * (h @ wd), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(mlp_in), jnp.arange(X))
    return y


def hidden(params, tokens, m: dict, layer_inputs: bool = False):
    """tokens [T] int32 -> the residual stream after the final norm [T, E]
    (with `layer_inputs`, also every layer's input [L, T, E], which is what
    its router reads)."""
    T = tokens.shape[0]
    x = _f32(params["tok_embed"][tokens])
    eps = m["norm_eps"]
    kinds = (jnp.asarray(m["rope_layout"], bool),
             jnp.asarray([m["window"] if w else T + 1 for w in m["window_layout"]],
                         jnp.int32))
    keys = ("w_q", "w_kv", "w_o", "b_o", "ln1_w", "ln2_w", "moe_router",
            "moe_w_gate", "moe_w_in", "moe_w_out")

    def layer(x, inp):
        p, rope, window = inp
        block_in = x
        r = x @ _f32(p["moe_router"])                            # step 1
        h = _rms(x, _f32(p["ln1_w"]), eps)                       # step 2
        q = jnp.einsum("te,ehd->thd", h, _f32(p["w_q"]))
        kv = jnp.einsum("te,eghd->gthd", h, _f32(p["w_kv"]))
        k, v = kv[0], kv[1]
        q = jnp.where(rope, _rotate(q, m["rope_theta"]), q)      # step 3
        k = jnp.where(rope, _rotate(k, m["rope_theta"]), k)
        a = _attention(q, k, v, window, m)                       # step 4
        x = x + jnp.einsum("thd,hde->te", a, _f32(p["w_o"])) + _f32(p["b_o"])
        mlp_in = _rms(x, _f32(p["ln2_w"]), eps)                  # step 5
        return x + _experts(mlp_in, r, p, m), (block_in if layer_inputs else None)

    x, inputs = jax.lax.scan(layer, x, ({k: params[k] for k in keys}, *kinds))
    x = _rms(x, _f32(params["ln_f_w"]), eps)                     # step 6
    return (x, inputs) if layer_inputs else x


def _head_rows(x_rows, head):
    return jnp.concatenate(
        [x_rows @ _f32(head[:, c: c + COL_BLOCK])
         for c in range(0, head.shape[1], COL_BLOCK)], axis=-1)


def make_logits(m: dict):
    """(params, tokens [T]) -> logits [T, V] float32 as a HOST array."""

    @jax.jit
    def hid(params, tokens):
        with jax.default_matmul_precision("highest"):
            return hidden(params, tokens, m)

    @jax.jit
    def head_rows(x_rows, head):
        with jax.default_matmul_precision("highest"):
            return _head_rows(x_rows, head)

    def fn(params, tokens):
        x = hid(params, jnp.asarray(tokens, jnp.int32))
        return np.concatenate(
            [np.asarray(head_rows(x[r: r + ROW_BLOCK], params["lm_head"]))
             for r in range(0, x.shape[0], ROW_BLOCK)], axis=0)

    return fn


def make_loss(m: dict):
    """(params, tokens [S+1]) -> summed next-token cross-entropy (float32)."""
    logits = make_logits(m)

    def loss(params, tokens):
        lg = logits(params, tokens[:-1])
        logp = lg - np.logaddexp.reduce(lg, axis=-1, keepdims=True)
        return float(-np.take_along_axis(logp, np.asarray(tokens[1:])[:, None], -1).sum())

    return loss
