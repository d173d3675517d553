"""Plain reference of the Ouro looped language model (ByteDance, arXiv:
2510.25741; `model_type: "ouro"`): float32 `jax.numpy`, matmuls at `highest`
precision, a full forward over one whole sequence, no cache, no paging, no
batching, no kernels. Independent of `ray_tpu/models/gpt.py`: it shares only
the layout of the parameter tree, which is the interface under test.

The model, as the published config's keys and the family's modelling code
give it (x is the residual stream [T, E]; *assumed* marks what the config has
no key for, listed under `assumed` in the configuration file):

1. h = Embed(tokens).
2. For t = 1 .. total_ut_steps: h = Stack(h), the SAME 48 layers with the
   SAME weights every pass; then h = N_f(h), the final RMSNorm (*assumed*: it
   closes every pass and its output is the next pass's input); then the exit
   gate lambda_t = sigmoid(w_g . h + b_g), one linear unit (*assumed* form).
3. One layer (*assumed*: sandwich norms, four RMSNorms a layer):
   a = x + N2(Attn(N1(x))); y = a + N4(W_down(silu(W_gate N3(a)) * W_up N3(a))).
   Attn: q, k, v = N1(x) Wq, Wk, Wv (16 heads of 128, as many K/V heads, no
   biases), q and k rotated over the whole head (*assumed* layout: half-split
   pairs (x[i], x[i + 64]), theta from rope_theta, no scaling), causal
   softmax(q k / sqrt(128)) v, then Wo. No window (use_sliding_window false).
4. logits = W_head h after the last pass: h was normed when that pass
   closed, so there is no second norm. Untied head.
5. Every (pass, layer) pair has keys and values of its own (*assumed*: cache
   slot t * 48 + l, as the family's cache class keeps them). A full forward
   has no cache, so this shows only in what a WRONG reference does:
   `cache_of_pass_one` below.
6. Exit: p_t = lambda_t * prod_{j<t} (1 - lambda_j) for t < T, p_T the
   remainder; a token leaves at the first t whose cumulative p reaches
   early_exit_threshold, at T if none does (`exit_steps`). At the published
   1.0 every token runs all passes and the logits are the last pass's.

Departures, noted. (a) The program's tree carries projection, output and MLP
biases and norm biases that the published model does not have; they are zero
at initialisation and the reference adds the output-side ones (`b_o`,
`b_out`) as the tree gives them. (b) Fitting the chip: the weights are
widened to float32 a layer at a time inside the layer scan, attention is
computed a block of queries at a time, and `make_logits` hands back a HOST
array. Neither changes a sum's terms, only where they are held.

Five switches in `m` make a WRONG reference, which the program must fail
(the benchmark's controls, `scripts/ouro_tolerance.py`): `ut_steps` lowered
(three passes for four), `cache_of_pass_one` (every pass attends over the
keys and values that pass 1 of the layer made), `norm_between_passes` false
(the final norm after the last pass only), `post_norms` false (no N2, N4),
`keys_unseen` (first, end): no later query sees the keys at those positions,
in any layer of any pass, as under a block table with one wrong entry."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 256          # queries a block of attention
_KEYS = ("w_qkv", "w_o", "b_o", "w_in", "w_gate", "w_out", "b_out", "ln1_w",
         "ln2_w", "ln1_post_w", "ln2_post_w")


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


def _attention(q, k, v, unseen=None):
    """Causal attention of q, k, v [T, H, Dh], a block of queries at a time.
    `unseen` (first, end) is a WRONG reference's: queries from `end` on do
    not see the keys at [first, end)."""
    T, H, Dh = q.shape
    pad = -T % Q_BLOCK
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, Q_BLOCK, H, Dh)
    j = jnp.arange(T)[None, :]

    def block(args):
        q_blk, i0 = args
        i = (i0 + jnp.arange(Q_BLOCK))[:, None]
        see = j <= i
        if unseen is not None:
            see &= (j < unseen[0]) | (j >= unseen[1]) | (i < unseen[1])
        att = jnp.einsum("shd,thd->hst", q_blk, k) * Dh ** -0.5
        att = jax.nn.softmax(jnp.where(see[None], att, -jnp.inf), -1)
        return jnp.einsum("hst,thd->shd", att, v)

    out = jax.lax.map(block, (qb, jnp.arange(qb.shape[0]) * Q_BLOCK))
    return out.reshape(-1, H, Dh)[:T]


def _stack(x, layers, m: dict, kv_given=None):
    """One pass of the layer stack over x [T, E] -> (x, the pass's post-rotary
    (k, v) [L, T, H, Dh] if `cache_of_pass_one` else None). With `kv_given`
    the layers attend over those keys and values instead of their own."""
    eps, post = m["norm_eps"], m.get("post_norms", True)
    keep = bool(m.get("cache_of_pass_one"))

    def layer(x, inp):
        p, given = inp
        h = _rms(x, _f32(p["ln1_w"]), eps)                          # N1
        qkv = jnp.einsum("te,eghd->gthd", h, _f32(p["w_qkv"]))
        q, k = _rotate(qkv[0], m["rope_theta"]), _rotate(qkv[1], m["rope_theta"])
        v = qkv[2]
        if given is not None:
            k, v = given
        a = _attention(q, k, v, m.get("keys_unseen"))
        a = jnp.einsum("thd,hde->te", a, _f32(p["w_o"])) + _f32(p["b_o"])
        if post:
            a = _rms(a, _f32(p["ln1_post_w"]), eps)                 # N2
        x = x + a
        h = _rms(x, _f32(p["ln2_w"]), eps)                          # N3
        y = (jax.nn.silu(h @ _f32(p["w_gate"])) * (h @ _f32(p["w_in"]))) @ _f32(p["w_out"])
        y = y + _f32(p["b_out"])
        if post:
            y = _rms(y, _f32(p["ln2_post_w"]), eps)                 # N4
        return x + y, ((k, v) if keep and given is None else None)

    return jax.lax.scan(layer, x, (layers, kv_given))


def hidden(params, tokens, m: dict):
    """tokens [T] int32 -> (the stream after the last pass closed [T, E],
    the exit gates lambda [passes, T])."""
    layers = {k: params[k] for k in _KEYS}
    wg, bg = _f32(params["exit_gate_w"]), _f32(params["exit_gate_b"])
    passes = m["ut_steps"]
    x, lams, kv_one = _f32(params["tok_embed"][tokens]), [], None
    for t in range(passes):                                         # step 2
        x, kv = _stack(x, layers, m, kv_one)
        if m.get("cache_of_pass_one") and t == 0:
            kv_one = kv
        if m.get("norm_between_passes", True) or t == passes - 1:
            x = _rms(x, _f32(params["ln_f_w"]), m["norm_eps"])      # N_f
        lams.append(jax.nn.sigmoid(x @ wg + bg))
    return x, jnp.stack(lams)


def exit_pdf(lams):
    """Step 6: lambda [passes, T] -> p [passes, T], a loop as written."""
    lams = np.asarray(lams, np.float64)
    left, out = np.ones_like(lams[0]), []
    for t in range(len(lams) - 1):
        out.append(lams[t] * left)
        left = left * (1.0 - lams[t])
    return np.stack(out + [left])


def exit_steps(lams, threshold: float):
    """Step 6's rule: the pass (counted from 1) at which each token leaves."""
    cum = np.cumsum(exit_pdf(lams), axis=0)
    reached = cum >= threshold
    return np.where(reached.any(axis=0), reached.argmax(axis=0) + 1, len(cum))


def make_hidden(m: dict):
    @jax.jit
    def fn(params, tokens):
        with jax.default_matmul_precision("highest"):
            return hidden(params, tokens, m)

    return fn


def make_logits(m: dict):
    """(params, tokens [T]) -> logits [T, V] float32 as a HOST array."""
    hid = make_hidden(m)

    @jax.jit
    def head(x, w):
        with jax.default_matmul_precision("highest"):
            return x @ _f32(w)

    def fn(params, tokens):
        x, _lams = hid(params, jnp.asarray(tokens, jnp.int32))
        return np.asarray(head(x, params["lm_head"]))

    return fn


def make_loss(m: dict):
    """The published objective is an expected loss over the exit distribution
    with an entropy term whose weight no config key gives: not written down
    here, as the program refuses to train such a model."""
    raise NotImplementedError(
        "a looped model's training objective (expected loss over the exit "
        "distribution) has no reference: the program refuses to train it")
