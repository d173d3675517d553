"""Plain reference of the Qwen3-Next layer stack (Qwen, `model_type:
"qwen3_next"`; written from the published configuration and the description of
its layers): float32 `jax.numpy`, matmuls at `highest` precision, the delta rule
ONE sequential scan over single tokens, no cache, no chunks, no slots, no
batching, no kernel. Independent of `ray_tpu/models/gpt.py`, `ray_tpu/ops/delta.py`
and `ray_tpu/ops/moe.py`: it shares only the layout of the parameter tree, which
is the interface under test (stacks by kind: `gdn_*` [delta layers, ...], `ga_*`
[attention layers, ...], the norms, the router, the experts and the shared expert
[L, ...]; every matrix [in, out]).

N(x) = x rsqrt(mean(x^2) + eps) (1 + w), eps 1e-6, `w` as stored (ZERO-centred).
Layer l over the stream x [T, E]: x <- x + Mixer_l(N1(x)); x <- x + MoE(N2(x));
after the last layer a final N and the untied head. Mixer_l is gated attention
where (l + 1) % `interval` == 0, else the gated delta net.

Gated delta net, token t (h = N1(x); G key heads of K, H value heads of V, key
head i serving value heads i H/G ..; C = 2 G K + H V channels under the taps):

    [q ; k ; v ; z]_t = W_qkvz h_t             G K | G K | H V | H V, head-major
    [b ; a]_t = W_ba h_t                       H | H
    (q, k, v)_t = silu(sum_{j<taps} w_conv[j] * (q|k|v)_{t-taps+1+j})     nothing before 0
    beta_t = sigmoid(b_t);   g_t = -exp(A_log) softplus(a_t + dt_bias)
    q_t <- q_t / sqrt(|q_t|^2 + 1e-6) / sqrt(K);   k_t <- k_t / sqrt(|k_t|^2 + 1e-6)
    S <- exp(g_t) S;  u = S^T k_t;  S <- S + k_t (beta_t (v_t - u))^T;  o_t = S^T q_t
    out_t = W_out (RMSNorm_V(o_t) w_norm silu(z_t))          a head; the gate AFTER the norm

with S in R^{K x V} a value head, S = 0 before token 0.

Gated attention: q_proj E -> H x 2 Dh, head n = [q_n | gate_n]; k, v E -> Hkv x Dh;
q_n <- N_q(q_n), k <- N_k(k) (zero-centred, one gain of Dh each); rotary over the
first `rotary_dim` features of a head in half-split pairs (i, i + rotary_dim / 2),
theta `rope_theta`; causal softmax(q k^T / sqrt(Dh)) v, query head n over K/V head
n // (H / Hkv); out = W_o (attn * sigmoid(gate)), elementwise.

MoE: p = softmax(W_r h) over ALL experts, float32; the top-k kept, their weights
over their sum; expert e adds W_down,e (silu(W_gate,e h) * W_up,e h); the shared
expert the same form times sigmoid(w_sg . h). The chip holds experts `held_start`
.. + `held_count`: what an absent expert would add is LEFT OUT (no exchange), as
the deployment's share is defined; the router stays whole.

Departures from the published code, each at its line: the q|k|v|z columns are
head-major by KIND (the published tensor interleaves them by key head: a fixed
permutation of the columns of a random matrix); the rotary pairs are half-split, as
published. The vocabulary is the held slice and the experts the held range.

Fitting the chip beside the model (the harness runs the reference inside the
replica): one layer at a time is widened to float32, the experts ONE at a time, the
head a block of vocabulary columns at a time; `make_logits` hands back a HOST array.

Switches in `m` that make a WRONG reference, which a sound program must fail (the
tests' and the benchmark's controls, `scripts/qwen3next_tolerance.py`): `no_delta`
(u dropped: Mamba-2's update), `no_decay`, `beta_one`, `no_qk_norm`,
`gate_before_norm` (RMSNorm_V(o silu(z))), `head_gate_scalar` (one gate a head:
the mean of its gate logits), `plain_norm` (a gain of w for 1 + w), `rotary_whole`
(the rotary term over the whole head), `top_k_wrong` (another k), `shared_ungated`,
`state_bf16` (the state rounded to bfloat16 after every token), `state_reset_every`
C (the state zeroed where t % C == 0), `tail_reset_every` C (the convolution sees
nothing from before the last multiple of C); `shared` false leaves the shared
expert out (the share test)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

COL_BLOCK = 8192       # vocabulary columns a block of the head

_GDN = ("w_qkvz", "w_ba", "conv_w", "dt_bias", "A_log", "norm_w", "w_out")
_GA = ("w_q", "w_kv", "q_norm_w", "k_norm_w", "w_o")
_MLP = ("ln1_w", "ln2_w", "moe_router", "shared_w_gate", "shared_w_in", "shared_w_out",
        "shared_gate")


def _norm(x, w, m: dict):
    gain = w if m.get("plain_norm") else 1.0 + w
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + m["norm_eps"]) * gain


def _rotate(x, m: dict):
    """x [T, heads, Dh]: the first `rotary_dim` features turned by position."""
    T, _, d = x.shape
    rd = d if m.get("rotary_whole") else m["rotary_dim"]
    ang = jnp.arange(T)[:, None] * m["rope_theta"] ** (-jnp.arange(0, rd, 2) / rd)[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b, rest = x[..., : rd // 2], x[..., rd // 2: rd], x[..., rd:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], -1)


def attention(h, p, m: dict):
    """h [T, E] -> what a gated attention layer adds to the stream."""
    T, Dh = h.shape[0], m["d_head"]
    qg = jnp.einsum("te,ehd->thd", h, p["w_q"])                     # [T, H, 2 Dh]
    kv = jnp.einsum("te,exgd->xtgd", h, p["w_kv"])                  # [2, T, Hkv, Dh]
    q, gate = qg[..., :Dh], qg[..., Dh:]
    q, k = _norm(q, p["q_norm_w"], m), _norm(kv[0], p["k_norm_w"], m)
    q, k = _rotate(q, m), _rotate(k, m)
    H, Hkv = q.shape[1], k.shape[1]
    k, v = (jnp.repeat(a, H // Hkv, axis=1) for a in (k, kv[1]))
    att = jnp.einsum("shd,thd->hst", q, k) / np.sqrt(Dh)
    seen = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    att = jax.nn.softmax(jnp.where(seen[None], att, -jnp.inf), -1)
    out = jnp.einsum("hst,thd->shd", att, v)
    if m.get("head_gate_scalar"):
        gate = jnp.broadcast_to(gate.mean(-1, keepdims=True), gate.shape)
    return jnp.einsum("shd,hde->se", out * jax.nn.sigmoid(gate), p["w_o"])


def delta_net(h, p, m: dict):
    """h [T, E] -> what the gated delta net adds to the stream, token by token."""
    T = h.shape[0]
    G, K, H, V, taps = m["key_heads"], m["key_dim"], m["value_heads"], m["value_dim"], m["d_conv"]
    wide = 2 * G * K + H * V
    qkvz, ba = h @ p["w_qkvz"], h @ p["w_ba"]       # columns by KIND (published: by key head)
    z = qkvz[:, wide:].reshape(T, H, V)
    t = jnp.arange(T)
    conv = jnp.zeros((T, wide), jnp.float32)
    for j in range(taps):                    # (q|k|v)_{t-taps+1+j}; nothing before token 0
        src = t - (taps - 1) + j
        first = 0 if not m.get("tail_reset_every") else t - t % m["tail_reset_every"]
        conv = conv + jnp.where((src >= first)[:, None],
                                qkvz[jnp.maximum(src, 0), :wide] * p["conv_w"][j][None, :], 0.0)
    qkv = jax.nn.silu(conv)
    q, k = (qkv[:, i * G * K:(i + 1) * G * K].reshape(T, G, K) for i in (0, 1))
    v = qkv[:, 2 * G * K:].reshape(T, H, V)
    if not m.get("no_qk_norm"):
        q, k = (a * jax.lax.rsqrt((a * a).sum(-1, keepdims=True) + 1e-6) for a in (q, k))
    q, k = (jnp.repeat(a, H // G, axis=1) for a in (q / np.sqrt(K), k))   # a value head's
    beta = jnp.ones((T, H)) if m.get("beta_one") else jax.nn.sigmoid(ba[:, :H])
    g = -jnp.exp(p["A_log"])[None, :] * jax.nn.softplus(ba[:, H:] + p["dt_bias"][None, :])
    if m.get("no_decay"):
        g = jnp.zeros_like(g)

    def token(s, inp):
        i, qt, kt, vt, gt, bt = inp
        if m.get("state_reset_every"):
            s = jnp.where(i % m["state_reset_every"] == 0, 0.0, s)
        s = jnp.exp(gt)[:, None, None] * s
        u = 0.0 if m.get("no_delta") else jnp.einsum("hkv,hk->hv", s, kt)
        s = s + kt[:, :, None] * (bt[:, None] * (vt - u))[:, None, :]
        if m.get("state_bf16"):     # not a pair of converts: the chip's compiler
            s = jax.lax.reduce_precision(s, 8, 7)   # keeps excess precision through one
        return s, jnp.einsum("hkv,hk->hv", s, qt)

    _, o = jax.lax.scan(token, jnp.zeros((H, K, V), jnp.float32), (t, q, k, v, g, beta))
    rms = lambda a: a * jax.lax.rsqrt((a * a).mean(-1, keepdims=True) + m["norm_eps"])
    gate = jax.nn.silu(z)
    o = rms(o * gate) if m.get("gate_before_norm") else rms(o) * gate
    return (o * p["norm_w"][None, None, :]).reshape(T, H * V) @ p["w_out"]


def route(h, router, m: dict):
    """h [T, E] -> combine [T, experts] float32: a token's weight at each expert
    it chose, 0 elsewhere."""
    probs = jax.nn.softmax(h @ router, axis=-1)                     # [T, X]
    idx = jnp.argsort(-probs, axis=-1)[:, : m.get("top_k_wrong", m["top_k"])]
    kept = jnp.take_along_axis(probs, idx, axis=-1)
    w = kept / kept.sum(-1, keepdims=True)
    return (jax.nn.one_hot(idx, probs.shape[-1]) * w[..., None]).sum(-2)


def _gated(h, w_gate, w_in, w_out):
    return (jax.nn.silu(h @ w_gate) * (h @ w_in)) @ w_out


def experts(h, p, layer, m: dict):
    """h [T, E] -> what layer `layer`'s MLP adds to the stream ON THIS CHIP: its
    held experts' weighted outputs, one expert at a time out of the whole stacks
    (`moe_w_*` [L, held, ...]), and the gated shared expert."""
    f32 = jnp.float32
    combine = route(h, p["moe_router"], m)
    combine = combine[:, m["held_start"]: m["held_start"] + m["held_count"]]

    def one(y, e):
        w_gate, w_in, w_out = (jax.lax.dynamic_slice(   # one expert's matrix where it lies
            a, (layer, e, 0, 0), (1, 1) + a.shape[2:])[0, 0].astype(f32)
            for a in (p["moe_w_gate"], p["moe_w_in"], p["moe_w_out"]))
        w = jax.lax.dynamic_index_in_dim(combine, e, 1, True)
        return y + w * _gated(h, w_gate, w_in, w_out), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(m["held_count"]))
    if m.get("shared", True):
        shared = _gated(h, p["shared_w_gate"], p["shared_w_in"], p["shared_w_out"])
        gate = 1.0 if m.get("shared_ungated") else jax.nn.sigmoid(h @ p["shared_gate"])[:, None]
        y = y + gate * shared
    return y


def make_logits(m: dict):
    """(params, tokens [T]) -> logits [T, V] float32 as a HOST array."""
    f32 = jnp.float32

    def mixed(mixer):
        @jax.jit
        def layer(x, p, stacks, l):         # `l` traced: the expert stacks stay whole
            with jax.default_matmul_precision("highest"):
                p = {k: v.astype(f32) for k, v in p.items()}
                x = x + mixer(_norm(x, p["ln1_w"], m), p, m)
                return x + experts(_norm(x, p["ln2_w"], m), {**p, **stacks}, l, m)
        return layer

    delta_layer, attention_layer = mixed(delta_net), mixed(attention)

    @jax.jit
    def head(x, ln, w):
        with jax.default_matmul_precision("highest"):
            return _norm(x, ln.astype(f32), m) @ w.astype(f32)

    def fn(params, tokens):
        x = params["tok_embed"][jnp.asarray(tokens, jnp.int32)].astype(f32)
        stacks = {k: params[k] for k in ("moe_w_gate", "moe_w_in", "moe_w_out")}
        n_delta = n_attn = 0
        for l in range(m["n_layers"]):
            own = {k: params[k][l] for k in _MLP}
            if (l + 1) % m["interval"] == 0:
                own.update({k: params["ga_" + k][n_attn] for k in _GA})
                x, n_attn = attention_layer(x, own, stacks, jnp.int32(l)), n_attn + 1
            else:
                own.update({k: params["gdn_" + k][n_delta] for k in _GDN})
                x, n_delta = delta_layer(x, own, stacks, jnp.int32(l)), n_delta + 1
        w = params["lm_head"]
        return np.concatenate(
            [np.asarray(head(x, params["ln_f_w"], w[:, c: c + COL_BLOCK]))
             for c in range(0, w.shape[1], COL_BLOCK)], axis=-1)

    return fn


def make_loss(m: dict):
    """(params, tokens [S+1]) -> summed next-token cross-entropy (float32)."""
    logits = make_logits(m)

    def loss(params, tokens):
        lg = logits(params, tokens[:-1])
        logp = lg - np.logaddexp.reduce(lg, axis=-1, keepdims=True)
        return float(-np.take_along_axis(logp, np.asarray(tokens[1:])[:, None], -1).sum())

    return loss
