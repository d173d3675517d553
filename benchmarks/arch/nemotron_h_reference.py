"""Plain reference of the Nemotron-H block stack (NVIDIA, `model_type:
"nemotron_h"`; written from the published configuration and the description of
its layers): float32 `jax.numpy`, matmuls at `highest` precision, the recurrence
ONE sequential scan over single tokens, no cache, no chunks, no slots, no
batching, no kernel. Independent of `ray_tpu/models/gpt.py`, `ray_tpu/ops/ssm.py`
and `ray_tpu/ops/moe.py`: it shares only the layout of the parameter tree, which
is the interface under test (three stacks by kind: `m2_*` [M blocks, ...],
`moe_*` / `shared_*` [E blocks, ...], `attn_ln_w` / `w_q` / `w_kv` / `w_o` [*
blocks, ...]; `m2_conv_w` tap-major [K, channels], the published [channels, 1, K]
transposed; a routed expert's up matrix [out, in] as published, every other
matrix [in, out]).

Block l of kind c (the l-th character of the pattern), x the stream [T, E],
N = RMSNorm (eps `norm_eps`, 1e-5): x <- x + mixer_c(N_l(x)); after the last
block a final N and the untied head.

M, for token t (h = N(x); H heads of P channels, Di = H P; G groups; N_s the
state's size; K taps):

    [z_t ; xBC_t ; dt_t] = W_in h_t                       Di | Di + 2 G N_s | H
    xBC_t = silu(b_conv + sum_{j<K} w_conv[j] * xBC_{t-K+1+j})       xBC_{<0} = 0
    [x_t ; B_t ; C_t] = xBC_t                             [H, P] | [G, N_s] | [G, N_s]
    delta_t = softplus(dt_t + dt_bias)                    not clamped
    S_t = exp(delta_t A_i) S_{t-1} + delta_t x_t (x) B_t^g      head i of group g = i // (H / G)
    y_t = S_t C_t^g + D_i x_t;   out_t = W_out N_groups(y_t * silu(z_t))

with A = -exp(A_log) [H], S_{-1} = 0, and N_groups the RMSNorm whose mean
square is taken over each of the G groups of Di / G channels, one gain of Di.

E: s = sigmoid(W_r h) over ALL experts, float32; the k experts with the largest
s + b chosen (b the selection bias), each weighted by its s over the chosen
ones' sum, times `route_scale`; expert e adds W_down,e relu(W_up,e h)^2; the
shared expert the same form, unweighted. The chip holds experts `held_start` ..
+ `held_count`: what an absent expert would add is LEFT OUT (no exchange), as
the deployment's share is defined; the router stays whole.

*: q, k, v, o without bias, H query heads over Hkv K/V heads (query head h reads
K/V head h // (H / Hkv)), causal softmax(q k^T / sqrt(d)) v, no positional term.

Departures from the published description: none of a sum's terms. The
vocabulary is the held slice and the experts the held range (both the
deployment's, stated in the configuration file); the tree's storage order above.

Fitting the chip beside the model (the harness runs the reference inside the
replica): one block at a time is widened to float32, an expert block ONE EXPERT
at a time, the head a block of vocabulary columns at a time, and `make_logits`
hands back a HOST array.

Switches in `m` that make a WRONG reference, which a sound program must fail
(the benchmark's controls, `scripts/nemotron_h_tolerance.py`):
`state_reset_every` C (the state zeroed where t % C == 0), `tail_reset_every` C
(the convolution sees nothing from before the last multiple of C), `state_bf16`
(the state rounded to bfloat16 after every token, by `lax.reduce_precision`),
`no_skip` (D x left out), `gate_after_norm` (N_groups(y) * silu(z)),
`norm_groups` (another group count for N_groups), `expert_act` "relu" (relu for
relu^2), `no_select_bias`, `top_k_wrong` (another k), `rotary` (a rotary term of
`rotary` as theta put into attention); `shared` false leaves the shared expert
out (the share test)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

COL_BLOCK = 8192       # vocabulary columns a block of the head

_M2 = ("ln_w", "w_in", "conv_w", "conv_b", "dt_bias", "A_log", "D", "norm_w", "w_out")
_ATTN = ("attn_ln_w", "w_q", "w_kv", "w_o")


def _rms(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _rotate(x, theta):
    """x [T, heads, d] with the rotary term of a wrong reference (halves)."""
    T, _, d = x.shape
    ang = jnp.arange(T)[:, None] * theta ** (-jnp.arange(0, d, 2) / d)[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attention(h, p, m: dict):
    """h [T, E] -> what an attention block adds to the stream."""
    T = h.shape[0]
    q = jnp.einsum("te,ehd->thd", h, p["w_q"])                      # [T, H, d]
    kv = jnp.einsum("te,exgd->xtgd", h, p["w_kv"])                  # [2, T, Hkv, d]
    H, Hkv = q.shape[1], kv.shape[2]
    k, v = (jnp.repeat(a, H // Hkv, axis=1) for a in kv)
    if m.get("rotary"):
        q, k = _rotate(q, m["rotary"]), _rotate(k, m["rotary"])
    att = jnp.einsum("shd,thd->hst", q, k) / np.sqrt(q.shape[-1])
    seen = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    att = jax.nn.softmax(jnp.where(seen[None], att, -jnp.inf), -1)
    return jnp.einsum("hst,thd,hde->se", att, v, p["w_o"])


def mamba2(h, p, m: dict):
    """h [T, E] -> what the Mamba-2 mixer adds to the stream, token by token."""
    T = h.shape[0]
    H, P, N, G, K = m["ssm_heads"], m["ssm_head_dim"], m["d_state"], m["ssm_groups"], m["d_conv"]
    Di = H * P
    zxd = h @ p["w_in"]
    z, xbc, dt = zxd[:, :Di], zxd[:, Di:2 * Di + 2 * G * N], zxd[:, 2 * Di + 2 * G * N:]
    t = jnp.arange(T)
    conv = p["conv_b"][None, :]
    for j in range(K):                       # xBC_{t-K+1+j}; nothing before token 0
        src = t - (K - 1) + j
        first = 0 if not m.get("tail_reset_every") else t - t % m["tail_reset_every"]
        conv = conv + jnp.where((src >= first)[:, None],
                                xbc[jnp.maximum(src, 0)] * p["conv_w"][j][None, :], 0.0)
    xbc = jax.nn.silu(conv)
    x = xbc[:, :Di].reshape(T, H, P)
    Bm, Cm = (jnp.repeat(a.reshape(T, G, N), H // G, axis=1)         # a head reads its group's
              for a in (xbc[:, Di:Di + G * N], xbc[:, Di + G * N:]))
    delta = jax.nn.softplus(dt + p["dt_bias"][None, :])             # [T, H]
    A = -jnp.exp(p["A_log"])                                        # [H]

    def token(s, inp):
        i, d, xt, bt, ct = inp
        if m.get("state_reset_every"):
            s = jnp.where(i % m["state_reset_every"] == 0, 0.0, s)
        s = jnp.exp(d * A)[:, None, None] * s + (d[:, None] * xt)[:, :, None] * bt[:, None, :]
        if m.get("state_bf16"):     # not a pair of converts: the chip's compiler
            s = jax.lax.reduce_precision(s, 8, 7)   # keeps excess precision through one
        return s, jnp.einsum("hpn,hn->hp", s, ct)

    _, y = jax.lax.scan(token, jnp.zeros((H, P, N), jnp.float32), (t, delta, x, Bm, Cm))
    if not m.get("no_skip"):
        y = y + p["D"][None, :, None] * x
    y = y.reshape(T, Di)
    gate = jax.nn.silu(z)

    def grouped(a):
        parts = a.reshape(T, m.get("norm_groups", G), -1)
        return (parts * jax.lax.rsqrt((parts * parts).mean(-1, keepdims=True)
                                      + m["norm_eps"])).reshape(T, Di)

    normed = grouped(y) * gate if m.get("gate_after_norm") else grouped(y * gate)
    return (normed * p["norm_w"][None, :]) @ p["w_out"]


def route(h, router, bias, m: dict):
    """h [T, E] -> combine [T, experts] float32: a token's weight at each expert
    it chose, 0 elsewhere."""
    scores = jax.nn.sigmoid(h @ router)                             # [T, X]
    chosen_by = scores if m.get("no_select_bias") else scores + bias[None, :]
    idx = jnp.argsort(-chosen_by, axis=-1)[:, : m.get("top_k_wrong", m["top_k"])]
    kept = jnp.take_along_axis(scores, idx, axis=-1)
    w = kept / kept.sum(-1, keepdims=True) * m["route_scale"]
    return (jax.nn.one_hot(idx, scores.shape[-1]) * w[..., None]).sum(-2)


def _act(u, m: dict):
    return jax.nn.relu(u) if m.get("expert_act") == "relu" else jnp.square(jax.nn.relu(u))


def experts(h, p, block: int, m: dict):
    """h [T, E] -> what expert block `block` adds to the stream ON THIS CHIP:
    its held experts' weighted outputs, one expert at a time out of the whole
    stacks (`moe_w_in`, `moe_w_out` [blocks, held, ...]), and the shared expert."""
    f32 = jnp.float32
    combine = route(h, p["moe_router"].astype(f32), p["moe_select_bias"].astype(f32), m)
    combine = combine[:, m["held_start"]: m["held_start"] + m["held_count"]]

    def one(y, e):
        w_in, w_out = (jax.lax.dynamic_slice(      # one expert's matrix where it lies
            a, (block, e, 0, 0), (1, 1) + a.shape[2:])[0, 0].astype(f32)
            for a in (p["moe_w_in"], p["moe_w_out"]))
        w = jax.lax.dynamic_index_in_dim(combine, e, 1, True)
        return y + w * (_act(h @ w_in.T, m) @ w_out), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(m["held_count"]))
    if m.get("shared", True):
        y = y + _act(h @ p["shared_w_in"].astype(f32), m) @ p["shared_w_out"].astype(f32)
    return y


def make_logits(m: dict):
    """(params, tokens [T]) -> logits [T, V] float32 as a HOST array."""
    eps = m["norm_eps"]

    @jax.jit
    def mamba_block(x, p):
        with jax.default_matmul_precision("highest"):
            p = {k: v.astype(jnp.float32) for k, v in p.items()}
            return x + mamba2(_rms(x, p["ln_w"], eps), p, m)

    @jax.jit
    def attention_block(x, p):
        with jax.default_matmul_precision("highest"):
            p = {k: v.astype(jnp.float32) for k, v in p.items()}
            return x + attention(_rms(x, p["attn_ln_w"], eps), p, m)

    @jax.jit
    def expert_block(x, p, block):          # `block` traced: the stacks stay whole
        with jax.default_matmul_precision("highest"):
            h = _rms(x, p["moe_ln_w"].astype(jnp.float32), eps)
            return x + experts(h, p, block, m)

    @jax.jit
    def head(x, ln, w):
        with jax.default_matmul_precision("highest"):
            return _rms(x, ln.astype(jnp.float32), eps) @ w.astype(jnp.float32)

    def fn(params, tokens):
        x = params["tok_embed"][jnp.asarray(tokens, jnp.int32)].astype(jnp.float32)
        index = dict.fromkeys("ME*", 0)
        for kind in m["pattern"]:
            i = index[kind]
            index[kind] += 1
            if kind == "M":
                x = mamba_block(x, {k: params["m2_" + k][i] for k in _M2})
            elif kind == "*":
                x = attention_block(x, {k: params[k][i] for k in _ATTN})
            else:
                own = {k: params[k][i] for k in ("moe_ln_w", "moe_router", "moe_select_bias",
                                                 "shared_w_in", "shared_w_out")}
                x = expert_block(x, {**own, "moe_w_in": params["moe_w_in"],
                                     "moe_w_out": params["moe_w_out"]}, jnp.int32(i))
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
