"""Plain reference of the SambaY decoder-hybrid-decoder as Phi-4-mini-flash-
reasoning runs it (`model_type: "phi4flash"`, arXiv:2507.06607; differential
attention after the Differential Transformer's `multihead_flashdiff_2`, which
the published modelling file follows): float32 `jax.numpy`, matmuls at
`highest` precision, ALL layers at ALL positions, the recurrence ONE sequential
scan over single tokens, differential attention as four dense softmax products
a pair, no cache, no chunks, no slots, no kernel, no skipped position.
Independent of `ray_tpu/models/gpt.py` and `ray_tpu/ops/`: it shares only the
layout of the parameter tree, which is the interface under test (stacks by a
layer's place in its pair: `sm_*`, `sa_*` [self pairs, ...], `cg_*`, `ca_*`
[cross pairs, ...]).

With N = `num_hidden_layers`, layer l, x the residual stream [T, E], LN =
LayerNorm with weight AND bias (eps `layer_norm_eps`):

    a = x + Mixer_l(LN1_l(x));   x <- a + W_down(silu(W_gate g) * (W_up g)), g = LN2_l(a)

    l even, l <= N/2    Mamba-1 (below); layer N/2 also hands out m = y
    l odd,  l <  N/2    differential attention, query i sees keys i - window < j <= i
    l = N/2 + 1         differential attention, causal over everything
    l even, l >  N/2    gated memory unit: W_o (m * silu(W_g h)), m layer N/2's
    l odd,  l >  N/2+1  differential CROSS attention: its own queries over the
                        k, v that layer N/2 + 1 made, causal over everything

Mamba-1, for token t (h = LN1(x), Di = 2 E, K taps, N_s states, R the step's rank):

    [u_t ; z_t] = W_in h_t
    c_t = silu(b_conv + sum_{j<K} w_conv[j] * u_{t-K+1+j})       u_{<0} = 0
    [d_t ; B_t ; C_t] = W_x c_t                                  no norm of any
    delta_t = softplus(W_dt d_t + b_dt)
    s_t = exp(delta_t[:, None] * A) * s_{t-1} + (delta_t * c_t)[:, None] * B_t[None, :]
    y_t = s_t C_t + D * c_t;   out_t = W_out (y_t * silu(z_t));   m_t = y_t

with A = -exp(A_log) [Di, N_s] and s_{-1} = 0.

Differential attention (H query heads, Hkv K/V heads of d; heads pair up in
order: (q1_i, q2_i) = query heads (2i, 2i+1), (k1_j, k2_j) and (v1_j, v2_j) =
K/V heads (2j, 2j+1); query pair i reads K/V pair j = i // (H / Hkv)):

    [q ; k ; v] = W_qkv h + b      (a cross layer: q = W_q h + b alone)
    P1 = softmax(q1 k1^T / sqrt(d)), P2 = softmax(q2 k2^T / sqrt(d)) under the mask
    o_i = P1 [v1 ; v2] - lambda_l P2 [v1 ; v2]
    lambda_l = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init(l),  lambda_init(l) = 0.8 - 0.6 exp(-0.3 l)
    o_i <- o_i / rms(o_i) * gain * (1 - lambda_init(l))          over its 2 d columns, eps 1e-5
    out = W_o concat_i(o_i) + b_o

Final LN, logits = stream x embedding^T (tied), no scale.

Departures from the published storage, none of which changes a sum's terms:
the MLP's fused `gate_up_proj` lies as two matrices (gate, up); `A_log` and the
convolution's taps lie state-major and tap-major ([N_s, Di], [K, Di]).

Fitting the chip beside the model (the harness runs the reference inside the
replica): one layer at a time is widened to float32, attention a query pair's
K/V pair at a time, the head a block of vocabulary columns at a time, and
`make_logits` hands back a HOST array.

`m` of `dims` may carry the keys of a deliberately WRONG reference, which
`scripts/phi4flash_tolerance.py` uses to show that the comparison sees each
mechanism (`_WRONG` lists them)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

COL_BLOCK = 8192       # vocabulary columns a block of the head

_WRONG = ("no_lambda", "no_subln", "no_lambda_scale", "one_lambda_init", "window_extra",
          "m_after_gate", "m_without_skip", "gmu_own_input", "cross_reads_pair",
          "state_reset_every", "tail_reset_every", "no_ln_bias", "state_bf16")


def _ln(x, w, b, m):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    y = (x - mean) * jax.lax.rsqrt(var + m["norm_eps"]) * w
    return y if m.get("no_ln_bias") else y + b


def _mamba(h, p, m: dict):
    """h [T, E] -> (what the mixer adds to the stream, m_t = y_t), token by token."""
    T = h.shape[0]
    N, R = m["d_state"], m["dt_rank"]
    Di, K = p["conv_b"].shape[0], p["conv_w"].shape[0]
    uz = h @ p["w_in"]
    u, z = uz[:, :Di], uz[:, Di:]
    t = jnp.arange(T)
    conv = p["conv_b"][None, :]
    for j in range(K):                       # u_{t-K+1+j}; nothing before token 0
        src = t - (K - 1) + j
        first = 0 if not m.get("tail_reset_every") else t - t % m["tail_reset_every"]
        conv = conv + jnp.where((src >= first)[:, None],
                                u[jnp.maximum(src, 0)] * p["conv_w"][j][None, :], 0.0)
    c = jax.nn.silu(conv)
    dbc = c @ p["w_x"]
    d, Bm, Cm = dbc[:, :R], dbc[:, R:R + N], dbc[:, R + N:]
    delta = jax.nn.softplus(d @ p["w_dt"] + p["b_dt"])              # [T, Di]
    A = -jnp.exp(p["A_log"]).T                                      # [Di, N]

    def token(s, inp):
        i, dl, ct, bt, cc = inp
        if m.get("state_reset_every"):
            s = jnp.where(i % m["state_reset_every"] == 0, 0.0, s)
        s = jnp.exp(dl[:, None] * A) * s + (dl * ct)[:, None] * bt[None, :]
        if m.get("state_bf16"):     # not a pair of converts: the chip's compiler
            s = jax.lax.reduce_precision(s, 8, 7)   # keeps excess precision through one
        return s, s @ cc

    _, y = jax.lax.scan(token, jnp.zeros((Di, N), jnp.float32), (t, delta, c, Bm, Cm))
    skip = p["D"][None, :] * c
    gate = jax.nn.silu(z)
    handed = y if m.get("m_without_skip") else y + skip
    return ((y + skip) * gate) @ p["w_out"], handed * gate if m.get("m_after_gate") else handed


def _diff_attention(q, k, v, p, layer, window, m: dict):
    """q [T, H, d], k, v [T, Hkv, d] -> what differential attention adds to the
    stream [T, E]; `layer` the layer's index and `window` the keys a query sees
    (at least T: causal over everything), both traced scalars."""
    T, H, d = q.shape
    Hkv = k.shape[1]
    q, k, v = (a.reshape(T, a.shape[1] // 2, 2, d) for a in (q, k, v))
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    seen = (j <= i) & (j > i - window)
    lam = p["lam"]
    init = 0.8 - 0.6 * jnp.exp(-0.3 * (0.0 if m.get("one_lambda_init") else layer))
    full = jnp.exp(lam[0] @ lam[1]) - jnp.exp(lam[2] @ lam[3]) + init
    outs = []
    for pair in range(H // 2):
        kp, vp = k[:, pair // (H // Hkv)], v[:, pair // (H // Hkv)]     # [T, 2, d]
        values = vp.reshape(T, 2 * d)
        probs = [jax.nn.softmax(jnp.where(seen, q[:, pair, half] @ kp[:, half].T / np.sqrt(d),
                                          -jnp.inf), -1) for half in (0, 1)]
        o = probs[0] @ values
        if not m.get("no_lambda"):
            o = o - full * (probs[1] @ values)
        if not m.get("no_subln"):
            o = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True) + m["norm_eps"]) * p["sub_w"]
        outs.append(o if m.get("no_lambda_scale") else o * (1.0 - init))
    return jnp.concatenate(outs, axis=-1) @ p["w_o"] + p["b_o"]


def make_logits(m: dict):
    """(params, tokens [T]) -> logits [T, V] float32 as a HOST array."""
    N, H, Hkv, d = m["n_layers"], m["n_heads"], m["n_kv_heads"], m["d_head"]
    P = N // 4 + 1                          # self-decoder pairs, the last (m, f)

    def mlp(x, p):
        g = _ln(x, p["ln2_w"], p["ln2_b"], m)
        return x + (jax.nn.silu(g @ p["w_gate"]) * (g @ p["w_in"])) @ p["w_out"]

    def widened(fn):
        def run(p, *args):
            with jax.default_matmul_precision("highest"):
                return fn({k: v.astype(jnp.float32) for k, v in p.items()}, *args)
        return jax.jit(run)

    @widened
    def mamba_layer(p, layer, x):
        h = _ln(x, p["ln1_w"], p["ln1_b"], m)
        out, handed = _mamba(h, {k[4:]: v for k, v in p.items() if k.startswith("ssm_")}, m)
        return mlp(x + out, p), handed

    @widened
    def attention_layer(p, layer, x):
        h = _ln(x, p["ln1_w"], p["ln1_b"], m)
        qkv = h @ p["w_qkv"] + p["b_qkv"]
        q, k, v = (a.reshape(a.shape[0], -1, d)
                   for a in jnp.split(qkv, (H * d, (H + Hkv) * d), axis=-1))
        window = jnp.where(layer == N // 2 + 1, x.shape[0], m["window"] + m.get("window_extra", 0))
        return mlp(x + _diff_attention(q, k, v, p, layer, window, m), p), (k, v)

    @widened
    def memory_layer(p, layer, x, handed):
        h = _ln(x, p["ln1_w"], p["ln1_b"], m)
        if m.get("gmu_own_input"):          # its own input, tiled to the inner width
            handed = jnp.tile(h, (1, handed.shape[1] // h.shape[1]))
        return mlp(x + (handed * jax.nn.silu(h @ p["gmu_gate"])) @ p["gmu_out"], p)

    @widened
    def cross_layer(p, layer, x, k, v):
        h = _ln(x, p["ln1_w"], p["ln1_b"], m)
        q = (h @ p["w_q"] + p["b_q"]).reshape(h.shape[0], H, d)
        return mlp(x + _diff_attention(q, k, v, p, layer, x.shape[0], m), p)

    @jax.jit
    def head(x, w, b, table):
        with jax.default_matmul_precision("highest"):
            f32 = jnp.float32
            return _ln(x, w.astype(f32), b.astype(f32), m) @ table.astype(f32).T

    def stack(params, role, i):
        return {k[3:]: v[i] for k, v in params.items() if k.startswith(role + "_")}

    def fn(params, tokens):
        x = params["tok_embed"][jnp.asarray(tokens, jnp.int32)].astype(jnp.float32)
        rows = {}
        for i in range(P):
            x, handed = mamba_layer(stack(params, "sm", i), jnp.float32(2 * i), x)
            x, rows[i] = attention_layer(stack(params, "sa", i), jnp.float32(2 * i + 1), x)
        k, v = rows[m.get("cross_reads_pair", P - 1)]
        for i in range(N // 2 - P):
            x = memory_layer(stack(params, "cg", i), jnp.float32(2 * (P + i)), x, handed)
            x = cross_layer(stack(params, "ca", i), jnp.float32(2 * (P + i) + 1), x, k, v)
        w = params["tok_embed"]
        return np.concatenate(
            [np.asarray(head(x, params["ln_f_w"], params["ln_f_b"], w[c: c + COL_BLOCK]))
             for c in range(0, w.shape[0], COL_BLOCK)], axis=-1)

    return fn


def make_loss(m: dict):
    """(params, tokens [S+1]) -> summed next-token cross-entropy (float32)."""
    logits = make_logits(m)

    def loss(params, tokens):
        lg = logits(params, tokens[:-1])
        logp = lg - np.logaddexp.reduce(lg, axis=-1, keepdims=True)
        return float(-np.take_along_axis(logp, np.asarray(tokens[1:])[:, None], -1).sum())

    return loss
