"""Plain reference of the Jamba layer stack (AI21, `model_type: "jamba"`;
written from the published `modeling_jamba.py`'s description of the layer,
its slow path): float32 `jax.numpy`, matmuls at `highest` precision, ONE
sequential scan over the whole sequence, no cache, no chunks, no slots, no
kernel. Independent of `ray_tpu/models/gpt.py` and `ray_tpu/ops/ssm.py`: it
shares only the layout of the parameter tree, which is the interface under
test (the state-space stack `ssm_*` [26, ...], the attention stack [2, ...],
norms and MLP [28, ...]; `ssm_A_log` and `ssm_conv_w` lie state-major and
tap-major, [N, Di] and [K, Di], the published [Di, N] and [Di, 1, K]
transposed).

One layer, x the residual stream [T, E], N = RMSNorm (eps `rms_norm_eps`):
a = x + Mixer(N1(x)); y = a + W_down(silu(W_gate g) * (W_up g)), g = N2(a).
Layer i is an attention layer where i % `attn_layer_period` ==
`attn_layer_offset`, else a Mamba layer.

Attention: q, k, v, o without bias, H query heads over Hkv K/V heads (query
head h reads K/V head h // (H / Hkv)), causal softmax(q k^T / sqrt(d)) v, no
positional term of any kind.

Mamba mixer, for token t (h = N1(x), Di = `mamba_expand` x E, K = `mamba_d_conv`,
N = `mamba_d_state`, R = `mamba_dt_rank`):

    [u_t ; z_t] = W_in h_t
    c_t = silu(b_conv + sum_{j<K} w_conv[j] * u_{t-K+1+j})       u_{<0} = 0
    [d_t ; B_t ; C_t] = W_x c_t;  d, B, C each through an RMSNorm of its own
    delta_t = softplus(W_dt d_t + b_dt)
    s_t = exp(delta_t[:, None] * A) * s_{t-1} + (delta_t * c_t)[:, None] * B_t[None, :]
    y_t = s_t C_t + D * c_t;   out_t = W_out (y_t * silu(z_t))

with A = -exp(A_log) [Di, N] and s_{-1} = 0. Final N, logits = stream x
embedding^T (tied), no scale.

Fitting the chip beside the model (the harness runs the reference inside the
replica): one layer at a time is widened to float32, the head a block of
vocabulary columns at a time, and `make_logits` hands back a HOST array. None
of these changes a sum's terms.

Switches in `m` that make a WRONG reference, which a sound program must fail
(the benchmark's controls, `scripts/jamba_tolerance.py`): `state_reset_every`
C (the scan's state zeroed where t % C == 0: a chunk that does not continue
the chunk before it), `tail_reset_every` C (the convolution sees no input from
before the last multiple of C), `state_bf16` (the state rounded to bfloat16
after every token, by `lax.reduce_precision`: a convert to bfloat16 and back
is elided on the chip, where the control then read the sound engine's own
number in every seed), `inner_norms` false (d, B, C used as projected)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

COL_BLOCK = 8192       # vocabulary columns a block of the head

_SHARED = ("ln1_w", "ln2_w", "w_gate", "w_in", "w_out")
_ATTN = ("w_q", "w_kv", "w_o")
_SSM = ("w_in", "conv_w", "conv_b", "w_x", "dt_norm_w", "b_norm_w", "c_norm_w",
        "w_dt", "b_dt", "A_log", "D", "w_out")


def _rms(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _attention(h, p):
    """h [T, E] -> what attention adds to the stream."""
    T = h.shape[0]
    q = jnp.einsum("te,ehd->thd", h, p["w_q"])                      # [T, H, d]
    kv = jnp.einsum("te,exgd->xtgd", h, p["w_kv"])                  # [2, T, Hkv, d]
    H, Hkv = q.shape[1], kv.shape[2]
    k, v = (jnp.repeat(a, H // Hkv, axis=1) for a in kv)
    att = jnp.einsum("shd,thd->hst", q, k) / np.sqrt(q.shape[-1])
    seen = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    att = jax.nn.softmax(jnp.where(seen[None], att, -jnp.inf), -1)
    return jnp.einsum("hst,thd,hde->se", att, v, p["w_o"])


def _mamba(h, p, m: dict):
    """h [T, E] -> what the Mamba mixer adds to the stream, token by token."""
    T = h.shape[0]
    eps, N, R = m["norm_eps"], m["d_state"], m["dt_rank"]
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
    if m.get("inner_norms", True):
        d, Bm, Cm = (_rms(d, p["dt_norm_w"], eps), _rms(Bm, p["b_norm_w"], eps),
                     _rms(Cm, p["c_norm_w"], eps))
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
    y = y + p["D"][None, :] * c
    return (y * jax.nn.silu(z)) @ p["w_out"]


def make_logits(m: dict):
    """(params, tokens [T]) -> logits [T, V] float32 as a HOST array."""

    def layer(x, p, mamba):
        with jax.default_matmul_precision("highest"):
            p = {k: v.astype(jnp.float32) for k, v in p.items()}
            h = _rms(x, p["ln1_w"], m["norm_eps"])
            mixer = {k[4:]: v for k, v in p.items() if k.startswith("ssm_")}
            x = x + (_mamba(h, mixer, m) if mamba else _attention(h, p))
            g = _rms(x, p["ln2_w"], m["norm_eps"])
            return x + (jax.nn.silu(g @ p["w_gate"]) * (g @ p["w_in"])) @ p["w_out"]

    layers = {kind: jax.jit(lambda x, p, kind=kind: layer(x, p, kind))
              for kind in (True, False)}

    @jax.jit
    def head(x, ln, w):
        with jax.default_matmul_precision("highest"):
            return _rms(x, ln.astype(jnp.float32), m["norm_eps"]) @ w.astype(jnp.float32).T

    def fn(params, tokens):
        x = params["tok_embed"][jnp.asarray(tokens, jnp.int32)].astype(jnp.float32)
        a = s = 0
        for i in range(m["n_layers"]):
            mamba = i % m["attn_period"] != m["attn_offset"]
            own = ({"ssm_" + k: params["ssm_" + k][s] for k in _SSM} if mamba
                   else {k: params[k][a] for k in _ATTN})
            x = layers[mamba](x, {**{k: params[k][i] for k in _SHARED}, **own})
            a, s = a + (not mamba), s + mamba
        w = params["tok_embed"]
        return np.concatenate(
            [np.asarray(head(x, params["ln_f_w"], w[c: c + COL_BLOCK]))
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
