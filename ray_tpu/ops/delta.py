"""The gated delta rule (linear attention whose state is a matrix a head that
is DECAYED and then CORRECTED by what it already predicts for the key; Gated
DeltaNet, arXiv:2412.06464, as the `qwen3_next` family's linear layers run it)
and the mixer built from it. Like `ops/ssm.py`'s scans it is ONE function that
a prefill chunk and a decode step both call, from the state a lane brings.

For a token t of a value head (its key head's q_t, k_t in R^K already
L2-normalised, q_t also over sqrt(K); v_t in R^V; g_t <= 0 the log decay;
beta_t in (0, 1); S in R^{K x V}, float32):

    S <- exp(g_t) S                     the decay
    u  = S^T k_t                        what the state predicts for this key
    S <- S + k_t (beta_t (v_t - u))^T   the correction: neither Mamba has it
    o_t = S^T q_t

Three bodies behind `delta_scan`: `delta_plain`, the recurrence as written
(what the tests hold the others to); `delta_step`, one token a lane (a decode
step: the state read, advanced and written); `delta_chunked`, the published
`chunk_gated_delta_rule` over chunks of C tokens. Inside a chunk, with
gamma_i the running sum of g and, for j < i,

    A_ij = -beta_i (k_i . k_j) exp(gamma_i - gamma_j)       strictly lower
    T = (I - A)^-1        W = T (beta k exp(gamma))         U = T (beta v)

a chunk that starts from the state S gives

    v' = U - W S
    o  = (q exp(gamma)) S + ((q k^T) exp(gamma_i - gamma_j), j <= i) v'
    S <- exp(gamma_C) S + (k exp(gamma_C - gamma))^T v'

T is a unit lower-triangular inverse: A is nilpotent (A^C = 0), so T = (I + A)
(I + A^2)(I + A^4)... in log2(C) products of [C, C] matrices, float32 at the
`highest` precision (forward substitution is C dependent steps of one row).
Every other sum is a matrix product with operands in `dtype` and float32 sums;
the decays and the state stay float32, and the state passes from chunk to chunk
in a `lax.scan`.

Every function takes a per-token `valid` mask [B, S] whose true entries LEAD: a
masked token has g = 0 and beta = 0, which leaves the state bit for bit what it
was, and what it writes to the output nobody reads.

In a device trace the chunk form's operations carry the scope `gdn_chunk` and
the step's `gdn_step` in their `op_name` (`jax.named_scope`); the compiler
names the instructions themselves by what they are (fusions, convolutions)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .ssm import _by_head, causal_conv


def delta_state_shape(heads: int, key_dim: int, value_dim: int) -> tuple:
    """The shape one layer's delta-rule state is kept in, a sequence."""
    return (heads, key_dim, value_dim)


def l2norm(x, eps: float = 1e-6):
    """x / sqrt(sum x^2 + eps) over the last axis, float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + eps)


def _delta_token(s, q, k, v, g, beta):
    """One token: s [B, H, K, V], q, k [B, H, K], v [B, H, V], g, beta [B, H],
    all f32 -> (o [B, H, V], the state after it)."""
    s = jnp.exp(g)[..., None, None] * s
    u = (s * k[..., None]).sum(-2)
    s = s + k[..., None] * (beta[..., None] * (v - u))[..., None, :]
    return (s * q[..., None]).sum(-2), s


def delta_plain(q, k, v, g, beta, s0):
    """One trip a token: q, k [B, S, G, K], v [B, S, H, V], g, beta [B, S, H],
    s0 [B, H, K, V], all f32 -> (o [B, S, H, V], s)."""
    H = v.shape[2]

    def step(s, inp):
        qt, kt, vt, gt, bt = inp
        o, s = _delta_token(s, _by_head(qt, H), _by_head(kt, H), vt, gt, bt)
        return s, o

    s, o = jax.lax.scan(step, s0, tuple(a.swapaxes(0, 1) for a in (q, k, v, g, beta)))
    return o.swapaxes(0, 1), s


def delta_step(q, k, v, g, beta, s0):
    """A decode step, S = 1. Shapes as `delta_plain`."""
    H = v.shape[2]
    with jax.named_scope("gdn_step"):
        o, s = _delta_token(s0, _by_head(q[:, 0], H), _by_head(k[:, 0], H), v[:, 0],
                            g[:, 0], beta[:, 0])
    return o[:, None], s


def _unit_lower_inverse(a):
    """(I - a)^-1 for a [..., C, C] STRICTLY lower triangular, float32."""
    C = a.shape[-1]
    mm = lambda x, y: jnp.matmul(x, y, precision=jax.lax.Precision.HIGHEST)
    t, p = jnp.eye(C, dtype=a.dtype) + a, a
    for _ in range(max(0, math.ceil(math.log2(C)) - 1)):
        p = mm(p, p)
        t = t + mm(t, p)
    return t


def delta_chunked(q, k, v, g, beta, s0, chunk: int, dtype=jnp.bfloat16):
    """The chunked form (the module's docstring) over chunks of C = `chunk`
    tokens, a last partial chunk padded with tokens of g = 0 and beta = 0, which
    move nothing. Shapes as `delta_plain`."""
    f32 = jnp.float32
    B, S, G, K = q.shape
    H, V = v.shape[2:]
    R, C = H // G, min(chunk, S)
    pad = -S % C
    if pad:
        q, k, v, g, beta = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                            for a in (q, k, v, g, beta))
    N = (S + pad) // C
    with jax.named_scope("gdn_chunk"):
        qc, kc = (a.reshape(B, N, C, G, K).astype(dtype) for a in (q, k))
        vc = v.reshape(B, N, C, G, R, V)
        # decays and betas head-major, [B, N, G, R, C]: tokens fill the lanes
        gam = jnp.cumsum(g.reshape(B, N, C, G, R).transpose(0, 1, 3, 4, 2), axis=-1)
        bet = beta.reshape(B, N, C, G, R).transpose(0, 1, 3, 4, 2)
        i, j = jnp.arange(C)[:, None], jnp.arange(C)[None, :]
        # exp(gamma_i - gamma_j) for j <= i, else 0                      [B, N, G, R, i, j]
        decay = jnp.exp(jnp.where(i >= j, gam[..., :, None] - gam[..., None, :], -jnp.inf))
        kk = jnp.einsum("bnigk,bnjgk->bngij", kc, kc, preferred_element_type=f32)
        a = -bet[..., None] * kk[:, :, :, None] * jnp.where(i > j, decay, 0.0)
        t = _unit_lower_inverse(a).astype(dtype)
        tok = lambda x: x.transpose(0, 1, 4, 2, 3)[..., None]          # [B, N, C, G, R, 1]
        kh = kc.astype(f32)[:, :, :, :, None, :]                       # [B, N, C, G, 1, K]
        w = jnp.einsum("bngrij,bnjgrk->bnigrk", t, (tok(bet * jnp.exp(gam)) * kh).astype(dtype),
                       preferred_element_type=f32).astype(dtype)
        u = jnp.einsum("bngrij,bnjgrv->bnigrv", t, (tok(bet) * vc).astype(dtype),
                       preferred_element_type=f32)
        qk = jnp.einsum("bnigk,bnjgk->bngij", qc, kc, preferred_element_type=f32)
        mix = (qk[:, :, :, None] * decay).astype(dtype)
        q_in = (tok(jnp.exp(gam)) * qc.astype(f32)[:, :, :, :, None, :]).astype(dtype)
        k_out = (tok(jnp.exp(gam[..., -1:] - gam)) * kh).astype(dtype)
        whole = jnp.exp(gam[..., -1])                                   # [B, N, G, R]

        def carry(s, inp):
            w, u, q_in, mix, k_out, whole = inp
            sd = s.astype(dtype)
            fresh = (u - jnp.einsum("bigrk,bgrkv->bigrv", w, sd,
                                    preferred_element_type=f32)).astype(dtype)
            o = (jnp.einsum("bigrk,bgrkv->bigrv", q_in, sd, preferred_element_type=f32)
                 + jnp.einsum("bgrij,bjgrv->bigrv", mix, fresh, preferred_element_type=f32))
            s = whole[..., None, None] * s + jnp.einsum(
                "bjgrk,bjgrv->bgrkv", k_out, fresh, preferred_element_type=f32)
            return s, o

        s, o = jax.lax.scan(carry, s0.reshape(B, G, R, K, V), tuple(
            x.swapaxes(0, 1) for x in (w, u, q_in, mix, k_out, whole)))
    return o.swapaxes(0, 1).reshape(B, N * C, H, V)[:, :S], s.reshape(B, H, K, V)


def delta_scan(q, k, v, g, beta, s0, valid, chunk: int = 64, form=None, dtype=jnp.bfloat16):
    """The gated delta rule over the S tokens of every lane from the state it is
    handed: q, k [B, S, G, K] (normalised), v [B, S, H, V], g [B, S, H] f32 (<=
    0), beta [B, S, H] f32, s0 [B, H, K, V] f32, valid [B, S] -> (o [B, S, H, V]
    f32, s like s0). `form`: None = the step for one token a lane and the chunked
    form for more (its products' operands in `dtype`); "plain" forces the
    recurrence as written."""
    f32 = jnp.float32
    g, beta = (jnp.where(valid[..., None], a, 0.0) for a in (g, beta))
    q, k, v = (a.astype(f32) for a in (q, k, v))
    if form == "plain":
        return delta_plain(q, k, v, g, beta, s0)
    if q.shape[1] == 1:
        return delta_step(q, k, v, g, beta, s0)
    return delta_chunked(q, k, v, g, beta, s0, chunk, dtype)


def gated_rmsnorm(o, z, w, eps: float):
    """RMSNorm(o) * w * silu(z) over the last axis (a head's channels): the gate
    AFTER the norm, one plain gain a channel of a head -> f32."""
    o, z = o.astype(jnp.float32), z.astype(jnp.float32)
    o = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True) + eps)
    return o * w.astype(jnp.float32) * jax.nn.silu(z)


def gated_delta_mixer(p, h, tail, s, valid, *, key_heads: int, chunk: int = 64,
                      eps: float = 1e-6, form=None):
    """The gated delta net over h [B, S, E] (the normed stream, compute dtype)
    from the state a lane brings: tail [B, taps - 1, 2 G K + H V] the
    convolution's last inputs, s [B, H, K, V] f32; `p` one layer's weights:
    w_qkvz [E, 2 G K + 2 H V] (columns q | k | v | z, each head-major), w_ba [E,
    2 H] (b | a), conv_w [taps, 2 G K + H V] (no bias), dt_bias, A_log [H],
    norm_w [V], w_out [H V, E]. Returns (out [B, S, E], new tail, new s)."""
    f32 = jnp.float32
    B, S, _ = h.shape
    H, K, V = s.shape[1:]
    G = key_heads
    qkvz = jnp.einsum("bse,ef->bsf", h, p["w_qkvz"])
    ba = jnp.einsum("bse,ef->bsf", h, p["w_ba"]).astype(f32)
    wide = 2 * G * K + H * V
    qkv, tail = causal_conv(qkvz[..., :wide], tail, p["conv_w"],
                            jnp.zeros((wide,), f32), valid)
    z = qkvz[..., wide:].reshape(B, S, H, V)
    q = l2norm(qkv[..., :G * K].reshape(B, S, G, K)) * K ** -0.5
    k = l2norm(qkv[..., G * K:2 * G * K].reshape(B, S, G, K))
    v = qkv[..., 2 * G * K:].reshape(B, S, H, V)
    beta = jax.nn.sigmoid(ba[..., :H])
    g = -jnp.exp(p["A_log"].astype(f32)) * jax.nn.softplus(ba[..., H:] + p["dt_bias"].astype(f32))
    o, s = delta_scan(q, k, v, g, beta, s, valid, chunk, form, h.dtype)
    o = gated_rmsnorm(o, z, p["norm_w"], eps).reshape(B, S, H * V)
    return jnp.einsum("bsd,de->bse", o.astype(h.dtype), p["w_out"]), tail, s
