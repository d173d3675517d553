"""The state-space mixer (Mamba-1 as the Jamba family runs it): a causal
depthwise convolution with a carried tail and a selective scan with a carried
state, each ONE function that a prefill chunk and a decode step both call (a
decode step is a chunk of one token), and the mixer built from them.

What a sequence carries between programs is the scan's state `s` and the
convolution's last `K - 1` inputs. For a token t of a sequence (h the normed
stream, Di the inner width, N the state's size, R the step's rank):

    [u_t ; z_t] = W_in h_t
    c_t = silu(b_conv + sum_j w_conv[j] * u_{t-K+1+j})      depthwise, causal
    [d_t ; B_t ; C_t] = W_x c_t, each under an RMSNorm of its own
    delta_t = softplus(W_dt d_t + b_dt)
    s_t = exp(delta_t * A) * s_{t-1} + (delta_t * c_t) B_t   A = -exp(A_log)
    y_t = s_t C_t + D * c_t
    out_t = W_out (y_t * silu(z_t))

The state is float32 (it accumulates over every token of a context) and is
kept as [N, Di / 128, 128] (`state_shape`): a channel block of the kernel is
then whole (8, 128) tiles with nothing to re-lay between HBM and the kernel.

Every function takes a per-token `valid` mask [B, S] whose true entries LEAD
(what the programs pad: a chunk on its right, a lane whole): a masked token
leaves the state and the tail bit for bit what they were, and what it writes
to the output nobody reads.

The scan has two bodies behind one signature: `_scan_plain`, a `lax.scan`
over time (the CPU's, and what the tests hold the kernel to), and
`_scan_pallas`, the kernel the TPU runs, its time loop INSIDE the kernel and
the state resident in VMEM across it (named `ssm_scan` in the device trace)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .norms import rmsnorm

_LANES = 128


def state_shape(d_inner: int, d_state: int) -> tuple:
    """The shape one layer's scan state is kept in, a sequence."""
    lanes = _LANES if d_inner % _LANES == 0 else d_inner
    return (d_state, d_inner // lanes, lanes)


def causal_conv(u, tail, w, b, valid):
    """u [B, S, Di] (compute dtype), tail [B, K-1, Di] the inputs before it,
    w [K, Di], b [Di], valid [B, S] -> (c [B, S, Di] in u's dtype, the new
    tail): c_t = silu(b + sum_j w[j] * ext[t + j]) over ext = [tail ; u],
    float32 inside; the new tail is the K-1 rows of ext that end at the last
    valid token (the old tail itself where none is valid)."""
    K, S = w.shape[0], u.shape[1]
    ext = jnp.concatenate([tail.astype(u.dtype), u], axis=1)      # [B, S+K-1, Di]
    acc = b.astype(jnp.float32)
    for j in range(K):
        acc = acc + w[j].astype(jnp.float32) * ext[:, j:j + S].astype(jnp.float32)
    n = valid.sum(axis=1).astype(jnp.int32)
    new_tail = jax.vmap(
        lambda e, i: jax.lax.dynamic_slice_in_dim(e, i, K - 1, axis=0))(ext, n)
    return jax.nn.silu(acc).astype(u.dtype), new_tail


def _scan_plain(delta, x, A, Bm, Cm, s0):
    """The recurrence as written, one trip a token: delta, x [B, S, Di] f32,
    A [N, Di], Bm, Cm [B, S, N], s0 [B, N, Di] -> (y [B, S, Di], s)."""

    def step(s, inp):
        d, xt, bt, ct = inp                                 # [B, Di] x2, [B, N] x2
        s = jnp.exp(d[:, None] * A) * s + (d * xt)[:, None] * bt[:, :, None]
        return s, (s * ct[:, :, None]).sum(axis=1)

    s, ys = jax.lax.scan(
        step, s0, tuple(a.swapaxes(0, 1) for a in (delta, x, Bm, Cm)))
    return ys.swapaxes(0, 1), s


def _scan_pallas(delta, x, A, Bm, Cm, s0, interpret=False):
    """The same recurrence, the time loop inside the kernel. delta, x [B, S,
    Dg, 128] f32 (Dg = Di / 128); A [N, Dg, 128]; Bm, Cm [B, 1, S * N] f32, read
    as scalars from SMEM; s0 [B, N, Dg, 128] -> (y like delta, s like s0).
    Grid (lanes, channel blocks): a block is G x 128 channels with all N
    states, in the output block from the first token to the last; channels
    fill whole vregs (sublanes and lanes), the N states are a static loop,
    and B_t[n], C_t[n] are scalars: no broadcast across lanes, no reduction."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, S, Dg, L = delta.shape
    N = A.shape[0]
    # Channel block: 8 sublane groups (1,024 channels, one vreg a state) when
    # the chunk is long, so that the three [S, G, 128] blocks and their second
    # buffers stay within a few MiB; every channel where it is short (a decode
    # step), so that a lane is one grid step.
    G = 8 if Dg % 8 == 0 and S * Dg * L * 4 * 6 > 8 << 20 else Dg

    def kernel(delta_ref, x_ref, a_ref, b_ref, c_ref, s0_ref, y_ref, s_ref):
        s_ref[...] = s0_ref[...]

        def token(t, carry):
            d = delta_ref[t]                                # [G, 128]
            dx = d * x_ref[t]
            y = jnp.zeros_like(d)
            for n in range(N):
                s = jnp.exp(d * a_ref[n]) * s_ref[n] + dx * b_ref[0, t * N + n]
                s_ref[n] = s
                y = y + s * c_ref[0, t * N + n]
            y_ref[t] = y
            return carry

        jax.lax.fori_loop(0, S, token, 0)

    tokens = pl.BlockSpec((None, S, G, L), lambda b, j: (b, 0, j, 0))
    scalars = pl.BlockSpec((None, 1, S * N), lambda b, j: (b, 0, 0),
                           memory_space=pltpu.SMEM)
    state = pl.BlockSpec((None, N, G, L), lambda b, j: (b, 0, j, 0))
    return pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct(delta.shape, jnp.float32),
                   jax.ShapeDtypeStruct(s0.shape, jnp.float32)),
        grid=(B, Dg // G),
        in_specs=[tokens, tokens, pl.BlockSpec((N, G, L), lambda b, j: (0, j, 0)),
                  scalars, scalars, state],
        out_specs=(tokens, state),
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=48 << 20),
        interpret=interpret, name="ssm_scan",
    )(delta, x, A, Bm, Cm, s0)


def selective_scan(delta, x, A, Bm, Cm, s0, valid, kernel=None):
    """s_t = exp(delta_t A) s_{t-1} + (delta_t x_t) B_t, y_t = s_t C_t, over
    the S tokens of every lane from the state it is handed: delta [B, S, Di]
    f32 (after its softplus), x [B, S, Di], A [N, Di] f32 (negative), Bm, Cm
    [B, S, N] f32, s0 [B, *state_shape] f32, valid [B, S] -> (y [B, S, Di]
    f32, s like s0). A masked token's step is 0, which leaves the state as it
    was: exp(0) s + 0. `kernel`: None = the Pallas kernel on the TPU where the
    channels fill whole lanes and the plain scan elsewhere; True / False force
    one (the tests' interpret mode goes through `_scan_pallas` itself)."""
    from .attention import _on_tpu

    B, S, Di = delta.shape
    N = A.shape[0]
    delta = jnp.where(valid[..., None], delta, 0.0)
    x = x.astype(jnp.float32)
    if kernel is None:
        kernel = _on_tpu() and Di % _LANES == 0
    if not kernel:
        y, s = _scan_plain(delta, x, A, Bm, Cm, s0.reshape(B, N, Di))
        return y, s.reshape(s0.shape)
    tiled = (B, S, Di // _LANES, _LANES)
    y, s = _scan_pallas(
        delta.reshape(tiled), x.reshape(tiled), A.reshape(N, *tiled[2:]),
        Bm.reshape(B, 1, S * N), Cm.reshape(B, 1, S * N), s0)
    return y.reshape(B, S, Di), s


def mamba_mixer(p, h, tail, s, valid, eps: float = 1e-6, kernel=None):
    """The mixer over h [B, S, E] (the normed stream, compute dtype) from the
    state a lane brings: tail [B, K-1, Di], s [B, *state_shape] f32; `p` one
    layer's weights in the compute dtype (`A_log` as stored): w_in [E, 2 Di],
    conv_w [K, Di], conv_b [Di], w_x [Di, R + 2N], dt_norm_w [R], b_norm_w
    [N], c_norm_w [N], w_dt [R, Di], b_dt [Di], A_log [N, Di], D [Di], w_out
    [Di, E]. Returns (out [B, S, E], new tail, new s)."""
    f32 = jnp.float32
    Di, R, N = p["conv_b"].shape[-1], p["dt_norm_w"].shape[-1], p["b_norm_w"].shape[-1]
    uz = jnp.einsum("bse,ef->bsf", h, p["w_in"])
    u, z = uz[..., :Di], uz[..., Di:]
    c, tail = causal_conv(u, tail, p["conv_w"], p["conv_b"], valid)
    dbc = jnp.einsum("bsd,df->bsf", c, p["w_x"], preferred_element_type=f32)
    d = rmsnorm(dbc[..., :R], p["dt_norm_w"], eps)
    Bm = rmsnorm(dbc[..., R:R + N], p["b_norm_w"], eps)
    Cm = rmsnorm(dbc[..., R + N:], p["c_norm_w"], eps)
    delta = jax.nn.softplus(
        jnp.einsum("bsr,rd->bsd", d.astype(h.dtype), p["w_dt"],
                   preferred_element_type=f32) + p["b_dt"].astype(f32))
    A = -jnp.exp(p["A_log"].astype(f32))
    y, s = selective_scan(delta, c, A, Bm, Cm, s, valid, kernel)
    y = y + p["D"].astype(f32) * c.astype(f32)
    gated = (y * jax.nn.silu(z.astype(f32))).astype(h.dtype)
    return jnp.einsum("bsd,de->bse", gated, p["w_out"]), tail, s



# ------------------------------------------------------------------ Mamba-2
# The second recurrence (below the first, whose lines the compile cache's key
# holds: ROADMAP D20): one SCALAR decay a head over a MATRIX state a head. For
# a token t (h the normed stream; H heads of P channels, Di = H P; G groups of
# H / G heads that share B and C; N the state's size; K taps):
#
#     [z_t ; xBC_t ; dt_t] = W_in h_t                   Di | Di + 2 G N | H
#     xBC_t = silu(b_conv + sum_j w_conv[j] * xBC_{t-K+1+j})  x, B and C together
#     [x_t ; B_t ; C_t] = xBC_t                         [H, P] | [G, N] | [G, N]
#     delta_t = softplus(dt_t + dt_bias)                [H], not clamped
#     S_t = exp(delta_t A) S_{t-1} + delta_t x_t (x) B_t    per head [P, N], A = -exp(A_log) [H]
#     y_t = S_t C_t + D x_t                             D one scalar a head
#     out_t = W_out (RMSNorm_groups(y_t * silu(z_t)))   the mean square over each
#                                                       of the G groups of Di / G
#
# What a sequence carries is the convolution's tail [K - 1, Di + 2 G N] and the
# float32 state [H, P, N] (`mamba2_state_shape`: N = 128 fills the lanes). The
# recurrence has three bodies behind `ssd_scan`: `ssd_step`, one token a lane
# (a decode step: the state read, advanced and written, nothing else);
# `ssd_chunked`, the matmul-shaped form over chunks of `chunk` tokens (inside a
# chunk the products C B^T under the decays' mask, between chunks the carried
# state); `_ssd_plain`, the recurrence as written, what the tests hold both to.


def mamba2_state_shape(heads: int, head_dim: int, d_state: int) -> tuple:
    """The shape one Mamba-2 block's state is kept in, a sequence."""
    return (heads, head_dim, d_state)


def _by_head(a, H):
    """[..., G, N] a group -> [..., H, N] a head (head i reads group i // (H / G))."""
    return jnp.repeat(a, H // a.shape[-2], axis=-2)


def _ssd_token(s, xt, d, A, bt, ct):
    """One token of the recurrence: s [B, H, P, N], xt [B, H, P], d [B, H], A [H],
    bt, ct [B, G, N] -> (y_t [B, H, P], the state after it)."""
    H = xt.shape[1]
    s = (jnp.exp(d * A)[..., None, None] * s
         + (d[..., None] * xt)[..., None] * _by_head(bt, H)[:, :, None, :])
    return (s * _by_head(ct, H)[:, :, None, :]).sum(-1), s


def _ssd_plain(x, dt, A, Bm, Cm, s0):
    """One trip a token: x [B, S, H, P], dt [B, S, H], A [H], Bm, Cm [B, S, G,
    N], s0 [B, H, P, N], all f32 -> (y [B, S, H, P], s)."""

    def step(s, inp):
        xt, d, bt, ct = inp
        y, s = _ssd_token(s, xt, d, A, bt, ct)
        return s, y

    s, ys = jax.lax.scan(step, s0, tuple(a.swapaxes(0, 1) for a in (x, dt, Bm, Cm)))
    return ys.swapaxes(0, 1), s


def ssd_step(x, dt, A, Bm, Cm, s0):
    """A decode step, S = 1: the state advanced by one token and read, no
    product of more than one row. Shapes as `_ssd_plain`."""
    y, s = _ssd_token(s0, x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0])
    return y[:, None], s


def ssd_chunked(x, dt, A, Bm, Cm, s0, chunk: int, dtype=jnp.bfloat16):
    """The chunked form: the S tokens in chunks of Q = `chunk` (a last partial
    chunk padded with steps of 0, which move nothing). With a_t = delta_t A
    and c_i its running sum inside a chunk, for a head of group g:

        y_i = sum_{j<=i} exp(c_i - c_j) (C_i . B_j) delta_j x_j        inside
              + exp(c_i) C_i . S_in                                     carried
        S_out = exp(c_Q) S_in + sum_j exp(c_Q - c_j) delta_j x_j (x) B_j

    Every sum over j or over N is a matrix product with operands in `dtype`
    and float32 sums; the decays and the state stay float32. The state passes
    from chunk to chunk in a `lax.scan` over the chunks. Shapes as `_ssd_plain`."""
    f32 = jnp.float32
    B, S, H, P = x.shape
    G, N = Bm.shape[2:]
    Q = min(chunk, S)
    pad = -S % Q
    if pad:
        x, dt, Bm, Cm = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                         for a in (x, dt, Bm, Cm))
    C = (S + pad) // Q
    R = H // G
    xq = x.reshape(B, C, Q, G, R, P)
    bq, cq = (a.reshape(B, C, Q, G, N).astype(dtype) for a in (Bm, Cm))
    # the decays head-major, [B, C, G, R, Q]: tokens fill the lanes
    dq = dt.reshape(B, C, Q, G, R).transpose(0, 1, 3, 4, 2)
    cum = jnp.cumsum(dq * A.reshape(G, R, 1), axis=-1)
    dx = (dt.reshape(B, C, Q, G, R, 1) * xq).astype(dtype)             # delta_j x_j
    # inside a chunk
    cb = jnp.einsum("bcign,bcjgn->bcgij", cq, bq, preferred_element_type=f32)
    seen = jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :]
    decay = jnp.exp(jnp.where(seen, cum[..., :, None] - cum[..., None, :], -jnp.inf))
    mix = (cb[:, :, :, None] * decay).astype(dtype)                    # [B, C, G, R, i, j]
    y = jnp.einsum("bcgrij,bcjgrp->bcigrp", mix, dx, preferred_element_type=f32)
    # what a chunk adds to the state, and its whole decay
    to_end = jnp.exp(cum[..., -1:] - cum).transpose(0, 1, 4, 2, 3)     # [B, C, Q, G, R]
    adds = jnp.einsum("bcjgrp,bcjgn->bcgrpn", (to_end[..., None] * dx.astype(f32)).astype(dtype),
                      bq, preferred_element_type=f32)
    whole = jnp.exp(cum[..., -1])                                      # [B, C, G, R]

    def carry(s, inp):
        add, w = inp
        return w[..., None, None] * s + add, s       # the state a chunk STARTS from

    s, starts = jax.lax.scan(
        carry, s0.reshape(B, G, R, P, N),
        (adds.swapaxes(0, 1), whole.swapaxes(0, 1)))
    y = y + jnp.exp(cum).transpose(0, 1, 4, 2, 3)[..., None] * jnp.einsum(
        "bcign,bcgrpn->bcigrp", cq, starts.swapaxes(0, 1).astype(dtype),
        preferred_element_type=f32)
    return y.reshape(B, C * Q, H, P)[:, :S], s.reshape(B, H, P, N)


def ssd_scan(x, dt, A, Bm, Cm, s0, valid, chunk: int = 128, form=None, dtype=jnp.bfloat16):
    """S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t, y_t = S_t C_t over the S
    tokens of every lane from the state it is handed: x [B, S, H, P], dt [B,
    S, H] f32 (after its softplus), A [H] f32 (negative), Bm, Cm [B, S, G, N],
    s0 [B, H, P, N] f32, valid [B, S] -> (y [B, S, H, P] f32, s like s0). A
    masked token's step is 0, which leaves the state as it was. `form`: None =
    the step for one token a lane and the chunked form for more (its products'
    operands in `dtype`); "plain" forces the recurrence as written."""
    f32 = jnp.float32
    dt = jnp.where(valid[..., None], dt, 0.0)
    x, Bm, Cm = (a.astype(f32) for a in (x, Bm, Cm))
    if form == "plain":
        return _ssd_plain(x, dt, A, Bm, Cm, s0)
    if x.shape[1] == 1:
        return ssd_step(x, dt, A, Bm, Cm, s0)
    return ssd_chunked(x, dt, A, Bm, Cm, s0, chunk, dtype)


def grouped_gated_rmsnorm(y, z, w, groups: int, eps: float):
    """RMSNorm(y * silu(z)) with the mean square taken over each of `groups`
    groups of channels, one learned gain a channel: y, z [..., Di] -> f32."""
    f32 = jnp.float32
    g = y.astype(f32) * jax.nn.silu(z.astype(f32))
    parts = g.reshape(*g.shape[:-1], groups, -1)
    parts = parts * jax.lax.rsqrt((parts * parts).mean(-1, keepdims=True) + eps)
    return parts.reshape(g.shape) * w.astype(f32)


def mamba2_mixer(p, h, tail, s, valid, *, groups: int, chunk: int = 128,
                 eps: float = 1e-5, form=None):
    """The Mamba-2 mixer over h [B, S, E] (the normed stream, compute dtype)
    from the state a lane brings: tail [B, K-1, Di + 2 G N], s [B, H, P, N]
    f32; `p` one block's weights (`A_log` as stored): w_in [E, 2 Di + 2 G N +
    H], conv_w [K, Di + 2 G N], conv_b [Di + 2 G N], dt_bias [H], A_log [H], D
    [H], norm_w [Di], w_out [Di, E]. Returns (out [B, S, E], new tail, new s)."""
    f32 = jnp.float32
    B, S, _ = h.shape
    H, P, N = s.shape[1:]
    Di, G = H * P, groups
    zxd = jnp.einsum("bse,ef->bsf", h, p["w_in"])
    z, xbc, dt = zxd[..., :Di], zxd[..., Di:2 * Di + 2 * G * N], zxd[..., 2 * Di + 2 * G * N:]
    xbc, tail = causal_conv(xbc, tail, p["conv_w"], p["conv_b"], valid)
    x = xbc[..., :Di].reshape(B, S, H, P)
    Bm = xbc[..., Di:Di + G * N].reshape(B, S, G, N)
    Cm = xbc[..., Di + G * N:].reshape(B, S, G, N)
    delta = jax.nn.softplus(dt.astype(f32) + p["dt_bias"].astype(f32))
    A = -jnp.exp(p["A_log"].astype(f32))
    y, s = ssd_scan(x, delta, A, Bm, Cm, s, valid, chunk, form, h.dtype)
    y = y + p["D"].astype(f32)[:, None] * x.astype(f32)
    normed = grouped_gated_rmsnorm(y.reshape(B, S, Di), z, p["norm_w"], G, eps)
    return jnp.einsum("bsd,de->bse", normed.astype(h.dtype), p["w_out"]), tail, s
