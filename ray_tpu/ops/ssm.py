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

