"""The mixers of a decoder-hybrid-decoder (SambaY, arXiv:2507.06607, as
Phi-4-mini-flash-reasoning runs it), each built on what `ops/ssm.py` and
`ops/paged_attention.py` already have:

* `mamba_mixer_plain`: Mamba-1 as published, WITHOUT the three inner RMSNorms
  the Jamba family adds (`ops/ssm.py` `mamba_mixer`), which also hands out
  its scan output `m_t = y_t = s_t C_t + D c_t` BEFORE the gate silu(z_t):
  what the gated memory units of later layers read for the same token.
* `gated_memory_unit`: out = W_o (m * silu(W_g x)), two products and a gate
  over another layer's `m`; it keeps nothing between programs.
* differential attention (Differential Transformer, `multihead_flashdiff_2`),
  as a COMPOSITION over plain grouped-query attention at twice the head size:
  heads pair up in order, query heads (2i, 2i+1) = (q1_i, q2_i), K heads
  (2j, 2j+1) = (k1_j, k2_j), V heads (v1_j, v2_j), query pair i reading K/V
  pair j = i // 2, and

      o_i = softmax(q1_i k1_j^T) [v1_j ; v2_j] - lambda softmax(q2_i k2_j^T) [v1_j ; v2_j]
      o_i <- RMSNorm_{2 Dh}(o_i) * (1 - lambda_init)

  A K/V PAIR lies in the pool as ONE head of 2 Dh ([k1 ; k2], [v1 ; v2]: the
  published row, byte for byte), and a query head rides as a head of 2 Dh
  whose other half is zero (`diff_queries`: [q1 ; 0], [0 ; q2]), so that its
  scores against the pair's key row are its own head's alone. That is
  grouped-query attention of H heads over H_kv / 2 heads of 2 Dh = 128: what
  the paged kernels take. `diff_combine` subtracts, norms and scales outside
  them. Twice the score products of heads of Dh, and no more bytes."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .ssm import causal_conv, selective_scan


def mamba_mixer_plain(p, h, tail, s, valid, kernel=None):
    """The mixer over h [B, S, E] (the normed stream, compute dtype) from the
    state a lane brings (tail [B, K-1, Di], s [B, *state_shape] f32), `p` one
    layer's weights under `ops/ssm.py`'s names less the three norms: w_in [E,
    2 Di], conv_w [K, Di], conv_b [Di], w_x [Di, R + 2N], w_dt [R, Di], b_dt
    [Di], A_log [N, Di], D [Di], w_out [Di, E]. Returns (out [B, S, E], m [B,
    S, Di] in h's dtype: the scan's output with the D c term, before the gate;
    new tail; new s)."""
    f32 = jnp.float32
    Di, R = p["conv_b"].shape[-1], p["w_dt"].shape[-2]
    N = (p["w_x"].shape[-1] - R) // 2
    uz = jnp.einsum("bse,ef->bsf", h, p["w_in"])
    u, z = uz[..., :Di], uz[..., Di:]
    c, tail = causal_conv(u, tail, p["conv_w"], p["conv_b"], valid)
    dbc = jnp.einsum("bsd,df->bsf", c, p["w_x"], preferred_element_type=f32)
    delta = jax.nn.softplus(
        jnp.einsum("bsr,rd->bsd", dbc[..., :R].astype(h.dtype), p["w_dt"],
                   preferred_element_type=f32) + p["b_dt"].astype(f32))
    A = -jnp.exp(p["A_log"].astype(f32))
    y, s = selective_scan(delta, c, A, dbc[..., R:R + N], dbc[..., R + N:], s, valid, kernel)
    y = y + p["D"].astype(f32) * c.astype(f32)
    gated = (y * jax.nn.silu(z.astype(f32))).astype(h.dtype)
    return jnp.einsum("bsd,de->bse", gated, p["w_out"]), y.astype(h.dtype), tail, s


def gated_memory_unit(x, m, w_gate, w_out):
    """x [B, S, E] (this layer's normed stream), m [B, S, Di] (another layer's
    scan output for the same tokens) -> W_out (m * silu(W_gate x)) [B, S, E]."""
    f32 = jnp.float32
    g = jnp.einsum("bse,ed->bsd", x, w_gate, preferred_element_type=f32)
    gated = (m.astype(f32) * jax.nn.silu(g)).astype(x.dtype)
    return jnp.einsum("bsd,de->bse", gated, w_out)


def lambda_init(layer):
    """0.8 - 0.6 exp(-0.3 l), l the layer's index in the whole stack (a number
    or an array of them), float32."""
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(layer, jnp.float32))


def diff_lambda(lam, layer):
    """lam [4, Dh] = (lq1, lk1, lq2, lk2) -> the layer's scalar
    exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init(layer), float32."""
    lam = lam.astype(jnp.float32)
    return (jnp.exp(jnp.sum(lam[0] * lam[1])) - jnp.exp(jnp.sum(lam[2] * lam[3]))
            + lambda_init(layer))


def diff_queries(q):
    """q [B, S, H, Dh] as projected -> [B, H, S, 2 Dh]: head 2i = [q1_i ; 0],
    head 2i + 1 = [0 ; q2_i], so that a head's scores against a pair's key row
    [k1 ; k2] are its own half's."""
    B, S, H, Dh = q.shape
    q = q.reshape(B, S, H // 2, 2, Dh)
    zero = jnp.zeros_like(q[:, :, :, 0])
    q = jnp.stack([jnp.concatenate([q[:, :, :, 0], zero], -1),
                   jnp.concatenate([zero, q[:, :, :, 1]], -1)], axis=3)
    return q.reshape(B, S, H, 2 * Dh).transpose(0, 2, 1, 3)


def diff_combine(attn, lam, gain, scale, eps: float = 1e-5):
    """attn [B, H, S, 2 Dh], head 2i = P1_i V, head 2i + 1 = P2_i V ->
    [B, S, H / 2 * 2 Dh]: o_i = P1_i V - lam P2_i V under an RMSNorm over its
    2 Dh columns (`gain` [2 Dh]) times `scale` = 1 - lambda_init; float32
    inside, attn's dtype out."""
    f32 = jnp.float32
    B, H, S, D = attn.shape
    a = attn.astype(f32).reshape(B, H // 2, 2, S, D)
    o = a[:, :, 0] - lam * a[:, :, 1]                       # [B, H/2, S, 2 Dh]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    o = o * (gain.astype(f32) * scale)
    return o.transpose(0, 2, 1, 3).reshape(B, S, H // 2 * D).astype(attn.dtype)
