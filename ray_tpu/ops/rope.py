"""Rotary position embeddings (RoPE). Pure jnp — XLA fuses this into the
surrounding matmuls; a kernel would add nothing."""

from __future__ import annotations

import jax.numpy as jnp


def rope_frequencies(head_dim: int, max_seq: int, theta: float = 10000.0, dtype=jnp.float32):
    """Precompute (cos, sin) tables of shape [max_seq, head_dim//2]."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    pos = jnp.arange(max_seq, dtype=jnp.float32)
    ang = jnp.outer(pos, inv)
    return jnp.cos(ang).astype(dtype), jnp.sin(ang).astype(dtype)


def rotate_half(x, c, s):
    """Rotate x [..., seq, head_dim] by the angles whose (cos, sin) are c, s
    [..., seq, head_dim//2], leading dims broadcast.

    Rotates pairs (x[2i], x[2i+1]) — GPT-NeoX/Llama convention via
    half-split (equivalent under a fixed permutation of dims).
    """
    while c.ndim < x.ndim:
        c = c[None]
        s = s[None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(x.dtype)


def apply_rope(x, cos, sin, positions=None):
    """x: [..., seq, head_dim]; cos/sin: [max_seq, head_dim//2]; rows
    `positions` of the tables, or the first `seq`."""
    seq = x.shape[-2]
    if positions is None:
        return rotate_half(x, cos[:seq], sin[:seq])
    return rotate_half(x, cos[positions], sin[positions])
