"""Normalization ops: RMSNorm / LayerNorm with a fused Pallas path on TPU.

XLA fuses these adequately in most cases; the Pallas path exists for the
(seq*batch, hidden) hot shape where keeping the row resident in VMEM for the
two passes (stats + scale) avoids an HBM round trip.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp


def _rmsnorm_ref(x, w, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[:].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[:] = (y * w_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def _rmsnorm_pallas(x2d, w, eps, block_rows=256):
    from jax.experimental import pallas as pl

    N, D = x2d.shape
    # A block's float32 copy stays within 4 MiB of VMEM (the kernel holds
    # two of them beside the double-buffered input and output blocks): 256
    # rows up to 4,096 columns, 128 rows at 7,168.
    while block_rows > 8 and block_rows * D * 4 > 4 << 20:
        block_rows //= 2
    block_rows = min(block_rows, N)
    return pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        out_shape=jax.ShapeDtypeStruct((N, D), x2d.dtype),
        grid=(pl.cdiv(N, block_rows),),
        in_specs=[
            pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
            pl.BlockSpec((D,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
    )(x2d, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rmsnorm(x, w, eps):
    from .attention import _on_tpu

    shape = x.shape
    if _on_tpu() and shape[-1] % 128 == 0:
        x2d = x.reshape(-1, shape[-1])
        return _rmsnorm_pallas(x2d, w, eps).reshape(shape)
    return _rmsnorm_ref(x, w, eps)


def _rmsnorm_fwd(x, w, eps):
    return _rmsnorm(x, w, eps), (x, w)


def _rmsnorm_bwd(eps, res, g):
    x, w = res
    _, vjp = jax.vjp(lambda x_, w_: _rmsnorm_ref(x_, w_, eps), x, w)
    return vjp(g)


_rmsnorm.defvjp(_rmsnorm_fwd, _rmsnorm_bwd)


def rmsnorm(x, weight, eps: float = 1e-6):
    return _rmsnorm(x, weight, eps)


def layernorm(x, weight, bias: Optional[jnp.ndarray] = None, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(x.dtype)
