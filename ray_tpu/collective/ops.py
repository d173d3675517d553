"""In-jit collectives — the ICI plane.

The reference's NCCL calls (`nccl_collective_group.py:allreduce` etc.) map on
TPU to XLA collective HLOs compiled into the program. These wrappers add
nothing at runtime — they exist so framework code reads at the same level of
intent as the reference API, and so the axis-name conventions of
`ray_tpu.parallel.mesh.AXIS_ORDER` are applied consistently.

All functions must be called under `shard_map`/`pjit` with bound axis names.
"""

from __future__ import annotations

from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp

AxisName = Union[str, Sequence[str]]


def psum(x, axis: AxisName):
    return jax.lax.psum(x, axis_name=axis)


def pmean(x, axis: AxisName):
    return jax.lax.pmean(x, axis_name=axis)

def pmax(x, axis: AxisName):
    return jax.lax.pmax(x, axis_name=axis)


def pmin(x, axis: AxisName):
    return jax.lax.pmin(x, axis_name=axis)


def allreduce_jit(x, axis: AxisName, op: str = "sum"):
    return {"sum": psum, "mean": pmean, "max": pmax, "min": pmin}[op](x, axis)


def all_gather(x, axis: AxisName, *, tiled: bool = True, gather_axis: int = 0):
    return jax.lax.all_gather(x, axis_name=axis, axis=gather_axis, tiled=tiled)


def reduce_scatter(x, axis: AxisName, *, scatter_axis: int = 0, op: str = "sum"):
    if op not in ("sum", "mean"):
        raise NotImplementedError("reduce_scatter supports sum/mean on TPU ICI")
    out = jax.lax.psum_scatter(
        x, axis_name=axis, scatter_dimension=scatter_axis, tiled=True
    )
    if op == "mean":
        out = out / axis_size(axis)
    return out


def all_to_all(
    x,
    axis: AxisName,
    *,
    split_axis: int,
    concat_axis: int,
    tiled: bool = True,
):
    """Ulysses-style head/sequence exchange rides this (`SURVEY.md §5`)."""
    return jax.lax.all_to_all(
        x, axis_name=axis, split_axis=split_axis, concat_axis=concat_axis, tiled=tiled
    )


def ppermute(x, axis: AxisName, perm: Sequence[tuple]):
    """Neighbor exchange — the ring-attention building block."""
    return jax.lax.ppermute(x, axis_name=axis, perm=perm)


def ring_shift(x, axis: str, shift: int = 1):
    """Send x to (rank+shift) mod n along `axis`; returns the received block."""
    n = axis_size(axis)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return jax.lax.ppermute(x, axis_name=axis, perm=perm)


def axis_index(axis: AxisName):
    return jax.lax.axis_index(axis)


def axis_size(axis: str) -> int:
    return jax.lax.axis_size(axis)


def barrier_jit(axis: AxisName):
    """Sync point inside jit: a zero-sized psum forces a collective."""
    return jax.lax.psum(jnp.zeros((), jnp.int32), axis_name=axis)


def unreplicate(tree):
    """Take the first element along a leading device axis (host-side)."""
    return jax.tree_util.tree_map(lambda x: x[0], tree)


# ------------------------------------------------------- ZeRO flat sharding
# Helpers for the cross-replica sharded weight update (arXiv 2004.13336):
# the optimizer works in ONE flat f32 parameter space, each data-parallel
# replica owning a contiguous chunk of it. Chunk boundaries use
# np.array_split sizing — the SAME partitioning rule the elastic
# checkpoint's axis-0 reshard applies (train/elastic/ckpt.py), so a shard
# saved at dp=4 restores as exactly rank r's runtime chunk at dp=2 with no
# re-padding. Any flat length works for any world size (no divisibility
# constraint; elementwise optimizers don't care about uneven chunks).
# The HOST-plane collectives these compose with (reduce_scatter_flat /
# all_gather_flat, object-store rendezvous between gang actors) live in
# ray_tpu.collective; the in-jit reduce_scatter/all_gather above are their
# ICI analogs.


def zero_shard_bounds(n: int, world: int, rank: int) -> "tuple[int, int]":
    """[start, end) of rank's chunk of a flat length-n vector under
    np.array_split sizing (first n % world chunks get one extra element)."""
    q, rem = divmod(int(n), int(world))
    start = rank * q + min(rank, rem)
    return start, start + q + (1 if rank < rem else 0)


def zero_flatten(tree):
    """Pytree -> (flat f32 1-D np.ndarray, spec). `spec` (a list of
    (shape, dtype) in tree_flatten leaf order + the treedef) round-trips
    through zero_unflatten. Master/optimizer math runs in f32 regardless of
    the working dtype — the f32-master half of the ZeRO recipe."""
    import numpy as np
    from jax import tree_util

    leaves, treedef = tree_util.tree_flatten(tree)
    spec = {
        "treedef": treedef,
        "leaves": [(tuple(np.shape(x)), np.asarray(x).dtype.str) for x in leaves],
    }
    if not leaves:
        return np.zeros((0,), np.float32), spec
    flat = np.concatenate(
        [np.asarray(x, dtype=np.float32).reshape(-1) for x in leaves]
    )
    return flat, spec


def zero_unflatten(flat, spec, cast: bool = True):
    """Inverse of zero_flatten. With cast=True each leaf is cast back to its
    recorded dtype (the working-precision tree); cast=False keeps f32."""
    import numpy as np
    from jax import tree_util

    out, pos = [], 0
    for shape, dtype_str in spec["leaves"]:
        n = int(np.prod(shape)) if shape else 1
        leaf = np.asarray(flat[pos : pos + n]).reshape(shape)
        if cast:
            leaf = leaf.astype(np.dtype(dtype_str))
        out.append(leaf)
        pos += n
    return tree_util.tree_unflatten(spec["treedef"], out)
