"""SPMD execution helpers: jit-with-shardings and shard_map wrappers.

Reference analog: none — this replaces the entire NCCL worker-group data
plane (`ray.util.collective`, torch DDP in `train/torch/train_loop_utils.py`)
with compiled XLA programs over a Mesh.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax

from .mesh import ShardingRules


def parallelize(
    fn: Callable,
    mesh,
    in_shardings=None,
    out_shardings=None,
    static_argnums=(),
    donate_argnums=(),
) -> Callable:
    """jit `fn` over `mesh` with explicit shardings (pjit idiom).

    Shardings may be NamedSharding, PartitionSpec (resolved against `mesh`),
    or None (let XLA propagate).
    """
    from jax.sharding import NamedSharding, PartitionSpec

    def resolve(s):
        if s is None or isinstance(s, NamedSharding):
            return s
        if isinstance(s, PartitionSpec):
            return NamedSharding(mesh, s)
        if isinstance(s, (tuple, list)):
            return type(s)(resolve(x) for x in s)
        if isinstance(s, dict):
            return {k: resolve(v) for k, v in s.items()}
        return s

    jitted = jax.jit(
        fn,
        in_shardings=resolve(in_shardings) if in_shardings is not None else None,
        out_shardings=resolve(out_shardings) if out_shardings is not None else None,
        static_argnums=static_argnums,
        donate_argnums=donate_argnums,
    )

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        with jax.set_mesh(mesh):
            return jitted(*args, **kwargs)

    wrapper.jitted = jitted
    wrapper.lower = jitted.lower
    return wrapper


def shard_fn(
    fn: Callable,
    mesh,
    in_specs,
    out_specs,
    check_vma: bool = False,
    manual_axes: Optional[frozenset] = None,
) -> Callable:
    """`shard_map` wrapper: per-device function with explicit collectives.

    This is where ring attention, Ulysses all-to-all, and hand-written
    pipeline schedules live — code inside `fn` sees its local shard and the
    mesh axis names are bound for `jax.lax.p*`.

    `manual_axes` restricts manual collectives to a subset of mesh axes; the
    rest stay AUTO — the compiler keeps partitioning the body over them
    (e.g. a pipeline manual over `pp` whose stages still auto-shard over
    dp/fsdp/tp).
    """
    kwargs = {}
    if manual_axes is not None:
        kwargs["axis_names"] = frozenset(manual_axes)
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma, **kwargs,
    )
