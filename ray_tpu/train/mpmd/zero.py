"""ZeRO-style sharded weight update over a stage's data-parallel replicas.

The ZeRO idea (arXiv 2004.13336): gradients REDUCE-SCATTER across the dp
group, each replica updates only its 1/dp chunk of the flat f32 optimizer
state (adam m/v + f32 master params), and the updated parameter chunks
ALL-GATHER back into the full working tree — optimizer memory per replica
drops ~dp x vs a replicated adamw, which is exactly the state that OOMs
first at GPT-J scale.

Layout contract: the flat space is chunked with np.array_split sizing
(`collective.ops.zero_shard_bounds`) — the SAME rule the host-plane
`collective.reduce_scatter_flat` uses for wire chunks and the elastic
checkpoint's axis-0 reshard applies on restore, so optimizer shards saved
at dp=4 restore as exactly the runtime chunks at dp=2.

Bit-parity contract: `ReplicatedAdamW` (the A/B baseline) reduces gradients
through the SAME reduce-scatter + all-gather pair before its full-width
update. AdamW is elementwise, so update-shard-then-gather and
gather-then-update produce bit-identical parameters — the parity gate in
tests/test_train_mpmd.py asserts exact equality, not allclose. Memory is
the only difference between the two paths.

Comm backends: `StoreDpComm` rides the host-plane object-store collectives
(separate replica processes — the DCN analog); `LocalDpComm` is an
in-process thread group for the parity tests and the local pipeline runner;
`SoloComm` is the dp=1 degenerate.
"""

from __future__ import annotations

import functools
import threading
from typing import Dict, List, Optional

import numpy as np

from ...collective.ops import zero_shard_bounds


class SoloComm:
    """dp = 1: collectives are identity."""

    world = 1
    rank = 0

    def reduce_scatter_flat(self, vec: np.ndarray) -> np.ndarray:
        return np.array(np.asarray(vec).reshape(-1), copy=True)

    def all_gather_flat(self, chunk: np.ndarray) -> np.ndarray:
        return np.array(np.asarray(chunk).reshape(-1), copy=True)


class StoreDpComm:
    """Host-plane dp group between replica PROCESSES: wraps
    `ray_tpu.collective.{reduce_scatter_flat,all_gather_flat}` for one named
    group. The caller must have joined the group (init_collective_group) on
    the thread that runs the collectives."""

    def __init__(self, group_name: str, world: int, rank: int):
        self.group_name = group_name
        self.world = world
        self.rank = rank

    def reduce_scatter_flat(self, vec: np.ndarray) -> np.ndarray:
        from ... import collective

        return collective.reduce_scatter_flat(vec, group_name=self.group_name)

    def all_gather_flat(self, chunk: np.ndarray) -> np.ndarray:
        from ... import collective

        return collective.all_gather_flat(chunk, group_name=self.group_name)


class _LocalGroupState:
    """Shared rendezvous for an in-process dp group (threads)."""

    def __init__(self, world: int):
        self.world = world
        self.cond = threading.Condition()
        self.rounds: Dict[str, dict] = {}

    def exchange(self, key: str, rank: int, value, timeout: float = 60.0) -> List:
        """Deposit `value` for round `key`; block until every rank has;
        return values in rank order. The last rank to leave frees the
        round."""
        with self.cond:
            r = self.rounds.setdefault(key, {"vals": {}, "served": 0})
            r["vals"][rank] = value
            self.cond.notify_all()
            if not self.cond.wait_for(
                lambda: len(r["vals"]) >= self.world, timeout
            ):
                raise TimeoutError(f"local dp round {key} timed out")
            out = [r["vals"][k] for k in sorted(r["vals"])]
            r["served"] += 1
            if r["served"] >= self.world:
                self.rounds.pop(key, None)
            return out


class LocalDpComm:
    """In-process dp group member (one per replica thread)."""

    def __init__(self, state: _LocalGroupState, rank: int):
        self._state = state
        self.world = state.world
        self.rank = rank
        self._seq = 0

    def _next(self, tag: str) -> str:
        self._seq += 1
        return f"{tag}:{self._seq}"

    def reduce_scatter_flat(self, vec: np.ndarray) -> np.ndarray:
        vals = self._state.exchange(
            self._next("rs"), self.rank, np.asarray(vec).reshape(-1)
        )
        # Sorted-rank reduction order, matching the host plane's _reduce —
        # every rank computes bit-identical chunks.
        mine = [np.array_split(v, self.world)[self.rank] for v in vals]
        out = np.array(mine[0], copy=True)
        for m in mine[1:]:
            out = out + m
        return out

    def all_gather_flat(self, chunk: np.ndarray) -> np.ndarray:
        vals = self._state.exchange(
            self._next("ag"), self.rank, np.asarray(chunk).reshape(-1)
        )
        return np.concatenate(vals)


def make_local_comms(world: int) -> List[LocalDpComm]:
    state = _LocalGroupState(world)
    return [LocalDpComm(state, r) for r in range(world)]


# ----------------------------------------------------------------- optimizer
@functools.lru_cache(maxsize=None)
def _adamw_jit():
    import jax
    import jax.numpy as jnp

    def update(master, m, v, g, t, lr, b1, b2, eps, wd):
        m = b1 * m + (1.0 - b1) * g
        v = b2 * v + (1.0 - b2) * g * g
        mhat = m / (1.0 - b1 ** t)
        vhat = v / (1.0 - b2 ** t)
        step = lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * master)
        return master - step, m, v

    return jax.jit(update)


class _AdamWBase:
    def __init__(
        self,
        init_flat: np.ndarray,
        comm,
        lr: float = 1e-3,
        betas=(0.9, 0.95),
        eps: float = 1e-8,
        weight_decay: float = 0.0,
    ):
        self.comm = comm
        self.n = int(np.asarray(init_flat).reshape(-1).shape[0])
        self.lr, self.betas, self.eps, self.wd = lr, betas, eps, weight_decay
        self.t = 0

    def _update(self, master, m, v, g):
        self.t += 1
        return _adamw_jit()(
            master, m, v, g,
            np.float32(self.t), np.float32(self.lr),
            np.float32(self.betas[0]), np.float32(self.betas[1]),
            np.float32(self.eps), np.float32(self.wd),
        )

    def _reduced(self, local_grad_flat: np.ndarray) -> np.ndarray:
        """This rank's chunk of the dp-MEAN gradient (reduce-scatter sum,
        then / world) — the one reduction both paths share."""
        chunk = self.comm.reduce_scatter_flat(
            np.asarray(local_grad_flat, dtype=np.float32).reshape(-1)
        )
        if self.comm.world > 1:
            chunk = chunk / np.float32(self.comm.world)
        return chunk


class ShardedAdamW(_AdamWBase):
    """ZeRO path: optimizer state holds ONLY this rank's chunk."""

    def __init__(self, init_flat, comm, **kw):
        super().__init__(init_flat, comm, **kw)
        lo, hi = zero_shard_bounds(self.n, comm.world, comm.rank)
        self.bounds = (lo, hi)
        flat = np.asarray(init_flat, dtype=np.float32).reshape(-1)
        self.master = np.array(flat[lo:hi], copy=True)
        self.m = np.zeros(hi - lo, np.float32)
        self.v = np.zeros(hi - lo, np.float32)

    @property
    def optimizer_bytes(self) -> int:
        return self.master.nbytes + self.m.nbytes + self.v.nbytes

    def step(self, local_grad_flat: np.ndarray):
        """Returns (full updated flat params [n] f32, grad_sumsq of the
        dp-mean gradient — summed across chunks via a scalar gather so
        every rank reports the global value)."""
        g = self._reduced(local_grad_flat)
        master, m, v = self._update(self.master, self.m, self.v, g)
        self.master = np.asarray(master)
        self.m, self.v = np.asarray(m), np.asarray(v)
        full = self.comm.all_gather_flat(self.master)
        chunk_sq = float(np.sum(np.square(g, dtype=np.float64)))
        sumsq = float(
            np.sum(self.comm.all_gather_flat(np.array([chunk_sq], np.float32)))
        ) if self.comm.world > 1 else chunk_sq
        return full, sumsq

    # --------------------------------------------------------- checkpoint
    def ckpt_tree(self) -> Dict[str, np.ndarray]:
        """Axis-0-shardable state: each leaf is this rank's chunk, and the
        concatenation across ranks is the full flat space — exactly the
        shape `ShardedCheckpoint.restore`'s reshard rule redistributes on a
        dp change."""
        return {"master": self.master, "m": self.m, "v": self.v, }

    def load_ckpt_tree(self, tree: Dict[str, np.ndarray], t: int) -> None:
        lo, hi = self.bounds
        for name in ("master", "m", "v"):
            got = np.asarray(tree[name], dtype=np.float32).reshape(-1)
            if got.shape[0] != hi - lo:
                raise ValueError(
                    f"restored {name} chunk has {got.shape[0]} elements, "
                    f"rank {self.comm.rank}/{self.comm.world} owns {hi - lo}"
                )
            setattr(self, name, np.array(got, copy=True))
        self.t = int(t)

    def full_flat(self) -> np.ndarray:
        return self.comm.all_gather_flat(self.master)


class ReplicatedAdamW(_AdamWBase):
    """A/B baseline: every replica holds the FULL optimizer state. The
    gradient reduction is the same reduce-scatter + all-gather pair as the
    ZeRO path, so the two produce bit-identical parameters; per-replica
    optimizer memory (dp x larger) is the measured difference."""

    def __init__(self, init_flat, comm, **kw):
        super().__init__(init_flat, comm, **kw)
        self.master = np.array(
            np.asarray(init_flat, dtype=np.float32).reshape(-1), copy=True
        )
        self.m = np.zeros(self.n, np.float32)
        self.v = np.zeros(self.n, np.float32)

    @property
    def optimizer_bytes(self) -> int:
        return self.master.nbytes + self.m.nbytes + self.v.nbytes

    def step(self, local_grad_flat: np.ndarray):
        chunk = self._reduced(local_grad_flat)
        g = (
            self.comm.all_gather_flat(chunk)
            if self.comm.world > 1 else chunk
        )
        master, m, v = self._update(self.master, self.m, self.v, g)
        self.master = np.asarray(master)
        self.m, self.v = np.asarray(m), np.asarray(v)
        sumsq = float(np.sum(np.square(g, dtype=np.float64)))
        return np.array(self.master, copy=True), sumsq

    def ckpt_tree(self) -> Dict[str, np.ndarray]:
        return {"master": self.master, "m": self.m, "v": self.v}

    def load_ckpt_tree(self, tree: Dict[str, np.ndarray], t: int) -> None:
        for name in ("master", "m", "v"):
            got = np.asarray(tree[name], dtype=np.float32).reshape(-1)
            if got.shape[0] != self.n:
                raise ValueError(
                    f"restored {name} has {got.shape[0]} elements, "
                    f"model flat space has {self.n}"
                )
            setattr(self, name, np.array(got, copy=True))
        self.t = int(t)

    def full_flat(self) -> np.ndarray:
        return np.array(self.master, copy=True)
