"""TPU accelerator discovery, worker pinning & pod helpers.

Reference: `python/ray/_private/accelerators/tpu.py` (device-node count,
`TPU_VISIBLE_CHIPS` isolation, pod resources) and
`python/ray/util/accelerators/tpu.py` (`get_current_pod_name`,
`get_current_pod_worker_count`).

A chip belongs to ONE process from that process's first JAX device query
until it exits. Everything here is therefore written for processes that must
NOT attach: the scheduler counts device nodes, pins each worker's platform in
its spawn environment, and a TPU worker reserves its chips (`claim_chips`)
before user code can touch JAX.
"""

from __future__ import annotations

import fcntl
import functools
import glob
import logging
import math
import os
from typing import Dict, List, MutableMapping, Optional

logger = logging.getLogger(__name__)

TPU_VISIBLE_CHIPS_ENV = "TPU_VISIBLE_CHIPS"
COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# GCE TPU-VM metadata environment mirrors.
_ACCEL_TYPE_ENV = "TPU_ACCELERATOR_TYPE"  # e.g. "v5litepod-16"
_WORKER_ID_ENV = "TPU_WORKER_ID"
_POD_NAME_ENV = "TPU_NAME"

# chips per host for each generation (v5e/v6e: 1,4, or 8; default 4).
_DEFAULT_CHIPS_PER_HOST = 4

_DEV_ROOT = "/dev"
_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)


def _chip_device_nodes() -> List[str]:
    """The accelerator device nodes the kernel exposes, one per chip:
    `/dev/accel*` under the accel driver, else the numbered IOMMU groups of
    `/dev/vfio/` under vfio passthrough."""
    accel = glob.glob(os.path.join(_DEV_ROOT, "accel*"))
    return sorted(accel or glob.glob(os.path.join(_DEV_ROOT, "vfio", "[0-9]*")))


@functools.lru_cache(maxsize=1)
def detect_num_chips() -> int:
    """Number of local TPU chips visible to this process.

    Never initializes a JAX backend: `jax.devices()` would *attach* this
    process to the chips, taking them from the worker the scheduler grants
    them to. `TPU_VISIBLE_CHIPS` (a pinned worker) wins; otherwise the count
    is what the machine exposes as device nodes.
    """
    visible = os.environ.get(TPU_VISIBLE_CHIPS_ENV)
    if visible:
        return len([c for c in visible.split(",") if c.strip() != ""])
    return len(_chip_device_nodes())


def place_compile_cache(env: Optional[MutableMapping[str, str]] = None) -> str:
    """Directory of the persistent XLA compile cache, fixed in `env` (default:
    this process's environment, which must happen before `import jax`).

    A directory given from outside in `JAX_COMPILATION_CACHE_DIR` is left
    alone; otherwise it is `<checkout>/.jax_cache` — a stable path, because
    the path is part of what makes a later run hit."""
    env = os.environ if env is None else env
    return env.setdefault(COMPILE_CACHE_ENV, os.path.join(_CHECKOUT, ".jax_cache"))


def worker_spawn_env(env: Dict[str, str], tpu: bool) -> Dict[str, str]:
    """Pin a worker's JAX platform in its spawn environment (in place).

    A TPU worker gets `JAX_PLATFORMS=tpu` whatever its parent runs on, so it
    fails at first device use if it did not get a chip instead of computing
    on the CPU with `has_tpu=True`. Every other process is kept off the chip
    (and off the ~2 s TPU plugin start-up)."""
    env["RAY_TPU_WORKER_TPU"] = "1" if tpu else "0"
    if tpu:
        env["JAX_PLATFORMS"] = "tpu"
        place_compile_cache(env)
        # JAX keeps only programs that took a second to compile. A paged
        # serving program of gpt2-large compiles in 0.8-1.0 s, so a replica
        # recompiled all 53 of its programs at every start (51 s of warm-up
        # against 23 s from the cache, PERF.md §6 PR 25). Keep them all.
        env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    else:
        platforms = env.get("JAX_PLATFORMS", "").lower().split(",")
        if "tpu" in platforms or platforms == [""]:  # e.g. "tpu,cpu" on TPU VMs
            env["JAX_PLATFORMS"] = "cpu"
    return env


# libtpu topology for a process that owns fewer chips than the host has.
_SUBSET_BOUNDS = {1: "1,1,1", 2: "1,2,1"}
_claim: Optional[tuple] = None  # (chip ids, open lock files) held for life


def _wait_nodes_free(ids: List[int], timeout_s: float = 60.0) -> None:
    """Wait until the claimed chips' vfio device nodes can be opened. A vfio
    group takes one opener at a time, and the kernel lets it go only when the
    last holder's exit has run its course: a process that is already gone
    from `/proc` can keep the node busy for a moment, and libtpu then fails
    at the first device query with "open(/dev/vfio/N): Device or resource
    busy" (what a run that starts right behind another one on the same chips
    met: PERF.md 7, PR 43). The probe opens and closes the node; anything but
    EBUSY (no such node, no permission, another kind of node) is libtpu's to
    report. After `timeout_s` the claim stands and libtpu says what it finds.
    A wait and a timeout are logged: both are rare and explain a slow attach."""
    import errno
    import time

    nodes = _chip_device_nodes()
    start, busy = time.monotonic(), 0
    for chip in ids:
        node = nodes[chip] if chip < len(nodes) else ""
        if os.sep + "vfio" + os.sep not in node:
            continue
        while True:
            try:
                os.close(os.open(node, os.O_RDWR))
                break
            except OSError as e:
                if e.errno != errno.EBUSY:
                    break
                if time.monotonic() - start > timeout_s:
                    logger.warning("%s still busy after %.0f s: claiming it as it is",
                                   node, timeout_s)
                    break
                busy += 1
                time.sleep(0.2)
    if busy:
        logger.warning("chips %s: vfio nodes busy at %d opens, %.1f s until let go",
                       ids, busy, time.monotonic() - start)


def claim_chips(quantity: float, lock_dir: str) -> List[int]:
    """Reserve `quantity` local chips for THIS process until it exits and pin
    libtpu to them; call before the first JAX device query.

    The scheduler's ledger frees a grant when a task ends, but the process
    keeps its chips while it lives, so the reservation is an flock per chip
    in `lock_dir` (shared by the workers of one runtime) held for the life
    of the process. A worker granted every chip of the host keeps libtpu's
    default topology; a smaller grant gets `TPU_VISIBLE_CHIPS` plus matching
    bounds. Raises when the host has no such number of free chips, or when
    this process already holds a different number."""
    global _claim
    want = max(1, math.ceil(quantity))
    if _claim is not None:
        if len(_claim[0]) != want:
            raise RuntimeError(
                f"this worker already holds TPU chips {_claim[0]} and cannot "
                f"re-attach with {want}; a chip stays with its process"
            )
        return _claim[0]
    total = len(_chip_device_nodes())
    if total == 0:
        return []  # nothing to pin; JAX_PLATFORMS=tpu fails at first use
    if want < total and want not in _SUBSET_BOUNDS:
        raise RuntimeError(
            f"cannot pin {want} of {total} local TPU chips to one process "
            f"(supported: {sorted(_SUBSET_BOUNDS)} or all {total})"
        )
    ids: List[int] = []
    locks = []
    for chip in range(total):
        f = open(os.path.join(lock_dir, f"tpu-chip-{chip}.lock"), "a+")
        try:
            fcntl.flock(f, fcntl.LOCK_EX | fcntl.LOCK_NB)
        except BlockingIOError:
            f.close()
            continue
        f.seek(0)
        f.truncate()
        f.write(f"{os.getpid()}\n")
        f.flush()
        ids.append(chip)
        locks.append(f)
        if len(ids) == want:
            break
    if len(ids) < want:
        for f in locks:
            f.close()
        raise RuntimeError(
            f"TPU grant of {want} chip(s) cannot be honoured: only {len(ids)} "
            f"of this host's {total} are free (holders: see {lock_dir}/"
            f"tpu-chip-*.lock)"
        )
    _claim = (ids, locks)
    _wait_nodes_free(ids)
    if want < total:
        os.environ[TPU_VISIBLE_CHIPS_ENV] = ",".join(map(str, ids))
        os.environ["TPU_CHIPS_PER_HOST_BOUNDS"] = _SUBSET_BOUNDS[want]
        os.environ["TPU_HOST_BOUNDS"] = "1,1,1"
    return ids


def get_accelerator_type() -> Optional[str]:
    """e.g. 'v5litepod-16'; None when not on a TPU VM."""
    return os.environ.get(_ACCEL_TYPE_ENV)


def pod_type_and_chip_count(accelerator_type: str) -> tuple[str, int]:
    """'v5litepod-16' → ('v5litepod', 16)."""
    head, _, count = accelerator_type.rpartition("-")
    return head, int(count)


def get_current_pod_name() -> Optional[str]:
    return os.environ.get(_POD_NAME_ENV)


def get_current_pod_worker_count() -> Optional[int]:
    accel = get_accelerator_type()
    if accel is None:
        return None
    _, chips = pod_type_and_chip_count(accel)
    per_host = chips_per_host()
    return max(1, chips // per_host)


def chips_per_host() -> int:
    n = detect_num_chips()
    return n if n > 0 else _DEFAULT_CHIPS_PER_HOST


def get_worker_id() -> int:
    return int(os.environ.get(_WORKER_ID_ENV, "0"))


def pod_resource_name(accelerator_type: str) -> str:
    """Custom resource advertised by pod head workers, e.g. 'TPU-v5litepod-16-head'."""
    return f"TPU-{accelerator_type}-head"
