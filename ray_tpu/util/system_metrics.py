"""Per-node system metrics (reference analog:
`dashboard/modules/reporter/reporter_agent.py:277` — the psutil-based node
reporter feeding the dashboard and Prometheus).

No psutil dependency: cpu from /proc/stat deltas, memory from
/proc/meminfo, disk from statvfs, TPU HBM occupancy from the JAX runtime
when a chip is attached (best-effort — 0.0 when unavailable, matching
nodes without accelerators)."""

from __future__ import annotations

import os
import time
from typing import Dict, Optional, Tuple


def _cpu_jiffies() -> Tuple[int, int]:
    """(busy, total) jiffies across all cpus."""
    with open("/proc/stat") as f:
        parts = f.readline().split()[1:]
    vals = [int(p) for p in parts]
    idle = vals[3] + (vals[4] if len(vals) > 4 else 0)  # idle + iowait
    total = sum(vals)
    return total - idle, total


class SystemMetricsSampler:
    """Stateful sampler: cpu_percent needs a jiffies delta between calls."""

    def __init__(self, disk_path: str = "/"):
        self.disk_path = disk_path
        self._last: Optional[Tuple[int, int]] = None

    def sample(self) -> Dict[str, float]:
        from .memory_monitor import node_memory

        busy, total = _cpu_jiffies()
        cpu_percent = 0.0
        if self._last is not None:
            db = busy - self._last[0]
            dt = total - self._last[1]
            if dt > 0:
                cpu_percent = 100.0 * db / dt
        self._last = (busy, total)
        mem_total, mem_avail = node_memory()
        try:
            st = os.statvfs(self.disk_path)
            disk_total = st.f_frsize * st.f_blocks
            disk_free = st.f_frsize * st.f_bavail
        except OSError:
            disk_total = disk_free = 0
        return {
            "cpu_percent": round(cpu_percent, 1),
            "mem_total_bytes": mem_total,
            "mem_used_bytes": mem_total - mem_avail,
            "disk_total_bytes": disk_total,
            "disk_used_bytes": disk_total - disk_free,
            "tpu_hbm_used_pct": tpu_hbm_used_pct(),
            "ts": time.time(),
        }


# Slow/failed samples back off with a cooldown instead of a permanent
# latch: one transient hiccup (GC pause, momentary runtime stall) must not
# kill the metric for the process lifetime. Consecutive bad samples double
# the cooldown up to _TPU_COOLDOWN_MAX_S; one good sample resets it.
_TPU_COOLDOWN_S = 30.0
_TPU_COOLDOWN_MAX_S = 600.0
_tpu_bad_streak = 0
_tpu_retry_at = 0.0


def _tpu_sample_failed():
    global _tpu_bad_streak, _tpu_retry_at
    _tpu_bad_streak += 1
    # Exponent clamped BEFORE pow: an unbounded streak would overflow
    # float pow (~2.0**1024) inside the metrics tick's except handler.
    cooldown = min(
        _TPU_COOLDOWN_S * (2.0 ** min(_tpu_bad_streak - 1, 16)),
        _TPU_COOLDOWN_MAX_S,
    )
    _tpu_retry_at = time.monotonic() + cooldown


def tpu_hbm_used_pct() -> float:
    """Best-effort HBM occupancy of the first chip (`bytes_in_use` over
    `bytes_limit`, percent): a memory number, NOT a duty cycle — busy and
    idle time come only from a profiler trace. Reported ONLY from processes
    whose JAX BACKEND is already initialized (never import or initialize
    here — a metrics sampler that triggers the jax import / chip attach
    inside a health tick would blow the probe deadline AND take the chip
    from the worker it was granted to). A slow stats call pauses sampling for a
    (growing) cooldown, then retries."""
    global _tpu_bad_streak
    import sys

    if time.monotonic() < _tpu_retry_at or "jax" not in sys.modules:
        return 0.0
    try:
        jax = sys.modules["jax"]
        # Backend-initialized check WITHOUT triggering initialization.
        backends = getattr(
            getattr(jax, "_src", None) and jax._src.xla_bridge, "_backends", None
        )
        if not backends:
            return 0.0
        t0 = time.monotonic()
        devs = jax.devices()
        if not devs or devs[0].platform != "tpu":
            return 0.0
        stats = devs[0].memory_stats() or {}
        if time.monotonic() - t0 > 0.25:
            _tpu_sample_failed()  # too slow to poll every tick
        else:
            _tpu_bad_streak = 0
        limit = stats.get("bytes_limit") or 0
        used = stats.get("bytes_in_use") or 0
        return round(100.0 * used / limit, 1) if limit else 0.0
    except Exception:  # noqa: BLE001
        _tpu_sample_failed()
        return 0.0
