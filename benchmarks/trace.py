"""Reduction of a profiler trace (`.xplane.pb`, read with
`jax.profiler.ProfileData`, nothing but JAX) to the numbers the per-layer
metrics read: device busy and idle seconds, self time per device operation,
executions of each jitted program, collective time not covered by compute,
and the idle gaps attributed to what the host was doing.

Device planes are `/device:TPU:<n>`: the line `XLA Ops` holds one event per
executed HLO instruction (nested: a `while` encloses its body), the line
`XLA Modules` one per executed program. Host threads are the lines of
`/host:CPU`."""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Tuple

_SUFFIX = re.compile(r"(\.\d+)+$")
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute|"
    r"^send|^recv", re.I)
# Containers hold other instructions; their own time is what the children
# leave uncovered.
_DEVICE_PREFIX = "/device:TPU:"


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def op_family(name: str) -> str:
    """The instruction's name without its number: an event of `XLA Ops` is
    named by the whole HLO line, `%fusion.123 = bf16[...] fusion(...)`, and
    reduces to `fusion`; `%copy.5` to `copy`."""
    return _SUFFIX.sub("", name.split(" = ", 1)[0].strip().lstrip("%"))


def _events(line) -> List[Tuple[float, float, str]]:
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name) for e in line.events]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _self_times(events) -> List[Tuple[float, float, str, float]]:
    """(start, end, name, self_ns) for properly nested events of one line."""
    out = []
    stack: List[list] = []  # [start, end, name, child_ns]
    for s, e, name in sorted(events, key=lambda t: (t[0], -t[1])):
        while stack and stack[-1][1] <= s:
            st = stack.pop()
            out.append((st[0], st[1], st[2], (st[1] - st[0]) - st[3]))
        if stack:
            stack[-1][3] += min(e, stack[-1][1]) - s
        stack.append([s, e, name, 0.0])
    while stack:
        st = stack.pop()
        out.append((st[0], st[1], st[2], (st[1] - st[0]) - st[3]))
    return out


_OWN = ("bench.", "engine.", "$")
_MARKED = ("bench.", "engine.", "PjitFunction(")


def _python_lines(host_lines):
    """The program's own view of the host, as two groups of threads: those
    that drive the device (they carry annotations, `bench.*` or `engine.*`,
    or JAX's own `PjitFunction(...)` event of a jitted call: the train loop,
    the engine's driver thread), and the other threads with frames of the
    Python tracer (`$file:line fn`). Events of the runtime itself and frames
    without a source file (`$<unknown> poll`) are left out, so that a gap is
    named by what the program was doing."""
    marked, rest = [], []
    for ev in host_lines:
        own = [t for t in ev if t[2].startswith(_OWN) and not t[2].startswith("$<unknown>")]
        if own:
            (marked if any(t[2].startswith(_MARKED) for t in ev) else rest).append(own)
    return marked, (rest or ([] if marked else host_lines))


def _innermost(points, lines):
    """For each point, (duration, name) of the shortest event over `lines`
    that covers it, or None."""
    best = [None] * len(points)
    for events in lines:
        evs = sorted(events, key=lambda t: (t[0], -t[1]))
        stack, j = [], 0
        for pi, (p, _) in enumerate(points):
            while j < len(evs) and evs[j][0] <= p:
                while stack and stack[-1][1] < evs[j][0]:
                    stack.pop()
                stack.append(evs[j])
                j += 1
            while stack and stack[-1][1] < p:
                stack.pop()
            if stack:
                s, e, name = stack[-1]
                if best[pi] is None or e - s < best[pi][0]:
                    best[pi] = (e - s, name)
    return best


def _attribute_gaps(gaps, host_lines, parts: int = 4) -> Dict[str, float]:
    """Seconds of device idle gaps by what the host was doing: each gap is
    cut into `parts` pieces and each piece goes to the innermost host event
    (the shortest over all threads) that covers its midpoint. The device's
    clock and the host's differ by up to about a millisecond in these
    traces, so gaps of that length and below are named only roughly."""
    points = []
    for gs, ge in gaps:
        w = (ge - gs) / parts
        points += [(gs + (k + 0.5) * w, w) for k in range(parts)]
    points.sort()
    marked, rest = _python_lines(host_lines)
    best = [a or b for a, b in                # an annotated thread wins
            zip(_innermost(points, marked), _innermost(points, rest))]
    out: Dict[str, float] = defaultdict(float)
    for (_, w), b in zip(points, best):
        out[b[1] if b else "(no host event)"] += w * 1e-9
    return dict(out)


def reduce_trace(path: str, min_gap_us: float = 5.0) -> dict:
    """All the per-layer readers need, averaged over the device planes."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host_lines = [], []
    for plane in data.planes:
        if plane.name.startswith(_DEVICE_PREFIX):
            lines = {ln.name: _events(ln) for ln in plane.lines}
            devices.append(lines)
        elif plane.name.startswith("/host:CPU"):
            host_lines = [ev for ev in (_events(ln) for ln in plane.lines) if ev]
    if not devices:
        raise ValueError(f"{path}: no {_DEVICE_PREFIX}* plane; not a TPU trace")

    n = len(devices)
    busy_s = window_s = exposed_s = collective_s = 0.0
    op_self: Dict[str, float] = defaultdict(float)
    op_count: Dict[str, int] = defaultdict(int)
    modules: Dict[str, List[float]] = defaultdict(list)
    gap_by_host: Dict[str, float] = defaultdict(float)
    for lines in devices:
        ops = lines.get("XLA Ops") or []
        if not ops:
            continue
        busy = _union([(s, e) for s, e, _ in ops])
        t0, t1 = busy[0][0], busy[-1][1]
        mods = lines.get("XLA Modules") or []
        if mods:
            t0 = min(t0, min(s for s, _, _ in mods))
            t1 = max(t1, max(e for _, e, _ in mods))
        window_s += (t1 - t0) * 1e-9
        busy_s += sum(e - s for s, e in busy) * 1e-9
        # The core runs one instruction at a time, so what a collective
        # holds of `XLA Ops` (a synchronous one, or the `-done` half that
        # waits for an asynchronous one) is time with no compute running.
        # The transfers themselves are the spans of `Async XLA Ops`.
        for s, e, nm, sf in _self_times(ops):
            fam = op_family(nm)
            op_self[fam] += sf * 1e-9
            op_count[fam] += 1
            if COLLECTIVE.search(fam):
                exposed_s += sf * 1e-9
        in_flight = _union([(s, e) for s, e, nm in lines.get("Async XLA Ops") or []
                            if COLLECTIVE.search(op_family(nm))])
        collective_s += sum(e - s for s, e in in_flight) * 1e-9
        for s, e, nm in mods:
            modules[re.sub(r"\(.*\)$", "", nm)].append((e - s) * 1e-9)
        gaps = [(a[1], b[0]) for a, b in zip(busy, busy[1:])
                if b[0] - a[1] >= min_gap_us * 1e3]
        for k, v in _attribute_gaps(gaps, host_lines).items():
            gap_by_host[k] += v

    top = lambda d, k=10: [[nm, v / n] for nm, v in
                           sorted(d.items(), key=lambda kv: -kv[1])[:k]]
    return {
        "devices": n,
        "busy_s": busy_s / n,
        "window_s": window_s / n,
        "collective_in_flight_s": collective_s / n,
        "collective_exposed_s": exposed_s / n,
        "op_self_s": {k: v / n for k, v in op_self.items()},
        "op_count": {k: c / n for k, c in op_count.items()},
        "module_s": {k: sorted(v) for k, v in modules.items()},
        "breakdown": {"device_ops": top(op_self), "idle_gaps": top(gap_by_host)},
    }
