"""Where a serving cell's host time goes, from the program's own spans:
`python3 -m scripts.serve_phases --workload gpt2-large.chat --seed <n>
--profile <none|off|annotations|frames>` from the root of a checkout.

One run of a serving cell of the benchmark, through the benchmark's own
runner and traffic (`benchmarks/runners/serve.py`, untouched), with a trace
id on every request. What it adds is read from what the program records
itself: the `engine.step` phase attrs, the engine's request spans and
`serve.handle`, reduced by `ray_tpu.util.flight.serve_report` over the
spans of the measured window. The last line of stdout is one JSON object:
the cell's end-to-end metrics, its existing per-layer metrics, `serve`
(the report), `timeline` (what `ray_tpu.timeline()` handed back after the
window: the seconds it took, its events by kind, the step records inside
the window, the controller's resident memory) and, with a profiler session, `idle_gaps` and
`idle_named_share`: the share of the device's idle time in the traced
window that is named by the engine's own `engine.*` annotations.

`--profile` is the profiler session on the replica: `none` (no trace id
and no profiler: the MEASURED course, as `benchmarks.run --trace 0` runs
it, with the per-layer metrics that need no span beside the end-to-end
ones: the engine's books and the other `engine_stats()` counters, as the
window's deltas in `counters`; `serve.stalls` names the window's slow
steps from their `engine.stall` spans, read from the timeline after the
window), `off` (trace ids only: what tracing costs when it is on, against
`none`), `annotations` (Python tracer off: gaps are
named by the engine's phases), `frames` (the benchmark's own setting,
Python tracer on: inside a phase the innermost frame wins the gap's name,
so this is the ledger's view).

This is a builder's tool, not the yardstick: `BENCHMARK.json` reads none of
it. PERF.md §7 lists the edits to `benchmarks/` that would make these
numbers per-layer metrics."""

from __future__ import annotations

import argparse
import collections
import glob
import json
import math
import os
import sys
import time

from benchmarks import harness, readers
from benchmarks.runners import serve as serve_runner


class PhaseReplica(serve_runner.BenchReplica):
    """The benchmark's replica with the profiler session as asked for."""

    profile = "annotations"

    def bench_trace_start(self, trace_dir):
        import shutil

        import jax

        if self.profile == "off":
            return time.time()
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 1 if self.profile == "frames" else 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        return time.time()

    def bench_trace_stop(self, trace_dir):
        return None if self.profile == "off" else super().bench_trace_stop(trace_dir)


class PhaseReplicaOff(PhaseReplica):
    profile = "off"


class PhaseReplicaFrames(PhaseReplica):
    profile = "frames"


def replica_class(profile: str):
    # by its importable name, so that the replica's worker unpickles it by
    # reference (this file runs as `__main__`)
    from scripts import serve_phases as me

    return {"off": me.PhaseReplicaOff, "annotations": me.PhaseReplica,
            "frames": me.PhaseReplicaFrames}[profile]


def _census(events, took_s: float, w0: float, seconds: float) -> dict:
    """What `ray_tpu.timeline()` handed back after the window and what it
    cost: the seconds the call took, its events by kind (spans by name),
    how many `engine.step` records start inside the window (to hold against
    the books' `steps`) and the controller process's resident memory."""
    kinds = collections.Counter(
        f"span:{ev.get('name')}" if ev.get("event") == "span" else ev.get("event")
        for ev in events)
    steps = [ev["ts"] for ev in events if ev.get("event") == "span"
             and ev.get("name") == "engine.step"]
    rss = None
    for path in glob.glob("/proc/[0-9]*/cmdline"):
        try:
            with open(path, "rb") as f:
                if b"ray_tpu.core.controller_main" not in f.read():
                    continue
            with open(path.replace("cmdline", "status")) as f:
                rss = max(rss or 0, next(
                    int(l.split()[1]) * 1024 for l in f if l.startswith("VmRSS")))
        except (OSError, StopIteration):
            continue
    return {"seconds": took_s, "events": len(events),
            "kinds": dict(kinds.most_common()),
            "steps_in_window": sum(w0 <= ts <= w0 + seconds for ts in steps),
            "controller_rss_bytes": rss}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--profile", choices=("none", "off", "annotations", "frames"),
                    default="annotations")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    loaded = harness.load_cell(args.workload)
    cell, bench = loaded["cell"], loaded["bench"]
    if loaded["traffic"]["kind"] != "requests":
        raise SystemExit(f"{cell['name']} is not a serving cell")
    seconds = args.seconds if args.seconds is not None else bench["run_seconds"]
    chips = 0 if args.rehearse else cell["chips"]
    if chips:
        harness.wait_chip_free()
    t0_wall = time.time()
    os.makedirs(harness.OUT, exist_ok=True)
    runtime = harness.Runtime(chips)
    traced = args.profile != "none"
    if traced:
        serve_runner.BenchReplica = replica_class(args.profile)
    ctx = dict(loaded, seed=args.seed, seconds=seconds, trace=traced,
               rehearse=args.rehearse, t0_wall=t0_wall, sweep=None)
    try:
        obs = serve_runner.run(ctx)
        import ray_tpu
        from ray_tpu.util import flight

        # after the window, whatever the profile: the step records and the
        # `engine.stall` spans are written without a trace id too
        flight.flush()                       # this process's serve.handle spans
        time.sleep(1.0)
        w0 = t0_wall + obs["phases"]["setup_s"]
        t = time.perf_counter()
        events = ray_tpu.timeline()
        census = _census(events, time.perf_counter() - t, w0, seconds)
        spans = [ev for ev in events
                 if ev.get("event") == "span" and w0 <= ev.get("ts", 0) <= w0 + seconds
                 and ev.get("name", "").startswith(("engine.", "serve."))]
    except BaseException:
        harness.dump_logs()
        raise
    finally:
        runtime.stop()

    metrics = {}
    for section in ("end_to_end", "per_layer"):
        for name in harness.cell_metrics(bench, cell["name"], section):
            try:
                value = readers.read(name, obs)
            except Exception:  # noqa: BLE001 — a reader with nothing to read
                continue
            if value is not None and math.isfinite(value):
                metrics[name] = value
    line = {"workload": cell["name"], "seed": args.seed, "profile": args.profile,
            "device": obs["device"], "attempted": obs["attempted"],
            "failed": obs["failed"], "checks": obs["checks"],
            "metrics": metrics, "counters": obs["counters"],
            "spans": len(spans), "serve": flight.serve_report(spans),
            "timeline": census}
    tr = obs.get("trace")
    if tr:
        gaps = tr["breakdown"]["idle_gaps"]
        idle = tr["window_s"] - tr["busy_s"]
        line.update({
            "traced": {"window_s": tr["window_s"], "busy_s": tr["busy_s"]},
            "idle_gaps": gaps,
            "idle_named_share": 100.0 * sum(
                s for name, s in gaps if name.startswith("engine.")) / idle})
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
