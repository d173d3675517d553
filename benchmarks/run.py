"""One run of one cell: `python3 -m benchmarks.run --workload <name> --seed <n>
--seconds <s> --trace <0|1>` from the root of a checkout.

Starts the runtime, lets the cell's runner do the TPU work in a worker that
was granted the cell's chips (this process never touches a JAX backend),
and prints the contract's JSON object as the last line of stdout. With
`--trace 0` the metrics are the cell's end-to-end metrics, with `--trace 1`
its per-layer metrics. A machine without the chips the cell asks for gets a
non-zero exit code and no result line.

`--rehearse` is the CPU control-flow check (under `JAX_PLATFORMS=cpu`): the
configuration's tiny preset, counts only, a device name that says CPU and no
`metrics`. `--sweep r1,r2,...` (serving cells) offers each rate in turn in
one process, for finding the knee."""

from __future__ import annotations

import argparse
import importlib
import json
import math
import os
import sys
import time

from . import harness, readers

RUNNERS = {"train": "benchmarks.runners.train", "requests": "benchmarks.runners.serve"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--sweep", default=None)
    args = ap.parse_args(argv)

    loaded = harness.load_cell(args.workload)
    cell, bench = loaded["cell"], loaded["bench"]
    seconds = args.seconds if args.seconds is not None else bench["run_seconds"]
    platforms = os.environ.get("JAX_PLATFORMS", "").lower().split(",")
    if args.rehearse:
        if args.sweep:
            print("--sweep reads rates and times: it needs the chip", file=sys.stderr)
            return 1
        if platforms != ["cpu"]:
            print("--rehearse is the CPU check: run it under JAX_PLATFORMS=cpu",
                  file=sys.stderr)
            return 1
    elif "tpu" not in platforms and platforms != [""]:
        print(f"JAX_PLATFORMS={os.environ['JAX_PLATFORMS']!r} keeps JAX off the "
              "TPU; a cell is measured on the chip or not at all", file=sys.stderr)
        return 1

    chips = 0 if args.rehearse else cell["chips"]
    waited = harness.wait_chip_free() if chips else 0.0
    t0_wall = time.time()                       # the set-up clock starts here
    os.makedirs(harness.OUT, exist_ok=True)
    runtime = harness.Runtime(chips)
    ctx = dict(loaded, seed=args.seed, seconds=seconds, trace=bool(args.trace),
               rehearse=args.rehearse, t0_wall=t0_wall,
               sweep=[float(r) for r in args.sweep.split(",")] if args.sweep else None)
    runner = importlib.import_module(RUNNERS[loaded["traffic"]["kind"]])
    killed = 0
    try:
        obs = runner.run(ctx)
    except BaseException:
        harness.dump_logs()
        raise
    finally:
        killed = runtime.stop()

    if "jax" in sys.modules:
        from jax._src import xla_bridge

        if xla_bridge.backends_are_initialized():
            print("the parent process initialized a JAX backend", file=sys.stderr)
            return 1
    if chips:
        harness.wait_chip_free(30.0)   # a killed worker lets go in ~3 s

    device = obs["device"]
    if args.sweep:
        print(json.dumps({"sweep": obs["sweep"], "device": device}))
        return 0
    if args.rehearse:
        print(json.dumps({
            "rehearsal": True, "workload": cell["name"],
            "device": {**device, "note": "CPU rehearsal: counts only, no rate"},
            "counts": {"attempted": obs["attempted"], "failed": obs["failed"],
                       **{k: v for k, v in obs["counters"].items()
                          if isinstance(v, int)}},
            "checks": obs["checks"],
        }))
        return 0 if _correct(obs["checks"]) else 1
    if device["platform"] != "tpu" or device["count"] != cell["chips"]:
        print(f"cell wants {cell['chips']} TPU chip(s), ran on {device}", file=sys.stderr)
        return 1

    section = "per_layer" if args.trace else "end_to_end"
    units = {m["name"]: m["unit"] for m in bench[section]}
    metrics = {}
    for name in harness.cell_metrics(bench, cell["name"], section):
        try:
            value = readers.read(name, obs)
        except Exception as e:  # noqa: BLE001
            if not args.trace:          # an end-to-end metric that cannot be
                raise                   # taken fails the run
            print(f"{name}: not read: {e!r}", file=sys.stderr)
            continue
        if value is not None and math.isfinite(value):
            metrics[name] = {"value": value, "unit": units[name]}
        elif not args.trace:
            print(f"{name}: no finite value ({value}); an end-to-end metric that "
                  "cannot be taken fails the run", file=sys.stderr)
            return 1
    if args.trace and obs.get("trace"):
        device = {**device, "busy_s": obs["trace"]["busy_s"],
                  "window_s": obs["trace"]["window_s"]}
    line = {
        "correct": _correct(obs["checks"]), "attempted": obs["attempted"],
        "failed": obs["failed"], "metrics": metrics, "device": device,
        "checks": obs["checks"], "phases": obs["phases"],
        "chip_wait_s": waited, "processes_killed_at_exit": killed,
    }
    if args.trace and obs.get("trace"):
        line["breakdown"] = obs["trace"]["breakdown"]
    if not line["correct"]:
        print(f"incorrect: {json.dumps(obs['checks'])}", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


def _correct(checks: dict) -> bool:
    return all(v for v in checks.values() if isinstance(v, bool))


if __name__ == "__main__":
    sys.exit(main())
