"""What every cell shares: finding a cell's files by name, the parent's
clock, the runtime's start and stop, and leaving no process behind. The
parent process never touches a JAX backend: the chip belongs to the worker
the runtime grants it to."""

from __future__ import annotations

import glob
import importlib
import json
import os
import signal
import sys
import time
import uuid

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT = os.path.join(ROOT, ".bench_out")          # listed in .gitignore
_RUN_TAG = "BENCH_PROCESS_TAG"
# What a configuration's architecture module exports (`benchmarks/arch/`).
ARCH_INTERFACE = ("dims", "program", "make_logits", "make_loss",
                  "train_flops_per_token", "weight_bytes", "kv_block_bytes",
                  "kernel_costs")


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT, "BENCHMARK.json")


def arch(name: str):
    """The architecture module a configuration names: `benchmarks.arch.<name>`,
    or the module of a dotted name as it stands. A module that lacks a name
    of the interface is refused here, before any chip is asked for."""
    mod = importlib.import_module(name if "." in name else f"benchmarks.arch.{name}")
    missing = [n for n in ARCH_INTERFACE if not callable(getattr(mod, n, None))]
    if missing:
        raise SystemExit(f"architecture module {mod.__name__} lacks {missing}")
    return mod


def load_cell(name: str) -> dict:
    """A cell of BENCHMARK.json with its configuration and traffic files."""
    bench = benchmark()
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        names = [w["name"] for w in bench["workloads"]]
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; have {names}")
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(ROOT, entry["file"])
    mix = load_json(HERE, "traffic", cell["traffic"] + ".json")
    if mix["kind"] not in config["runners"]:
        raise SystemExit(
            f"{name}: {entry['file']} has no runner part for traffic kind {mix['kind']!r}")
    arch(config["arch"])
    return {"cell": cell, "config": config, "traffic": mix, "bench": bench}


def cell_metrics(bench: dict, cell_name: str, section: str) -> list:
    """Names of the `section` metrics this cell reports."""
    return [m["name"] for m in bench[section]
            if "workloads" not in m or cell_name in m["workloads"]]


def key_seed(seed: int) -> int:
    """Any whole number -> 31 bits for `jax.random.PRNGKey`."""
    import numpy as np

    return int(np.random.SeedSequence(seed).generate_state(1)[0] >> 1)


# ------------------------------------------------------------ chip, processes
def chip_holders() -> dict:
    """{pid: [device nodes]} for every process holding a TPU device node."""
    out = {}
    for fd in glob.glob("/proc/[0-9]*/fd/*"):
        try:
            target = os.readlink(fd)
        except OSError:
            continue
        if target.startswith("/dev/accel") or (
            target.startswith("/dev/vfio/") and target[10:].isdigit()
        ):
            out.setdefault(int(fd.split("/")[2]), []).append(target)
    return out


def wait_chip_free(timeout_s: float = 60.0) -> float:
    t0 = time.monotonic()
    while True:
        holders = chip_holders()
        if not holders:
            return time.monotonic() - t0
        if time.monotonic() - t0 > timeout_s:
            raise SystemExit(f"chip still held after {timeout_s}s: {holders}")
        time.sleep(0.1)


def _tagged(tag: str) -> list:
    needle = f"{_RUN_TAG}={tag}".encode()
    pids = []
    for path in glob.glob("/proc/[0-9]*/environ"):
        pid = int(path.split("/")[2])
        if pid == os.getpid():
            continue
        try:
            with open(path, "rb") as f:
                if needle in f.read().split(b"\0"):
                    pids.append(pid)
        except OSError:
            continue
    return pids


class Runtime:
    """`ray_tpu.init()` with this run's tag in the environment; `stop()`
    shuts it down, waits for its processes and kills what is left."""

    def __init__(self, chips: int):
        from ray_tpu.util.accelerators import tpu as tpu_util

        self.tag = uuid.uuid4().hex
        os.environ[_RUN_TAG] = self.tag
        os.environ["RAY_TPU_LOG_TO_DRIVER"] = "0"   # stdout carries the result
        self.cache_dir = tpu_util.place_compile_cache()
        if chips:
            have = tpu_util.detect_num_chips()
            if have < chips:
                raise SystemExit(
                    f"cell needs {chips} TPU chip(s); this machine exposes {have}")
        import ray_tpu

        self.ray = ray_tpu
        ray_tpu.init()
        if chips and ray_tpu.cluster_resources().get("TPU", 0) < chips:
            self.stop()
            raise SystemExit("runtime advertises fewer TPU chips than the cell needs")

    def stop(self, grace_s: float = 15.0) -> int:
        try:
            self.ray.shutdown()
        except Exception as e:  # noqa: BLE001 — still kill what is left
            print(f"shutdown: {e!r}", file=sys.stderr)
        deadline = time.monotonic() + grace_s
        while _tagged(self.tag) and time.monotonic() < deadline:
            time.sleep(0.1)
        left = _tagged(self.tag)
        for pid in left:
            try:
                os.kill(pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
        while _tagged(self.tag):
            time.sleep(0.05)
        return len(left)


def dump_logs():
    """On failure: the tail of every log of this run's runtime session."""
    for path in sorted(glob.glob(f"/tmp/ray_tpu/session_*_{os.getpid()}/*.log")):
        with open(path, "rb") as f:
            f.seek(max(0, os.path.getsize(path) - 5000))
            tail = f.read().decode(errors="replace")
        if tail.strip():
            print(f"----- {path}\n{tail}", file=sys.stderr)
