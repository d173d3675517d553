"""Self-check of the yardstick, on any machine and without a chip:
`python3 -m benchmarks.selfcheck`.

Holds the trace reduction to a small trace recorded on a TPU v5e
(`selfcheck_data/tiny.xplane.pb`), the training rate to a synthetic step
series with the window's edge moved by half a step (it must not move) and
with one slow step (it must move by the stall's share of the time, while the
steady per-layer rate beside it does not), the percentile, lateness and token-rate arithmetic
to known answers, the span readers to hand-made spans, the GPT family's
architecture module to known sizes and costs, every name and unit of
`BENCHMARK.json` to the allowed characters, and every cell, configuration and
metric to its files.

`--record <path>` is how the recorded trace was made: it runs on the chip,
in this process, a few steps of a small jitted program between annotated
host spans."""

from __future__ import annotations

import json
import math
import os
import re
import shutil
import sys

from . import harness, peaks, readers, stats, traffic
from . import trace as trace_mod

DATA = os.path.join(harness.HERE, "selfcheck_data", "tiny.xplane.pb")
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
_UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def record(path: str) -> None:
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("--record needs the chip")

    def step(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=3)
        return y, (y * y).sum()

    fn = jax.jit(step)
    x = jnp.ones((256, 256), jnp.bfloat16)
    w = jnp.ones((256, 256), jnp.bfloat16) * 0.01
    float(fn(x, w)[1])
    tmp = os.path.join(harness.OUT, "selfcheck_trace")
    shutil.rmtree(tmp, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    import time
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            x, s = fn(x, w)
        with jax.profiler.TraceAnnotation("bench.loss_read"):
            float(s)
        with jax.profiler.TraceAnnotation("bench.pause"):
            time.sleep(0.002)
    jax.profiler.stop_trace()
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    shutil.copy(trace_mod.find_xplane(tmp), path)
    print(f"recorded {path}: {os.path.getsize(path)} bytes")
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            events = list(line.events)
            print(plane.name, "|", line.name, len(events),
                  [(e.name, int(e.duration_ns)) for e in events[:6]])
    print(json.dumps(trace_mod.reduce_trace(path), indent=1)[:3000])


def check_trace() -> None:
    r = trace_mod.reduce_trace(DATA)
    assert r["devices"] == 1, r["devices"]
    assert 0 < r["busy_s"] < r["window_s"], (r["busy_s"], r["window_s"])
    steps = [v for k, v in r["module_s"].items() if "jit_step" in k]
    assert steps and len(steps[0]) == 3, r["module_s"].keys()
    # self times add up to the busy time: nothing is counted twice
    assert abs(sum(r["op_self_s"].values()) - r["busy_s"]) < 0.02 * r["busy_s"], (
        sum(r["op_self_s"].values()), r["busy_s"])
    gaps = dict(r["breakdown"]["idle_gaps"])
    assert any("bench." in k for k in gaps), gaps
    assert max(gaps, key=gaps.get) == "bench.pause", gaps
    assert r["collective_exposed_s"] == 0.0
    # a synthetic device line: a loop that encloses its body
    ops = [(0.0, 100.0, "%while.1 = (s32[]) while(...)"), (10.0, 40.0, "%fusion.2 = f32[] fusion(...)"),
           (40.0, 70.0, "%all-gather-done.3 = f32[] all-gather-done(...)"), (80.0, 90.0, "%copy.4 = copy(...)")]
    selfs = {trace_mod.op_family(n): s for _, _, n, s in trace_mod._self_times(ops)}
    assert selfs == {"while": 30.0, "fusion": 30.0, "all-gather-done": 30.0,
                     "copy": 10.0}, selfs
    host = [[(0.0, 1000.0, "outer"), (100.0, 300.0, "inner"), (120.0, 130.0, "tiny")]]
    got = trace_mod._attribute_gaps([(110.0, 150.0), (250.0, 450.0)], host)
    want = {"tiny": 10e-9, "inner": 30e-9 + 50e-9, "outer": 150e-9}
    assert all(abs(got[k] - v) < 1e-12 for k, v in want.items()) and len(got) == 3, got


def check_rate() -> None:
    tokens, group, step = 13 * 1024, 8, 0.72
    ends = lambda n: [step * (i + 1) for i in range(n)]
    base = ends(62)                                       # a 45 s window
    want = tokens / step
    rate = lambda xs: stats.whole_step_rate(xs, 0.0, tokens, 1, 5 * group)
    steady = lambda xs: stats.group_median_rate(xs, 0.0, group, tokens, 1)
    assert abs(rate(base) - want) < 1e-6 * want
    # the edge half a step earlier or later, one step fewer or more: unmoved
    for n in (61, 63):
        assert abs(rate(ends(n)) - want) < 1e-6 * want
        assert abs(steady(ends(n)) - want) < 1e-6 * want
    naive = lambda xs, window: len(xs) * tokens / window  # what PR 22 did
    assert abs(naive(base[:61], 44.5) / naive(base, 44.5) - 1) > 0.01
    # one stall of 0.5 s in 44.6 s: the end-to-end rate falls by its share of
    # the time; the steady rate beside it (per layer) does not see it
    slow = [t + (0.5 if i >= 20 else 0.0) for i, t in enumerate(base)]
    assert abs(rate(slow) / want - base[-1] / slow[-1]) < 1e-9
    assert rate(slow) < 0.99 * want
    assert abs(steady(slow) - want) < 1e-6 * want
    for fn, xs in ((rate, base[:39]), (steady, base[:39])):
        try:
            fn(xs)
            raise AssertionError("39 steps, 4 groups, must not give a rate")
        except ValueError:
            pass


def check_arithmetic() -> None:
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile([1, 2, 3, 4], 90) == 3.7
    assert stats.percentile([7], 99) == 7
    assert stats.token_rate([1.0, 1.5, 2.0, 3.0]) == 1.5
    obs = {"series": {"late_ms": [0.1, 0.2, 5.0], "ttft_ms": [10.0, 20.0, math.inf]}}
    assert readers.series_percentile(obs, {"series": "ttft_ms", "q": 50}) == 20.0
    assert readers.series_percentile(obs, {"series": "late_ms", "q": 100}) == 5.0
    assert peaks.roofline_seconds(peaks.flash_fwd_cost(260, 1024, 64), "TPU v5 lite")[1] == "compute"
    try:
        peaks.peak("TPU v9")
        raise AssertionError("unknown device must be an error")
    except ValueError:
        pass
    mix = harness.load_json(harness.HERE, "traffic", "chat-steady.json")
    a = traffic.requests(mix, 1, 45.0, 50304)
    b = traffic.requests(mix, 2 ** 31 + 12345, 45.0, 50304)
    assert [(r.due_s, len(r.prompt), r.max_new_tokens) for r in a] == [
        (r.due_s, len(r.prompt), r.max_new_tokens) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in b]
    assert all(r.due_s < 45.0 for r in a) and len(a) == len(b)
    # warm-up: a wave a bucket of blocks from 2 to 64, each within its width
    # and reaching 17 lanes; then a prompt for each prefill program in use
    plan = traffic.warm_plan(mix, 45.0, 16, 32, 64)
    assert [w[0][0] for w in plan[:-1]] == [16, 32, 64, 128, 256, 512], plan
    for wave in plan[:-1]:
        room = 2 * wave[0][0]
        assert len(wave) == 17 and all(length + new < room for length, new in wave)
    used = {(traffic._pow2(c), traffic._pow2(-(-(len(r.prompt) + 1) // 16)))
            for r in a for c in [64] * (len(r.prompt) // 64) + [len(r.prompt) % 64 or 64]}
    warmed = {(traffic._pow2(c), traffic._pow2(-(-(length + 1) // 16)))
              for length, _ in plan[-1] for c in [64] * (length // 64) + [length % 64 or 64]}
    assert used <= warmed, used - warmed


def check_spans() -> None:
    """The span readers on hand-made spans: a known percentile, a known sum
    over seconds, count and another arg, the window's edges, and an absent
    span, which gives None so that the metric is left out."""
    step = lambda ts, dur, **args: {"name": "engine.step", "ts": ts, "dur": dur, "args": args}
    obs = {"window": {"t0": 100.0, "seconds": 10.0}, "spans": [
        step(99.0, 0.5, decodes=9, waited_ns=10 ** 9, kv_util=0.9),      # before the window
        step(100.0, 0.010, decodes=0, prefills=1, waited_ns=2 * 10 ** 9, kv_util=0.0),
        step(102.0, 0.020, decodes=2, prefills=0, waited_ns=0, kv_util=0.2),
        step(104.0, 0.030, decodes=4, prefills=1, waited_ns=10 ** 9, kv_util=0.4),
        step(110.5, 0.040, decodes=8, waited_ns=10 ** 9, kv_util=0.8),   # after it
        {"name": "engine.queue_wait", "ts": 101.0, "dur": 0.001, "args": {}},
        {"name": "engine.queue_wait", "ts": 105.0, "dur": 0.003, "args": {}},
        {"name": "engine.queue_wait", "ts": 110.5, "dur": 0.005, "args": {}},
    ]}
    near = lambda got, want: got is not None and abs(got - want) < 1e-9 * max(1.0, abs(want))
    steps = {"span": "engine.step"}
    assert near(readers.span_sum(obs, {**steps, "arg": "dur", "over": "count", "scale": 1e3}), 20.0)
    assert near(readers.span_sum(obs, {**steps, "arg": "waited_ns", "over": "seconds",
                                       "scale": 1e-7}), 30.0)            # 3 s of 10: 30%
    assert near(readers.span_sum(obs, {**steps, "arg": "decodes", "over": "count",
                                       "where": "decodes"}), 3.0)        # (2 + 4) / 2 steps
    assert near(readers.span_sum(obs, {**steps, "arg": "decodes", "over": {"arg": "prefills"}}), 3.0)
    assert near(readers.span_percentile(obs, {**steps, "q": 50, "scale": 1e3}), 20.0)
    assert near(readers.span_percentile(obs, {**steps, "q": 100, "arg": "decodes"}), 4.0)
    waits = {"span": "engine.queue_wait", "q": 50, "scale": 1e3}
    assert near(readers.span_percentile(obs, waits), 2.0)
    assert near(readers.span_percentile(obs, {**waits, "after_window_s": 1.0}), 3.0)
    # kv_util held 0.0 until 102.02, 0.2 until 104.03, 0.4 to the window's end
    want = (0.2 * (104.03 - 102.02) + 0.4 * (110.0 - 104.03)) / 10.0
    assert near(readers.span_time_mean(obs, {**steps, "arg": "kv_util"}), want)
    absent = {"span": "engine.nothing", "arg": "dur", "over": "count", "q": 50}
    assert readers.span_sum(obs, absent) is None and readers.span_percentile(obs, absent) is None
    assert readers.span_sum({}, {**steps, "arg": "dur", "over": "count"}) is None
    assert readers.span_sum(obs, {**steps, "arg": "dur", "over": {"arg": "absent"}}) is None
    # through a metric's own file, as `run.py` reads it; no spans: left out
    assert near(readers.read("engine_step_ms", obs), 20.0)
    assert near(readers.read("decode_lanes_mean.sat", obs), 3.0)
    assert near(readers.read("queue_wait_p50_ms", obs), 3.0)
    assert near(readers.read("engine_wait_share", obs), 30.0)
    assert readers.read("prefill_span_p50_ms", obs) is None
    assert readers.read("engine_step_ms", {"spans": [], "window": obs["window"]}) is None


def check_arch() -> None:
    """The GPT family's module on gpt2-large's own file: its sizes, the
    program model they select, and the operations and bytes to known answers."""
    gpt = harness.arch("gpt")
    config = harness.load_json(harness.HERE, "configs", "gpt2-large.json")
    m = gpt.dims(config, False)
    assert m == {"n_layers": 36, "d_model": 1280, "n_heads": 20, "d_head": 64, "d_mlp": 5120,
                 "max_seq": 1024, "vocab_size": 50304, "pos": "learned", "rotary_dim": 64,
                 "parallel_block": False, "tie_embeddings": True}, m
    assert gpt.program(config, m) == ("gpt2-large", m)
    assert gpt.dims(config, True)["d_model"] == config["rehearsal"]["sizes"]["n_embd"]
    f = gpt.train_flops_per_token(m, 1024)
    assert abs(f / 4.95e9 - 1) < 0.02, f                   # 6 x 708M + attention
    assert gpt.weight_bytes(m) == 4 * 772177920            # tied head counted once
    assert gpt.weight_bytes({**m, "tie_embeddings": False}) == 4 * (772177920 + 1280 * 50304)
    assert gpt.kv_block_bytes(m, 16) == 2949120            # the 2.95 MB of the config's notes
    costs = gpt.kernel_costs(m, 13, 1024, 1)
    assert costs == {"flash_fwd": peaks.flash_fwd_cost(260, 1024, 64),
                     "flash_bwd_dq": peaks.flash_bwd_dq_cost(260, 1024, 64),
                     "flash_bwd_dkv": peaks.flash_bwd_dkv_cost(260, 1024, 64)}
    fsdp = gpt.dims(harness.load_json(harness.HERE, "configs", "gptj-6b.json"), False)
    assert (fsdp["d_head"], fsdp["pos"], fsdp["parallel_block"], fsdp["tie_embeddings"]) == (
        256, "rotary", True, False)
    assert gpt.kernel_costs(fsdp, 8, 2048, 4)["flash_fwd"] == peaks.flash_fwd_cost(32, 2048, 256)
    try:
        harness.arch("no_such_family")
        raise AssertionError("an unknown architecture must be an error")
    except ImportError:
        pass


def check_files() -> None:
    bench = harness.benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}, set(bench)
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert _NAME.match(m["name"]) and _UNIT.match(m["unit"]), m
        assert readers.reader_spec(m["name"])["kind"] in readers.KINDS, m["name"]
    for m in bench["per_layer"]:
        assert m["moves"] in e2e, m
    for c in bench["configs"]:
        assert _NAME.match(c["name"]) and os.path.exists(os.path.join(harness.ROOT, c["file"]))
        config = harness.load_json(harness.ROOT, c["file"])
        assert config["reduced"] == c["reduced"]
        harness.arch(config["arch"])            # resolves, with the whole interface
    for w in bench["workloads"]:
        assert _NAME.match(w["name"]) and _NAME.match(w["traffic"]) and len(w["why"]) <= 200
        loaded = harness.load_cell(w["name"])
        for section in ("end_to_end", "per_layer"):
            names = harness.cell_metrics(bench, w["name"], section)
            assert names, (w["name"], section)
        moved = {m["moves"] for m in bench["per_layer"]
                 if "workloads" not in m or w["name"] in m["workloads"]}
        assert moved <= set(harness.cell_metrics(bench, w["name"], "end_to_end")), w["name"]
        assert loaded["traffic"]["kind"] in loaded["config"]["runners"]


CHECKS = (check_rate, check_arithmetic, check_spans, check_arch, check_files, check_trace)


def main() -> int:
    if len(sys.argv) == 3 and sys.argv[1] == "--record":
        record(sys.argv[2])
        return 0
    for check in CHECKS:
        check()
        print(f"ok  {check.__name__}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
