"""Readers: how a named metric is taken from a run's observations. Name,
unit, layer and `moves` live in `BENCHMARK.json` alone; the file
`benchmarks/metrics/<reader>.json` holds only the reader: one of the kinds
below with its parameters. A metric `<reader>.<suffix>` uses the file of
`<reader>`, so one reader serves a quantity that is split by cell because
its cells report different end-to-end metrics (`itl_p90_ms.sat`). A new
metric over a span, counter, series or trace event that the program records
is a new file, not new code: the runners keep every `engine.*` / `serve.*`
span of the window with its args (`obs["spans"]`), every integer of
`engine_stats()` as the window's delta (`obs["counters"]`) and every scalar
the train step reports as a series. What depends on the model is asked of
the configuration's architecture module (`obs["facts"]["arch"]`). A reader
that finds nothing to read returns None and the metric is left out of the
line."""

from __future__ import annotations

import statistics
from typing import Optional

from . import harness, peaks, stats


def _arch(obs):
    return harness.arch(obs["facts"]["arch"])


def _series(obs, r):
    return (obs.get("series") or {}).get(r["series"]) or None


def phase(obs, r):
    return (obs.get("phases") or {}).get(r["key"])


def series_percentile(obs, r):
    xs = _series(obs, r)
    return None if not xs else stats.percentile(xs, r["q"]) * r.get("scale", 1.0)


def series_mean(obs, r):
    xs = _series(obs, r)
    return None if not xs else statistics.fmean(xs) * r.get("scale", 1.0)


def series_share_above(obs, r):
    xs = _series(obs, r)
    if not xs:
        return None
    cut = r["factor"] * statistics.median(xs)
    return 100.0 * sum(x > cut for x in xs) / len(xs)


def counter(obs, r):
    v = (obs.get("counters") or {}).get(r["key"])
    return None if v is None else v * r.get("scale", 1.0)


def counter_ratio(obs, r):
    c = obs.get("counters") or {}
    num, den = c.get(r["num"]), c.get(r["den"])
    return None if num is None or not den else r.get("scale", 1.0) * num / den


def _window_spans(obs, r):
    """The spans of one name that start inside the window, or up to
    `after_window_s` after it (a request due at its very end); with `where`,
    those whose arg of that name is not 0."""
    w = obs.get("window")
    if not w:
        return []
    end = w["t0"] + w["seconds"] + r.get("after_window_s", 0.0)
    return [ev for ev in obs.get("spans") or ()
            if ev["name"] == r["span"] and w["t0"] <= ev["ts"] <= end
            and ("where" not in r or (ev.get("args") or {}).get(r["where"]))]


def _span_value(ev, arg):
    return ev["dur"] if arg == "dur" else (ev.get("args") or {}).get(arg, 0)


def span_percentile(obs, r):
    """Percentile `q` of a span's duration, or of one of its args (`arg`)."""
    xs = [_span_value(ev, r.get("arg", "dur")) for ev in _window_spans(obs, r)]
    return None if not xs else stats.percentile(xs, r["q"]) * r.get("scale", 1.0)


def span_sum(obs, r):
    """A span's arg (`dur`: its duration) summed over the window, over
    `"over"`: the window's `seconds`, the span `count`, or another arg's
    sum (`{"arg": name}`)."""
    evs = _window_spans(obs, r)
    if not evs:
        return None
    over = r["over"]
    den = (obs["window"]["seconds"] if over == "seconds" else len(evs) if over == "count"
           else sum(_span_value(ev, over["arg"]) for ev in evs))
    total = sum(_span_value(ev, r["arg"]) for ev in evs)
    return None if not den else r.get("scale", 1.0) * total / den


def span_time_mean(obs, r):
    """Mean over the window's TIME of a gauge the span carries (`arg`): each
    record's value holds from its span's end to the next one's, the first
    one's from the window's start."""
    evs = sorted(_window_spans(obs, r), key=lambda ev: ev["ts"])
    if not evs:
        return None
    w = obs["window"]
    edges = [w["t0"]] + [ev["ts"] + ev["dur"] for ev in evs[1:]] + [w["t0"] + w["seconds"]]
    held = sum(_span_value(ev, r["arg"]) * max(0.0, b - a)
               for ev, a, b in zip(evs, edges, edges[1:]))
    return r.get("scale", 1.0) * held / w["seconds"]


def whole_step_rate(obs, r):
    xs, f = _series(obs, {"series": "step_end_s"}), obs["facts"]
    if not xs:
        return None
    return stats.whole_step_rate(
        xs, 0.0, f["tokens_per_step"], f["chips"], 5 * f["group_steps"])


def group_median_rate(obs, r, min_groups=3):
    # three, not five: in a traced run the profiler's start and stop take
    # steps from the window
    xs, f = _series(obs, {"series": "step_end_s"}), obs["facts"]
    if not xs:
        return None
    return stats.group_median_rate(
        xs, 0.0, f["group_steps"], f["tokens_per_step"], f["chips"], min_groups)


def token_rate(obs, r):
    xs = _series(obs, r)
    return None if not xs or len(xs) < 2 else stats.token_rate(xs)


def mfu(obs, r):
    """The model step's own utilization: required FLOPs at the STEADY rate
    over the peak. Stalls of other layers show in `train_tok_s_chip` and
    `step_outlier_share`, and a traced run has the profiler's in it."""
    rate = group_median_rate(obs, r)
    if rate is None:
        return None
    f = obs["facts"]
    need = _arch(obs).train_flops_per_token(f["model"], f["seq"]) * rate
    return 100.0 * need / peaks.peak(obs["device"]["kind"])["bf16_flops"]


def _trace(obs):
    return obs.get("trace") or None


def trace_idle_share(obs, r):
    t = _trace(obs)
    return None if not t else 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def trace_module_ms(obs, r):
    t = _trace(obs)
    if not t:
        return None
    runs = [x for name, xs in t["module_s"].items() if r["module"] in name for x in xs]
    return None if not runs else 1e3 * statistics.median(runs)


def trace_op_share(obs, r):
    t = _trace(obs)
    if not t:
        return None
    got = sum(v for k, v in t["op_self_s"].items() if any(n in k for n in r["ops"]))
    return 100.0 * got / t["busy_s"]


def trace_collective_exposed_share(obs, r):
    t = _trace(obs)
    return None if not t else 100.0 * t["collective_exposed_s"] / t["window_s"]


def kernel_roofline(obs, r):
    """Least time the chip could take for the kernel's calls in the traced
    window, over the time they took. Costs are per call on one device's
    shard [BH/device, S, Dh]."""
    t = _trace(obs)
    if not t:
        return None
    k, f = r["kernel"], obs["facts"]
    names = [n for n in t["op_self_s"] if n.startswith(k)]
    took = sum(t["op_self_s"][n] for n in names)
    calls = sum(t["op_count"][n] for n in names)
    if not took or not calls:
        return None
    cost = _arch(obs).kernel_costs(f["model"], f["batch"], f["seq"], f["chips"])[k]
    least, _bound = peaks.roofline_seconds(cost, obs["device"]["kind"])
    return 100.0 * least * calls / took


def hbm_roofline(obs, r):
    """(weights + live KV) / peak HBM bandwidth over the decode program's
    median device time. Live KV is the pool times the live share of it, the
    gauge `live` names on a span, as its mean over the window's time."""
    ms = trace_module_ms(obs, r)
    util = span_time_mean(obs, r["live"])
    if ms is None or util is None:
        return None
    f = obs["facts"]
    weights = _arch(obs).weight_bytes(f["model"])
    least = (weights + util * f["kv_pool_bytes"]) / peaks.peak(obs["device"]["kind"])["hbm_bytes_s"]
    return 100.0 * least / (ms * 1e-3)


KINDS = {f.__name__: f for f in (
    phase, series_percentile, series_mean, series_share_above, counter,
    counter_ratio, span_percentile, span_sum, span_time_mean, whole_step_rate,
    group_median_rate, token_rate, mfu, trace_idle_share,
    trace_module_ms, trace_op_share, trace_collective_exposed_share,
    kernel_roofline, hbm_roofline,
)}


def reader_spec(name: str) -> dict:
    return harness.load_json(harness.HERE, "metrics", name.split(".", 1)[0] + ".json")


def read(name: str, obs: dict) -> Optional[float]:
    spec = reader_spec(name)
    return KINDS[spec["kind"]](obs, spec)
