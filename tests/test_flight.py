"""Cluster flight recorder (util/flight.py): ring semantics, storm drop
accounting, bubble attribution, and the merged Perfetto export.

Reference analogs: TorchTitan's flight recorder, Ray's timeline export.
The cluster-marked tests at the bottom cover the shipping paths (worker
piggyback + `flight_pull`); the rest are pure-unit on fabricated spans.
"""

import asyncio
import json
import time

import pytest

import ray_tpu
from ray_tpu.util import flight, tracing
from ray_tpu.util.flight import FlightRecorder


def _span(name, ts, dur, *, lane, step=None, mb=None, flow=None, trace="",
          worker=None, **extra):
    args = {"lane": lane, **extra}
    if step is not None:
        args["step"] = step
    if mb is not None:
        args["mb"] = mb
    if flow is not None:
        args["flow"] = flow
    ev = {"ts": ts, "event": "span", "name": name, "dur": dur,
          "trace": trace, "args": args}
    if worker is not None:
        ev["worker"] = worker
    return ev


# ------------------------------------------------------------------ ring
def test_ring_cap_drops_newest_and_counts():
    """Storm semantics: at cap the NEWEST span drops (the ring keeps the
    oldest evidence, matching task_events_dropped), and every drop is
    counted exactly once."""
    rec = FlightRecorder(cap=5, component="unit")
    for i in range(12):
        t = flight.now_ns()
        rec.record(f"storm.{i}", t, t + 1000, lane="test")
    assert len(rec) == 5
    assert rec.dropped == 7
    names = [e["name"] for e in rec.snapshot()]
    assert names == [f"storm.{i}" for i in range(5)]


def test_death_kind_spans_exempt_from_cap():
    """A storm must not evict the evidence: death/abort/kill spans append
    past the cap."""
    rec = FlightRecorder(cap=3, component="unit")
    t = flight.now_ns()
    for i in range(6):
        rec.record(f"noise.{i}", t, t, lane="test")
    rec.record("worker.death", t, t, lane="test", kind="death")
    rec.record("rpc.abort", t, t, lane="test", kind="abort")
    assert len(rec) == 5  # 3 capped + 2 exempt
    assert rec.dropped == 3
    kinds = [e["args"].get("kind") for e in rec.snapshot()]
    assert kinds[-2:] == ["death", "abort"]


def test_drain_emits_single_drop_marker_and_resets():
    rec = FlightRecorder(cap=2, component="unit-c")
    t = flight.now_ns()
    for i in range(5):
        rec.record(f"s{i}", t, t, lane="test")
    out = rec.drain()
    markers = [e for e in out if e.get("event") == "flight_spans_dropped"]
    assert len(markers) == 1
    assert markers[0]["n"] == 3 and markers[0]["component"] == "unit-c"
    # Counter and ring both reset: a quiet second drain ships nothing.
    assert rec.drain() == []
    assert rec.dropped == 0 and len(rec) == 0


def test_span_context_records_abort_on_raise():
    rec = FlightRecorder(cap=16)
    with pytest.raises(ValueError):
        with rec.span("kv.import", lane="serve/engine", trace="t1"):
            raise ValueError("boom")
    (ev,) = rec.snapshot()
    assert ev["name"] == "kv.import" and ev["trace"] == "t1"
    assert ev["args"]["kind"] == "abort"
    assert ev["args"]["error"] == "ValueError"


def test_requeue_respects_cap_and_counts_overflow():
    rec = FlightRecorder(cap=4)
    t = flight.now_ns()
    rec.record("live", t, t, lane="test")
    stale = [_span(f"old{i}", 1.0, 0.0, lane="test") for i in range(6)]
    rec.requeue(stale)
    assert len(rec) == 4
    # Requeued events go back in FRONT (they are older than the ring).
    assert rec.snapshot()[0]["name"] == "old0"
    assert rec.dropped == 3


def test_cumulative_counts_survive_drain_and_requeue():
    """Beside the per-drain `dropped` (the marker's count), the recorder
    keeps two totals since the process started: every span `record` was
    handed, and those a full ring refused or `requeue` could not put back.
    Neither `drain` nor `requeue` resets them, and a requeued span is not
    recorded twice."""
    rec = FlightRecorder(cap=4, component="unit")
    t = flight.now_ns()
    for i in range(6):
        rec.record(f"s{i}", t, t, lane="test")
    assert (rec.recorded_total, rec.dropped_total, rec.dropped) == (6, 2, 2)
    out = rec.drain()
    assert len(out) == 5 and rec.dropped == 0               # 4 spans + the marker
    assert (rec.recorded_total, rec.dropped_total) == (6, 2)
    rec.record("live", t, t, lane="test")
    rec.requeue(out[:4])                                    # room for 3 of the 4
    assert len(rec) == 4 and rec.dropped == 1
    assert (rec.recorded_total, rec.dropped_total) == (7, 3)
    rec.record("late", t, t, lane="test", kind="death")     # exempt from the cap
    assert (rec.recorded_total, rec.dropped_total) == (8, 3)
    rec.drain()
    assert rec.drain() == [] and (rec.recorded_total, rec.dropped_total) == (8, 3)


def test_clock_offset_inside_its_own_uncertainty_is_none():
    """An offset smaller than half the round trip that measured it is not
    evidence of skew: processes of one host keep the host's one clock."""
    rec = FlightRecorder(cap=8, component="unit")
    rec.set_clock_offset(0.004, rtt_s=0.010)
    assert rec.clock_offset == 0.0
    rec.set_clock_offset(-0.004, rtt_s=0.010)
    assert rec.clock_offset == 0.0
    rec.set_clock_offset(0.006, rtt_s=0.010)
    assert rec.clock_offset == 0.006
    rec.set_clock_offset(2.5)
    assert rec.clock_offset == 2.5


def test_clock_offset_rebases_spans_onto_controller_clock():
    rec = FlightRecorder(cap=8)
    rec.set_clock_offset(2.5)
    t0 = flight.now_ns()
    rec.record("x", t0, t0 + 10_000_000, lane="test")
    (ev,) = rec.snapshot()
    # wall(t0) = local wall + offset, within scheduling slop.
    assert abs(ev["ts"] - (time.time() + 2.5)) < 0.5
    assert ev["dur"] == pytest.approx(0.01, abs=1e-4)
    assert abs(rec.cluster_time() - (time.time() + 2.5)) < 0.5


def test_disabled_recorder_is_a_noop(monkeypatch):
    monkeypatch.setenv("RAY_TPU_FLIGHT", "0")
    flight._reset_for_tests()
    t = flight.now_ns()
    flight.record("never", t, t, lane="test")
    with flight.span("also.never", lane="test"):
        pass
    assert flight.recorder().snapshot() == []
    monkeypatch.setenv("RAY_TPU_FLIGHT", "1")
    flight.record("yes", t, t, lane="test")
    assert [e["name"] for e in flight.recorder().snapshot()] == ["yes"]
    flight._reset_for_tests()


# ------------------------------------------------- pipeline bubble report
def _two_lane_step(step, t0):
    """A deterministic 2-stage, 1-replica step: s0 computes [t0, t0+1] and
    [t0+2, t0+3]; s1 waits 1s then computes [t0+1, t0+2] and [t0+3, t0+4].
    Window 4s x 2 lanes = 8 lane-seconds, busy 4 -> bubble 0.5; s1's
    warmup 1s, s0's drain 1s, steady idle 2s."""
    l0, l1 = "mpmd/s0r0", "mpmd/s1r0"
    return [
        _span("mpmd.fwd", t0, 1.0, lane=l0, step=step, mb=0,
              flow=f"mb/{step}/0/r0"),
        _span("mpmd.recv_wait", t0, 1.0, lane=l1, step=step, mb=0),
        _span("mpmd.fwd", t0 + 1.0, 1.0, lane=l1, step=step, mb=0,
              flow=f"mb/{step}/0/r0"),
        _span("mpmd.bwd", t0 + 2.0, 1.0, lane=l0, step=step, mb=0,
              flow=f"mb/{step}/0/r0"),
        _span("mpmd.update", t0 + 3.0, 1.0, lane=l1, step=step),
    ]


def test_pipeline_report_decomposes_bubble():
    events = _two_lane_step(1, 100.0) + _two_lane_step(2, 200.0)
    rep = flight.pipeline_report(events)
    assert rep is not None and set(rep["steps"]) == {1, 2}
    s1 = rep["steps"][1]
    assert s1["lanes"] == 2
    assert s1["window_s"] == pytest.approx(4.0)
    assert s1["compute_s"] == pytest.approx(4.0)
    assert s1["bubble_frac"] == pytest.approx(0.5)
    assert s1["warmup_s"] == pytest.approx(1.0)  # s1 idle before its fwd
    assert s1["drain_s"] == pytest.approx(1.0)   # s0 idle after its bwd
    assert s1["steady_s"] == pytest.approx(2.0)
    assert s1["transport_wait_s"] == pytest.approx(1.0)
    # Aggregate over both (identical) steps keeps the same fraction.
    assert rep["bubble_frac"] == pytest.approx(0.5)
    assert rep["compute_s"] == pytest.approx(8.0)
    # Non-MPMD timelines yield no report, not a zero-filled one.
    assert flight.pipeline_report(
        [_span("engine.step", 1.0, 0.1, lane="serve/engine")]) is None


# -------------------------------------------------- data ingest attribution
def test_ingest_report_attributes_data_stalls():
    """The streaming-data half of the bubble story: stall seconds per
    (data lane, kind), throughput from `data.bundle` markers, and the
    bottleneck = the worst (lane, kind) pair."""
    events = [
        _span("data.bundle", 10.0, 0.0, lane="data/op0", rows=100, bytes=800),
        _span("data.bundle", 10.5, 0.0, lane="data/op0", rows=100, bytes=800),
        _span("data.wait", 10.0, 0.4, lane="data/op1"),
        _span("data.drain", 10.5, 0.2, lane="data/op1"),
        _span("data.backpressure", 10.2, 1.5, lane="data/ingest"),
        _span("data.starve", 12.0, 0.1, lane="data/ingest"),
        # Non-data spans stay out of the report entirely.
        _span("mpmd.fwd", 10.0, 1.0, lane="mpmd/s0r0", step=1, mb=0),
    ]
    rep = flight.ingest_report(events)
    assert rep is not None
    assert set(rep["lanes"]) == {"data/op0", "data/op1", "data/ingest"}
    op0 = rep["lanes"]["data/op0"]
    assert op0["bundles"] == 2 and op0["rows"] == 200 and op0["bytes"] == 1600
    stalls = rep["lanes"]["data/op1"]["stalls_s"]
    assert stalls["data.wait"] == pytest.approx(0.4)
    assert stalls["data.drain"] == pytest.approx(0.2)
    assert rep["bottleneck"]["lane"] == "data/ingest"
    assert rep["bottleneck"]["kind"] == "data.backpressure"
    assert rep["bottleneck"]["stall_s"] == pytest.approx(1.5)
    assert rep["window_s"] == pytest.approx(2.1)
    # The shared export ships the same report on every flight surface.
    assert flight.flight_payload(events)["ingest"] == rep
    # No data spans -> no report, not a zero-filled one.
    assert flight.ingest_report(
        [_span("engine.step", 1.0, 0.1, lane="serve/engine")]) is None


@pytest.mark.cluster
def test_streaming_pipeline_records_data_lane_spans(cluster_runtime, monkeypatch):
    """A live pull-plane run + ingest bridge lands per-operator spans on
    `data/op{i}` lanes and ingest spans on `data/ingest`, and the recorder
    snapshot feeds ingest_report end to end."""
    # The ring is read below: a flusher thread that an earlier test of this
    # process started would drain it to the controller every half second.
    monkeypatch.setattr(flight, "flush", lambda: 0)
    from ray_tpu import data as rdata
    from ray_tpu.data.context import DataContext
    from ray_tpu.data.streaming import StreamingIngest

    ctx = DataContext.get_current()
    saved = dict(ctx.__dict__)
    flight._reset_for_tests()
    try:
        ctx.streaming_pull = True
        ds = rdata.range(4000, parallelism=4).map_batches(
            lambda b: {"id": b["id"]})
        with StreamingIngest(ds, 500, epochs=1, prefetch=2) as ing:
            n = sum(len(b["id"]) for b in ing)
        assert n == 4000
        evs = flight.recorder().snapshot()
        data_lanes = {e["args"]["lane"] for e in evs
                      if e.get("name", "").startswith("data.")}
        assert any(l.startswith("data/op") for l in data_lanes), data_lanes
        rep = flight.ingest_report(evs)
        assert rep is not None
        op_lanes = [l for l in rep["lanes"] if l.startswith("data/op")]
        assert op_lanes
        # Every consumed bundle left a throughput marker on its op lane.
        assert sum(rep["lanes"][l]["bundles"] for l in op_lanes) >= 4
        assert sum(rep["lanes"][l]["rows"] for l in op_lanes) >= 4000
    finally:
        ctx.__dict__.update(saved)
        flight._reset_for_tests()


# --------------------------------------------------------- merged export
def test_merged_chrome_trace_lanes_flows_metadata():
    events = (
        _two_lane_step(1, 100.0)
        + [
            _span("disagg.prefill_handoff", 100.1, 0.02, lane="serve/router",
                  trace="req-9", flow="disagg/req-9"),
            _span("kv.import", 100.2, 0.03, lane="serve/engine",
                  trace="req-9", flow="disagg/req-9", worker="w1"),
            # A classic (non-flight) timeline event rides along untouched.
            {"ts": 100.0, "event": "task_submitted", "task_id": "ab" * 12},
        ]
    )
    out = flight.merged_chrome_trace(events)
    counts = tracing.validate_chrome_trace(out)
    assert counts.get("X", 0) >= 7
    assert counts.get("s", 0) >= 2 and counts.get("f", 0) >= 2

    lanes = {e["args"]["name"] for e in out
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {"mpmd/s0r0", "mpmd/s1r0", "serve/router",
            "serve/engine"} <= lanes
    procs = {e["args"]["name"] for e in out
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert "worker w1" in procs and "driver" in procs
    # Flow arrows: the microbatch chain and the disagg chain both present.
    flow_names = {e["name"] for e in out if e["ph"] in ("s", "f")}
    assert {"mb/1/0/r0", "disagg/req-9"} <= flow_names
    # crc32-stable: a second export is byte-identical (Perfetto diffing).
    assert json.dumps(out, sort_keys=True) == json.dumps(
        flight.merged_chrome_trace(events), sort_keys=True)
    # trace_id restriction keeps only that request's flight spans.
    only = flight.merged_chrome_trace(events, trace_id="req-9")
    assert {e["name"] for e in only if e["ph"] == "X" and
            e.get("cat") == "flight"} == {"disagg.prefill_handoff",
                                          "kv.import"}


def test_flight_spans_merge_into_trace_forest():
    """args.lane spans are the timeline's free span events, so a
    traced flight span joins the request's forest for free."""
    events = [
        _span("disagg.prefill_handoff", 10.0, 0.5, lane="serve/router",
              trace="req-3"),
        _span("kv.export", 10.1, 0.2, lane="serve/engine", trace="req-3"),
    ]
    t = tracing.trace_payload(events, trace_id="req-3")["trace"]
    assert t is not None
    assert {s["name"] for s in t["spans"]} == {"disagg.prefill_handoff",
                                               "kv.export"}


def test_replica_spans_ride_the_ring_into_the_trace_forest(monkeypatch):
    """A traced request's `replica.handle` / `replica.handle_stream` spans
    are appends to the flight ring (the second span system,
    `tracing.record_span`, one control-plane send a request on the
    request's own thread, is gone): the request's thread sends nothing,
    the drained events join the request's forest with the spans the ring
    already carried, and an untraced call records none."""
    import types

    import cloudpickle

    from ray_tpu.core import api
    from ray_tpu.serve.replica import Replica

    class Echo:
        def stream(self, n):
            yield from range(n)

        def unary(self):
            return "ok"

    sends = []
    rt = types.SimpleNamespace(
        backend=types.SimpleNamespace(record_trace_event=sends.append),
        _context=types.SimpleNamespace(trace_id="req-7"))
    monkeypatch.setenv("RAY_TPU_FLIGHT", "1")
    monkeypatch.setenv("RAY_TPU_FLIGHT_FLUSH_S", "3600")
    monkeypatch.setattr(api, "_runtime_or_attach", lambda: rt)
    flight._reset_for_tests()
    assert not hasattr(tracing, "record_span")
    rep = Replica("app", "dep", "dep#0", cloudpickle.dumps(Echo),
                  cloudpickle.dumps(((), {})))
    assert list(rep.handle_request_streaming("stream", (3,), {})) == [0, 1, 2]
    assert rep.handle_request("unary", (), {}) == "ok"
    rt._context.trace_id = None
    assert rep.handle_request("unary", (), {}) == "ok"      # untraced
    assert not sends
    events = flight.recorder().drain() + [
        _span("engine.completion", flight.cluster_time(), 0.1,
              lane="serve/engine-mixed/requests", trace="req-7")]
    flight._reset_for_tests()
    tree = tracing.trace_forest(events)["req-7"]
    assert [s["name"] for s in tree["spans"]] == [
        "replica.handle_stream", "replica.handle", "engine.completion"]
    for ev in tree["spans"][:2]:
        a = ev["args"]
        assert a["lane"] == "serve/replica" and a["request_id"] == "req-7"
        assert (a["app"], a["deployment"], a["replica"]) == ("app", "dep", "dep#0")
        assert ev["dur"] >= 0 and tree["start"] <= ev["ts"] <= tree["end"]
    assert tree["spans"][0]["args"]["method"] == "stream"


# The metric files that read the engine's books (ISSUE 37), each against
# counters of a window it can be worked out from by hand.
_BOOKS_OBS = {"counters": {
    "steps": 1000, "steps_chunk": 100, "steps_decode": 800,
    "steps_decode_only": 700, "step_ns": 20_000_000_000,
    "step_chunk_ns": 7_000_000_000, "step_decode_only_ns": 8_400_000_000,
    "host_ns": 3_000_000_000, "slow_ns": 250_000_000, "gc_ns": 40_000_000,
    "waited_ns": 9_000_000_000, "loop_ns": 45_000_000_000,
    "decode_lanes": 2400, "decode_bucket_lanes": 3200,
    "decode_lanes_beside_chunk": 600, "prefill_tokens": 30_000,
    "prefill_tokens_padded": 40_000, "stream_tokens": 2000,
    "stream_wake_ns": 300_000_000, "stream_send_ns": 1_000_000_000,
    "stream_behind": 50, "sched_ns": 100_000_000, "side_ns": 50_000_000,
    "build_ns": 1_500_000_000, "dispatch_ns": 900_000_000,
    "fetch_ns": 17_000_000_000, "sample_ns": 450_000_000,
    "export_ns": 2_000_000_000, "steps_slow": 3, "gc_collections": 7,
    # ISSUE 53: the gauges' integers, the two holes of the span, the ring
    "kv_block_held_ns": 9_000_000_000_000, "kv_block_cap_ns": 45_000_000_000_000,
    "moe_steps_read": 800, "moe_experts_touched_milli": 20_200_000,
    "moe_load_max_ppm": 100_000_000, "ut_steps_read": 800,
    "ut_exit_step_milli": 2_000_000, "between_ns": 400_000_000,
    "flight_spans_recorded": 4000,
    "flight_spans_dropped": 10}}


@pytest.mark.parametrize("name, want", [
    ("chunk_step_ms", 70.0), ("decode_only_step_ms", 12.0),
    ("step_host_ms", 3.0), ("step_host_ms.sat", 3.0), ("stall_ms", 250.0),
    ("gc_pause_ms", 40.0), ("gc_pause_ms.sat", 40.0),
    ("engine_step_ms_books", 20.0), ("engine_wait_share_books", 20.0),
    ("gap_chunk_share", 25.0), ("decode_bucket_mean", 4.0),
    ("decode_bucket_fill_share", 75.0), ("decode_bucket_fill_share.sat", 75.0),
    ("prefill_token_fill_share", 75.0), ("stream_wake_ms", 0.15),
    ("stream_send_ms", 0.5), ("stream_send_ms.sat", 0.5),
    ("stream_behind_share", 2.5), ("stream_behind_share.sat", 2.5),
    ("step_sched_ms", 0.1), ("step_side_ms", 0.05), ("step_build_ms_books", 1.5),
    ("step_dispatch_ms", 0.9), ("step_fetch_ms_books", 17.0),
    ("step_sample_ms", 0.45), ("step_export_ms_books", 2.0),
    ("stall_steps", 3.0), ("gc_collections", 7.0),
    ("kv_util_mean_books", 20.0), ("kv_util_mean_books.itl", 20.0),
    ("decode_lanes_mean_books", 3.0), ("decode_lanes_mean_books.sat", 3.0),
    ("moe_experts_touched_mean_books", 25.25), ("moe_expert_load_max_books", 12.5),
    ("ut_exit_step_mean_books", 2.5), ("step_between_ms", 0.4),
    ("step_between_ms.sat", 0.4), ("flight_drop_share", 0.25),
    ("flight_drop_share.sat", 0.25),
])
def test_metric_files_read_the_books(name, want):
    """Every per-layer metric of the books is a `BENCHMARK.json` entry with
    a parameter-only reader file: it reads the window's counters, gives
    nothing (the metric is left out) for a program without the counter, as
    the parent is, and a window's total of none reads 0."""
    from benchmarks import harness, readers

    entry = next(m for m in harness.benchmark()["per_layer"] if m["name"] == name)
    assert entry["source"] == "program_counter" and entry["workloads"]
    assert readers.reader_spec(name)["kind"] in ("counter", "counter_ratio")
    assert readers.read(name, _BOOKS_OBS) == pytest.approx(want, rel=1e-12)
    assert readers.read(name, {"counters": {"total_tokens": 5}}) is None
    if readers.reader_spec(name)["kind"] == "counter":
        quiet = {"counters": dict.fromkeys(_BOOKS_OBS["counters"], 0)}
        assert readers.read(name, quiet) == 0.0


# ------------------------------------------------------- phases of a loop
def test_phase_adds_elapsed_ns_and_never_imports_jax():
    """`flight.phase` in a process that has not imported jax: the time
    between its two stamps is ADDED to the accumulator, nothing is
    recorded, and jax stays out of `sys.modules` (the driver and the
    controller stay off it)."""
    import subprocess
    import sys

    code = (
        "import sys, time\n"
        "from ray_tpu.util import flight\n"
        "acc = {'a_ns': 5}\n"
        "with flight.phase('engine.a', acc, 'a_ns'):\n"
        "    time.sleep(0.01)\n"
        "with flight.phase('engine.a', acc, 'a_ns'):\n"
        "    with flight.phase('engine.b', acc, 'b_ns'):\n"
        "        time.sleep(0.01)\n"
        "with flight.phase('engine.c'):\n"
        "    pass\n"
        "assert 'jax' not in sys.modules, 'phase imported jax'\n"
        "assert acc['a_ns'] >= acc['b_ns'] + 10_000_000 > 20_000_000, acc\n"
        "assert set(acc) == {'a_ns', 'b_ns'} and len(flight.recorder()) == 0\n"
        "print('ok')\n"
    )
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr


def test_phase_keeps_time_when_the_body_raises():
    acc = {}
    with pytest.raises(KeyError):
        with flight.phase("engine.x", acc, "x_ns"):
            raise KeyError("boom")
    assert acc["x_ns"] >= 0


def _serve_step(ts, dur, decodes=2, prefills=0, **ns):
    return _span("engine.step", ts, dur, lane="serve/engine-mixed",
                 decodes=decodes, prefills=prefills, tokens=decodes,
                 **{**dict.fromkeys(flight.SERVE_STEP_PHASES, 0),
                    "waited_ns": 0, "queue_depth": 1, "running": decodes,
                    "kv_util": 0.25, **ns})


def _serve_request(tid, t, *, ingress=0.010, wait=0.050, prefill=0.200,
                   deliver=0.020, first_chunk=True, skip=()):
    """The spans one traced request leaves: caller, then engine."""
    submit = t + ingress
    first = submit + wait + prefill
    handle = {"method": "generate_stream", "replica": "r0", "chunks": 3}
    if first_chunk:
        handle["first_chunk_ts"] = first + deliver
    spans = {
        "serve.handle": _span("serve.handle", t, 1.0, lane="serve/handle",
                              trace=tid, **handle),
        "engine.queue_wait": _span("engine.queue_wait", submit, wait,
                                   lane="e/requests", trace=tid),
        "engine.prefill": _span("engine.prefill", submit + wait, prefill,
                                lane="e/requests", trace=tid),
        "engine.first_token": _span("engine.first_token", first, 0.0,
                                    lane="e/requests", trace=tid),
    }
    return [ev for name, ev in spans.items() if name not in skip]


def test_serve_report_phases_and_ttft_closure():
    """serve_report on hand-made spans: phase means per step, the idle
    share of the window, gauges over decode steps only; a request joins
    only when both sides are there (a missing engine side, a missing
    caller side and a request with no first chunk are left out, not
    guessed), and the four parts close the mean TTFT."""
    events = [
        _serve_step(100.0, 0.100, sched_ns=1_000_000, fetch_ns=9_000_000,
                    export_ns=6_000_000, waited_ns=500_000_000),
        _serve_step(100.9, 0.100, decodes=0, prefills=1, kv_util=0.75,
                    sched_ns=3_000_000, export_ns=2_000_000),   # no decode
    ]
    events += _serve_request("t1", 100.0)
    events += _serve_request("t2", 100.2, ingress=0.030, deliver=0.040)
    events += _serve_request("t3", 100.3, skip=("engine.prefill",))
    events += _serve_request("t4", 100.4, skip=("serve.handle",))
    events += _serve_request("t5", 100.5, first_chunk=False)
    rep = flight.serve_report(events)
    assert rep["steps"] == 2 and abs(rep["window_s"] - 1.0) < 1e-9
    assert abs(rep["step_ms"] - 100.0) < 1e-6
    assert abs(rep["phase_ms"]["sched_ns"] - 2.0) < 1e-9
    assert abs(rep["phase_ms"]["fetch_ns"] - 4.5) < 1e-9
    assert abs(rep["phase_ms"]["export_ns"] - 4.0) < 1e-9
    assert abs(rep["wait_share"] - 50.0) < 1e-6
    assert rep["decode_lanes_mean"] == 2 and rep["kv_util_mean"] == 0.25
    assert rep["queue_depth_mean"] == 1
    assert rep["requests"] == 2
    assert abs(rep["ingress_p50_ms"] - 20.0) < 1e-3     # median of 10, 30
    assert abs(rep["queue_wait_p50_ms"] - 50.0) < 1e-3
    assert abs(rep["prefill_p50_ms"] - 200.0) < 1e-3
    assert abs(rep["deliver_p50_ms"] - 30.0) < 1e-3
    assert abs(rep["ttft_mean_ms"] - 300.0) < 1e-3     # (280 + 320) / 2
    assert abs(rep["ttft_unattributed_share"]) < 1e-6
    # a delivery the spans do not explain shows as the unattributed share
    late = _serve_request("t6", 200.0)
    late[0]["args"]["first_chunk_ts"] += 0.070          # 280 -> 350 ms
    rep = flight.serve_report(late)
    assert rep["steps"] == 0 and rep["requests"] == 1
    assert abs(rep["ttft_unattributed_share"] - 0.0) < 1e-6  # deliver grew
    late[1]["dur"] -= 0.035                             # a part goes missing
    assert abs(flight.serve_report(late)["ttft_unattributed_share"] - 10.0) < 1e-6
    assert flight.serve_report(_two_lane_step(1, 100.0)) is None
    assert flight.flight_payload(events)["serve"]["requests"] == 2


def test_stalls_are_named_by_serve_report_and_printed_by_the_cli(capsys):
    """An `engine.stall` span (written for a slow step, traced or not) is
    listed by `serve_report` with when it was, the host's part of its span
    against the mean, the phase that took most of it, the GC inside it and
    the load, and `ray-tpu flight` prints one line for it: a stall of a
    run nobody traced is named from the timeline alone, even where its step
    record is gone."""
    import argparse

    from ray_tpu.scripts import cli

    phases = {**dict.fromkeys(flight.SERVE_STEP_PHASES[:-1], 0),
              "build_ns": 114_000_000, "sched_ns": 1_000_000,
              "fetch_ns": 9_000_000}
    events = [
        _serve_step(100.0, 0.010), _serve_step(100.5, 0.125, **phases),
        _span("engine.stall", 100.5, 0.125, lane="serve/engine-mixed",
              mean_ns=1_600_000, gc_ns=2_000_000, bucket=4, queue_depth=0,
              running=3, **phases)]
    rep = flight.serve_report(events)
    assert rep["steps"] == 2 and len(rep["stalls"]) == 1
    stall = rep["stalls"][0]
    assert stall["at_s"] == pytest.approx(0.5)
    assert stall["host_ms"] == pytest.approx(116.0)     # the span less fetch
    assert stall["mean_ms"] == pytest.approx(1.6)
    assert (stall["phase"], stall["phase_ms"]) == ("build", pytest.approx(114.0))
    assert stall["gc_ms"] == pytest.approx(2.0)
    assert (stall["bucket"], stall["queue_depth"], stall["running"]) == (4, 0, 3)
    assert flight.serve_report(events[:2])["stalls"] == []
    assert len(flight.serve_report(events[2:])["stalls"]) == 1   # alone on the ring

    class Backend:
        def _request(self, msg):
            return {"timeline": events}

    cli.cmd_flight(Backend(), None, argparse.Namespace(
        wait=0.0, trace_id=None, output=None))
    printed = capsys.readouterr().out
    assert "stall at +0.50s: host 116.0 ms against a mean of 1.60, build 114.0" in printed
    assert "bucket 4 running 3 queued 0" in printed


# ------------------------------------------- one export path, two surfaces
class _StubController:
    """Just enough controller for DashboardServer._route: a timeline plus
    the flight_pull handler the /api/flight endpoint awaits."""

    from ray_tpu.core.controller import Controller

    _timeline_view = Controller._timeline_view   # what both surfaces export
    del Controller

    def __init__(self, timeline):
        self.timeline = list(timeline)
        self.pulls = 0

    async def h_flight_pull(self, conn, meta, msg):
        self.pulls += 1
        return {"ok": True, "workers": 0}


def _route_json(controller, path, query):
    from ray_tpu.dashboard import DashboardServer

    server = DashboardServer(controller)
    status, ctype, body = asyncio.new_event_loop().run_until_complete(
        server._route(path, query))
    assert status.startswith("200"), body
    return json.loads(body)


def test_cli_and_dashboard_flight_exports_identical():
    """Satellite: `ray-tpu flight` and GET /api/flight are the same
    flight.flight_payload call — byte-identical output for one timeline
    (the CLI writes payload['trace_events']; the dashboard returns the
    whole payload)."""
    events = _two_lane_step(1, 100.0) + [
        _span("kv.fetch", 100.5, 0.01, lane="serve/kv", trace="req-1",
              flow="disagg/req-1", rung="span_pull"),
        {"ts": 99.0, "event": "flight_spans_dropped", "n": 4,
         "component": "worker"},
    ]
    c = _StubController(events)
    got = _route_json(c, "/api/flight", {})
    got.pop("ts")  # the HTTP envelope's scrape stamp
    want = flight.flight_payload(c._timeline_view())  # == what cmd_flight prints/writes
    assert c.pulls == 1  # the endpoint poked the workers first
    assert json.dumps(got, sort_keys=True, default=str) == json.dumps(
        want, sort_keys=True, default=str)
    assert got["dropped"] == 4
    # And restricted to one request id, still identical.
    got = _route_json(c, "/api/flight", {"trace_id": "req-1"})
    got.pop("ts")
    want = flight.flight_payload(c._timeline_view(), trace_id="req-1")
    assert json.dumps(got, sort_keys=True, default=str) == json.dumps(
        want, sort_keys=True, default=str)


def test_cli_and_dashboard_trace_exports_identical():
    """Same contract for `ray-tpu trace` / GET /api/traces via
    tracing.trace_payload."""
    events = [
        _span("proxy.request", 5.0, 0.6, lane="serve/router", trace="t1"),
        _span("engine.prefill", 5.1, 0.2, lane="serve/engine", trace="t1"),
    ]
    c = _StubController(events)
    got = _route_json(c, "/api/traces", {"trace_id": "t1"})
    got.pop("ts")
    want = tracing.trace_payload(events, trace_id="t1")["trace"]
    assert json.dumps(got, sort_keys=True, default=str) == json.dumps(
        want, sort_keys=True, default=str)
    got = _route_json(c, "/api/traces", {})
    got.pop("ts")
    want = tracing.trace_payload(events, limit=50)
    assert json.dumps(got, sort_keys=True, default=str) == json.dumps(
        want, sort_keys=True, default=str)


# --------------------------------------- what the controller keeps and hands on
@pytest.fixture
def bare_controller(tmp_path, monkeypatch):
    """A Controller with no socket and no loop: its timeline, the handlers
    that fill it and the ones that read it."""
    monkeypatch.setenv("RAY_TPU_CONTROLLER_SHARD_THREADS", "0")
    from ray_tpu.core import config as rt_config

    rt_config._reset_cache_for_tests()
    from ray_tpu.core.controller import Controller

    return Controller(num_cpus=1, resources={}, session_dir=str(tmp_path / "sess"),
                      object_store_memory=1 << 20, standalone=True)


def _ask(handler, msg):
    return asyncio.new_event_loop().run_until_complete(handler(None, {}, msg))


def test_state_summary_bounds_spans_and_lifecycle_events_apart(bare_controller):
    """12,000 spans and 12,000 lifecycle events arrive as `task_events`
    batches, interleaved: EVERY span comes back (a serving window's step
    records are not pushed out by the narration of its requests' tasks), at
    most 10,000 of the others (the newest), all in time order, and the
    cheap form carries no timeline."""
    c = bare_controller
    t0 = 1000.0
    for b in range(120):
        batch = []
        for i in range(100):
            k = b * 100 + i
            # a span is stamped with its START and shipped later: out of order
            batch.append(_span("engine.step", t0 + k * 0.01 - 0.3, 0.005,
                               lane="serve/engine", seq=k))
            batch.append({"ts": t0 + k * 0.01, "event": "task_span",
                          "task": f"t{k}", "seq": k})
        _ask(c.h_task_events, {"events": batch})
    got = _ask(c.h_state_summary, {})["timeline"]
    spans = [e for e in got if e["event"] == "span"]
    rest = [e for e in got if e["event"] != "span"]
    assert [e["args"]["seq"] for e in spans] == list(range(12_000))
    assert [e["seq"] for e in rest] == list(range(2_000, 12_000))
    assert [e["ts"] for e in got] == sorted(e["ts"] for e in got)
    assert len(c.timeline) == 24_000 and c._timeline_base == 0     # ONE list, untrimmed
    cheap = _ask(c.h_state_summary, {"counts_only": True})
    assert "timeline" not in cheap and cheap["object_gc_collections"] == 0
    assert cheap["object_gc_bytes"] == 0


def test_a_trim_leaves_one_marker_and_cursors_still_hold(bare_controller, monkeypatch):
    """The list's own bound: a trim drops the oldest half-cap and says so
    ONCE (`timeline_trimmed`: how many, how many of them spans), and a
    `poll_events` cursor taken before the trim still yields every event
    that came after it."""
    c = bare_controller
    monkeypatch.setattr(type(c), "_TIMELINE_CAP", 1_000)
    monkeypatch.setattr(type(c), "_TIMELINE_TRIM", 500)
    first = [(_span("engine.step", 10.0 + i, 0.1, lane="serve/engine") if i % 5 == 0
              else {"ts": 10.0 + i, "event": "task_span", "task": f"t{i}"})
             for i in range(900)]
    _ask(c.h_task_events, {"events": first})
    cursor = _ask(c.h_poll_events, {"cursor": -1})["cursor"]
    assert cursor == 900 and not any(
        e["event"] == "timeline_trimmed" for e in c.timeline)
    later = [{"ts": 2000.0 + i, "event": "actor_death", "actor": f"a{i}"}
             for i in range(150)]
    _ask(c.h_task_events, {"events": later})        # 1,050 > the cap: one trim
    markers = [e for e in c.timeline if e["event"] == "timeline_trimmed"]
    assert len(markers) == 1 and c._timeline_base == 500
    assert (markers[0]["n"], markers[0]["spans"]) == (500, 100)
    assert len(c.timeline) == 900 - 500 + 150 + 1
    polled = _ask(c.h_poll_events, {"cursor": cursor, "kinds": ["actor_death"]})
    assert [e["actor"] for e in polled["events"]] == [f"a{i}" for i in range(150)]
    assert polled["cursor"] == c._timeline_base + len(c.timeline)
    # a cursor from before the trimmed part clamps forward to what is left
    old = _ask(c.h_poll_events, {"cursor": 100, "limit": 10_000})
    assert len(old["events"]) == len(c.timeline)
    # the view reports the loss too: the marker is a lifecycle event
    view = _ask(c.h_state_summary, {})["timeline"]
    assert sum(e["event"] == "timeline_trimmed" for e in view) == 1
    assert sum(e["event"] == "span" for e in view) == 180 - 100


# ------------------------------------------------------------ shipping e2e
@pytest.mark.cluster
def test_worker_spans_reach_timeline_via_flight_pull(cluster_runtime):
    """The pull-on-demand path: a span recorded inside a worker process
    sits in that worker's ring until the controller pokes it with
    flight_pull; the piggybacked flush lands it in the merged timeline
    with the worker id stamped."""
    from ray_tpu.core import api

    @ray_tpu.remote
    def noisy():
        from ray_tpu.util import flight as fl

        t0 = fl.now_ns()
        fl.recorder().record("test.flight_unit", t0, t0 + 5_000_000,
                             lane="test/worker", attrs={"mark": 1})
        return 1

    assert ray_tpu.get(noisy.remote()) == 1
    backend = api._global_runtime().backend
    out = backend._request({"type": "flight_pull"})
    assert out["ok"] and out["workers"] >= 1

    deadline = time.monotonic() + 10
    spans = []
    while time.monotonic() < deadline:
        spans = [e for e in ray_tpu.timeline()
                 if e.get("event") == "span"
                 and e.get("name") == "test.flight_unit"]
        if spans:
            break
        backend._request({"type": "flight_pull"})
        time.sleep(0.3)
    assert spans, "flight span never reached the controller timeline"
    ev = spans[0]
    assert ev["args"]["lane"] == "test/worker"
    assert ev.get("worker")  # stamped by the piggyback flush
    assert ev["dur"] == pytest.approx(0.005, abs=2e-3)
    # The merged export renders it on its own named lane.
    chrome = flight.merged_chrome_trace(ray_tpu.timeline())
    lanes = {e["args"]["name"] for e in chrome
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert "test/worker" in lanes
