"""Task-causality tracing tests.

Reference analog: `python/ray/tests/test_tracing.py` (span parent/child
links around remote calls).
"""

import time

import pytest

import ray_tpu
from ray_tpu.util import tracing

pytestmark = pytest.mark.cluster


def _wait_spans(names, deadline_s=8.0):
    """Timeline events for direct-path tasks are worker-batched and
    eventually consistent — poll until the expected spans land COMPLETE
    (their task_done flushes in a later batch than the dispatch)."""
    end = time.monotonic() + deadline_s
    while True:
        spans = tracing.build_trace(ray_tpu.timeline())
        by_name = {}
        for s in spans.values():
            by_name.setdefault(s.name, []).append(s)
        done = all(
            n in by_name and all(s.done_at is not None for s in by_name[n])
            for n in names
        )
        if done or time.monotonic() >= end:
            return spans, by_name
        time.sleep(0.2)


def test_nested_task_parentage(cluster_runtime):
    @ray_tpu.remote
    def child(x):
        return x + 1

    @ray_tpu.remote
    def parent():
        return ray_tpu.get(child.remote(1))

    assert ray_tpu.get(parent.remote()) == 2

    spans, by_name = _wait_spans(["parent", "child"])
    assert "parent" in by_name and "child" in by_name
    child_span = by_name["child"][0]
    parent_span = by_name["parent"][0]
    # The child's parent pointer is the submitting task.
    assert child_span.parent == parent_span.task_id
    assert child_span in parent_span.children
    assert parent_span.duration is not None and parent_span.duration > 0


def test_task_tree_and_flows(cluster_runtime):
    @ray_tpu.remote
    def leaf(i):
        return i

    @ray_tpu.remote
    def fan():
        return ray_tpu.get([leaf.remote(i) for i in range(3)])

    assert ray_tpu.get(fan.remote()) == [0, 1, 2]
    # All three leaves flush from (possibly) different workers — poll until
    # the whole fan-out is visible.
    end = time.monotonic() + 8.0
    while True:
        tree = tracing.get_task_tree()
        fan_nodes = [t for t in tree if t["name"] == "fan"]
        if (fan_nodes and len(fan_nodes[0]["children"]) == 3) or (
            time.monotonic() >= end
        ):
            break
        time.sleep(0.2)
    assert fan_nodes and len(fan_nodes[0]["children"]) == 3

    flows = tracing.chrome_trace_with_flows(ray_tpu.timeline())
    kinds = {e["ph"] for e in flows}
    assert {"X", "s", "f"} <= kinds  # spans + causality arrows


def test_worker_phase_spans_nest_under_task(cluster_runtime):
    """Executing workers record dep-fetch/deserialize/execute/store-result
    phase events through the batched task_events channel; they attach to
    the task's span and inherit the trace id."""
    @ray_tpu.remote
    def leafy(x):
        return x * 2

    @ray_tpu.remote
    def rooty():
        return ray_tpu.get(leafy.remote(21))

    assert ray_tpu.get(rooty.remote()) == 42
    end = time.monotonic() + 10.0
    child = root = None
    while time.monotonic() < end:
        spans = tracing.build_trace(ray_tpu.timeline())
        by_name = {}
        for s in spans.values():
            by_name.setdefault(s.name, []).append(s)
        if "leafy" in by_name and "rooty" in by_name:
            child, root = by_name["leafy"][0], by_name["rooty"][0]
            if child.phases and root.phases:
                break
        time.sleep(0.2)
    assert child is not None and child.phases, "no phase events arrived"
    phase_names = {p["phase"] for p in child.phases}
    assert {"dep_fetch", "deserialize", "execute", "store_result"} <= phase_names
    # Phases sit inside the task's span window and carry its trace.
    assert all(p["dur"] >= 0.0 for p in child.phases)
    # One trace id across the whole submission tree: the root task roots
    # the trace; the child inherits it through the worker's context.
    assert child.trace == root.trace == root.task_id
    tree = child.to_dict()
    assert tree["phases"] and tree["trace"] == root.task_id


def test_chrome_trace_deterministic_across_hash_seeds():
    """Lane/flow ids derive from crc32, not builtin hash() — identical
    exports regardless of PYTHONHASHSEED (the salted-hash lanes used to
    reshuffle every run)."""
    import json as _json
    import os
    import subprocess
    import sys

    script = r"""
import importlib.util, json, sys
spec = importlib.util.spec_from_file_location(
    "tracing_standalone", sys.argv[1])
mod = importlib.util.module_from_spec(spec)
spec.loader.exec_module(mod)
events = [
    {"ts": 1.0, "event": "task_submitted", "task": "aa" * 12, "name": "root",
     "parent": None},
    {"ts": 1.1, "event": "task_dispatched", "task": "aa" * 12, "worker": "w1"},
    {"ts": 1.2, "event": "task_submitted", "task": "bb" * 12, "name": "kid",
     "parent": "aa" * 12},
    {"ts": 1.3, "event": "task_phase", "task": "bb" * 12, "phase": "execute",
     "dur": 0.1, "worker": "w2"},
    {"ts": 1.5, "event": "task_done", "task": "bb" * 12},
    {"ts": 1.6, "event": "task_done", "task": "aa" * 12},
    {"ts": 1.0, "event": "span", "name": "proxy.request", "dur": 0.6,
     "trace": "t1"},
]
print(json.dumps(mod.chrome_trace_with_flows(events), sort_keys=True))
"""
    src = tracing.__file__
    outs = []
    for seed in ("0", "12345"):
        env = dict(os.environ, PYTHONHASHSEED=seed)
        r = subprocess.run(
            [sys.executable, "-c", script, src],
            capture_output=True, text=True, timeout=60, env=env,
        )
        assert r.returncode == 0, r.stderr
        outs.append(r.stdout)
    assert outs[0] == outs[1]
    data = _json.loads(outs[0])
    # Deterministic = derived from content: lanes come from crc32.
    import zlib

    task_events = [e for e in data if e.get("args", {}).get("task_id") == "aa" * 12
                   and e["ph"] == "X" and e.get("cat") != "phase"]
    assert task_events
    assert task_events[0]["tid"] == zlib.crc32(("aa" * 12).encode()) % 1000


def test_api_timeline_writes_chrome_trace(cluster_runtime, tmp_path):
    """api.timeline(filename) writes chrome://tracing/Perfetto JSON as its
    docstring always promised (raw events via raw=True or return value)."""
    import json

    @ray_tpu.remote
    def t():
        return 1

    assert ray_tpu.get(t.remote()) == 1
    chrome_path = str(tmp_path / "chrome.json")
    raw_path = str(tmp_path / "raw.json")
    events = ray_tpu.timeline(chrome_path)
    assert isinstance(events, list) and events
    assert any("event" in e for e in events)  # return value stays raw
    chrome = json.load(open(chrome_path))
    assert chrome and all("ph" in e for e in chrome)
    # Schema check shared with the flight-recorder exports: every event
    # carries the fields Perfetto requires for its ph kind, flow arrows
    # pair up, and the whole thing JSON round-trips.
    counts = tracing.validate_chrome_trace(chrome)
    assert counts.get("X", 0) >= 1
    ray_tpu.timeline(raw_path, raw=True)
    raw = json.load(open(raw_path))
    # The controller timeline keeps accumulating between the two snapshots
    # (e.g. a late worker_registered), so the earlier snapshot must be a
    # prefix of the later one — equality would be a race.
    assert raw[: len(events)] == events


def test_serve_request_trace_end_to_end(cluster_runtime):
    """Acceptance path: one HTTP request against serve.LLMDeployment yields
    a single trace containing proxy, queue-wait, prefill, and first-token
    spans (plus replica + completion), visible via the timeline, the
    dashboard /api/traces, and exportable as chrome-trace JSON — and the
    engine's TTFT histogram, prefix-cache counters, and step-budget
    histogram land in /metrics with replica-tagged series."""
    import json
    import urllib.request

    from ray_tpu import serve

    serve.start(http_options={"host": "127.0.0.1", "port": 0})
    app = serve.LLMDeployment.bind(
        model="gpt2-small",
        model_overrides=dict(
            vocab_size=64, n_layers=2, d_model=48, n_heads=3, d_head=16,
            d_mlp=96, max_seq=128, attn_impl="ref", remat=False,
            dtype="float32",
        ),
        engine_options={"num_blocks": 32, "block_size": 4, "max_num_seqs": 4},
    )
    serve.run(app, name="llm-trace", route_prefix="/llm-trace")
    try:
        port = serve.http_port()
        body = json.dumps({"prompt": [1, 2, 3], "max_new_tokens": 4}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/llm-trace", data=body, method="POST"
        )
        resp = urllib.request.urlopen(req, timeout=120)
        rid = resp.headers.get("x-request-id")
        out = json.loads(resp.read())
        assert rid and len(out["tokens"]) == 4

        want = {
            "proxy.request", "replica.handle", "engine.queue_wait",
            "engine.admission", "engine.prefill", "engine.first_token",
            "engine.completion",
        }
        end = time.monotonic() + 20.0
        names = set()
        while time.monotonic() < end:
            spans = [
                e for e in ray_tpu.timeline()
                if e.get("event") == "span" and e.get("trace") == rid
            ]
            names = {e["name"] for e in spans}
            if want <= names:
                break
            time.sleep(0.3)
        assert want <= names, f"missing spans: {want - names}"
        lanes = {e["name"]: e["args"]["lane"] for e in spans}
        assert lanes["proxy.request"] == "serve/proxy"      # the flight ring
        assert lanes["replica.handle"] == "serve/replica"

        # Dashboard surfaces the same trace.
        # this test's own session: under xdist `session_latest` may be the
        # cluster of another worker's test
        from ray_tpu.core import api

        with open(api._global_runtime().backend.session_dir + "/address.json") as f:
            info = json.load(f)
        rows = json.loads(
            urllib.request.urlopen(info["dashboard_url"] + "/api/traces",
                                   timeout=5).read()
        )["traces"]
        assert any(r["trace_id"] == rid for r in rows)
        detail = json.loads(
            urllib.request.urlopen(
                info["dashboard_url"] + f"/api/traces?trace_id={rid}", timeout=5
            ).read()
        )
        assert {"engine.prefill", "proxy.request"} <= {
            s["name"] for s in detail["spans"]
        }

        # Chrome-trace export of exactly this request.
        chrome = tracing.chrome_trace_with_flows(ray_tpu.timeline(), trace_id=rid)
        assert any(e.get("name") == "engine.prefill" for e in chrome)

        # TTFT histogram: bucketed exposition reaches /metrics.
        end = time.monotonic() + 10.0
        text = ""
        while time.monotonic() < end:
            text = urllib.request.urlopen(
                info["metrics_url"], timeout=5).read().decode()
            if "serve_engine_ttft_s_count" in text:
                break
            time.sleep(0.25)
        assert "# TYPE serve_engine_ttft_s histogram" in text
        assert "serve_engine_ttft_s_bucket" in text and 'le="+Inf"' in text
        assert "serve_engine_ttft_s_sum" in text

        # Prefix-cache counters + chunked-prefill step-budget histogram ride
        # the same replica-tagged exposition (pruned by controller _drain).
        # Two identical 8-token prompts (2 full blocks): the first request
        # registers them, the second hits.
        for _ in range(2):
            body2 = json.dumps(
                {"prompt": [5, 6, 7, 8, 9, 10, 11, 12], "max_new_tokens": 2}
            ).encode()
            urllib.request.urlopen(
                urllib.request.Request(
                    f"http://127.0.0.1:{port}/llm-trace", data=body2,
                    method="POST",
                ),
                timeout=120,
            ).read()
        end = time.monotonic() + 10.0
        while time.monotonic() < end:
            text = urllib.request.urlopen(
                info["metrics_url"], timeout=5).read().decode()
            if "serve_engine_prefix_cache_hits_total" in text:
                break
            time.sleep(0.25)
        assert "# TYPE serve_engine_prefix_cache_hits_total counter" in text
        assert "# TYPE serve_engine_step_budget_tokens histogram" in text
        assert "serve_engine_step_budget_tokens_bucket" in text
        hit_line = next(
            l for l in text.splitlines()
            if l.startswith("serve_engine_prefix_cache_hits_total{")
        )
        assert 'deployment="LLMDeployment"' in hit_line
        assert 'replica="' in hit_line, "cache counters must be replica-tagged"
    finally:
        serve.shutdown()


def test_serve_handle_trace_covers_caller_to_delivery(cluster_runtime):
    """One trace id set by a Python caller covers the request's whole path
    through `handle.options(stream=True)`: `serve.handle` (caller's
    process), `replica.handle_stream` and the engine's five request spans,
    with the first chunk's stamp between the engine's first token and the
    span's end; the unary path leaves a `serve.handle` without it."""
    from ray_tpu import serve
    from ray_tpu.util import flight

    serve.start()
    app = serve.LLMDeployment.bind(
        model="gpt2-small",
        model_overrides=dict(
            vocab_size=64, n_layers=2, d_model=48, n_heads=3, d_head=16,
            d_mlp=96, max_seq=128, attn_impl="ref", remat=False,
            dtype="float32",
        ),
        engine_options={"num_blocks": 32, "block_size": 4, "max_num_seqs": 4},
    )
    handle = serve.run(app, name="llm-handle", route_prefix="/llm-handle",
                       timeout_s=120)
    try:
        # untraced, and it warms the engine's programs
        assert len(list(handle.options(stream=True).generate_stream.remote(
            [1, 2, 3], 3))) == 3
        tid, tid2 = tracing.new_trace_id(), tracing.new_trace_id()
        tracing.set_trace_id(tid)
        chunks = list(handle.options(stream=True).generate_stream.remote(
            [4, 5, 6, 7], 5))
        tracing.set_trace_id(tid2)
        out = handle.generate.remote([4, 5, 6, 7], 2).result(timeout_s=60)
        tracing.set_trace_id(None)
        assert len(chunks) == 5 and len(out["tokens"]) == 2

        want = {"serve.handle", "replica.handle_stream", "engine.queue_wait",
                "engine.admission", "engine.prefill", "engine.first_token",
                "engine.completion"}
        end = time.monotonic() + 60.0
        while time.monotonic() < end:
            flight.flush()                      # the caller's own ring
            events = [e for e in ray_tpu.timeline() if e.get("event") == "span"]
            mine = {e["name"]: e for e in events if e.get("trace") == tid}
            unary = {e["name"]: e for e in events if e.get("trace") == tid2}
            if want <= set(mine) and {"serve.handle", "replica.handle"} <= set(unary):
                break
            time.sleep(0.3)
        assert want <= set(mine), f"missing spans: {want - set(mine)}"
        # the replica's span came over the flight ring, and `ray-tpu trace
        # <id>` (trace_payload) shows the path from caller to completion
        assert mine["replica.handle_stream"]["args"]["lane"] == "serve/replica"
        shown = tracing.trace_payload(ray_tpu.timeline(), trace_id=tid)["trace"]
        assert want <= {s["name"] for s in shown["spans"]}
        h, first = mine["serve.handle"], mine["engine.first_token"]
        a = h["args"]
        assert a["method"] == "generate_stream" and a["chunks"] == 5
        assert a["replica"] and a["pick_ns"] >= 0 and a["submit_ns"] > 0
        assert a["lane"] == "serve/handle"
        # caller -> engine.submit -> first token -> first chunk -> last chunk
        # (one machine: every stamp is on the same clock to well under 50 ms)
        eps = 0.05
        assert h["ts"] <= mine["engine.queue_wait"]["ts"] + eps
        assert first["ts"] - eps <= a["first_chunk_ts"] <= h["ts"] + h["dur"] + eps
        assert "first_chunk_ts" not in unary["serve.handle"]["args"]
        assert unary["serve.handle"]["args"]["method"] == "generate"
        # the untraced call recorded nothing of its own
        assert sum(1 for e in events if e["name"] == "serve.handle") == 2
        rep = flight.serve_report([e for e in events if e.get("trace") == tid])
        assert rep["requests"] == 1 and abs(rep["ttft_unattributed_share"]) < 10
    finally:
        serve.shutdown()
