"""Observability plane: state API, CLI, Prometheus /metrics, log tailing.

Reference analogs: `python/ray/util/state/state_cli.py` (`ray list ...`),
`python/ray/scripts/scripts.py` (`ray status/timeline`),
`_private/metrics_agent.py` (Prometheus), `_private/log_monitor.py`.
"""

import json
import os
import subprocess
import sys
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu.core import api

pytestmark = pytest.mark.cluster


@pytest.fixture
def cluster_rt():
    ray_tpu.init(num_cpus=4)
    yield api._global_runtime().backend
    ray_tpu.shutdown()


def _session_info():
    """This test's own session, not `session_latest`: under xdist that
    link may belong to the cluster of another worker's test."""
    path = os.path.join(api._global_runtime().backend.session_dir, "address.json")
    with open(path) as f:
        return json.load(f)


def test_state_api_lists(cluster_rt):
    backend = cluster_rt

    @ray_tpu.remote
    class A:
        def ping(self):
            return "pong"

    a = A.options(name="obs-actor").remote()
    assert ray_tpu.get(a.ping.remote()) == "pong"

    actors = backend._request({"type": "list_actors"})["actors"]
    assert any(x["name"] == "obs-actor" and x["state"] == "ALIVE" for x in actors)
    workers = backend._request({"type": "list_workers"})["workers"]
    assert len(workers) >= 1 and all("node_id" in w for w in workers)
    ref = ray_tpu.put(list(range(50_000)))
    objs = backend._request({"type": "list_objects"})
    assert objs["total"] >= 1
    _ = ref


def test_prometheus_metrics_endpoint(cluster_rt):
    info = _session_info()
    text = urllib.request.urlopen(info["metrics_url"], timeout=5).read().decode()
    assert "ray_tpu_workers_alive" in text
    assert "ray_tpu_object_store_bytes" in text
    assert "ray_tpu_nodes_alive 1" in text


def test_user_metrics_exported(cluster_rt):
    from ray_tpu.util.metrics import Counter, Gauge

    Counter("my_app_events").inc(3)
    Counter("my_app_events").inc(2)
    Gauge("my_app_qps").set(7.5, tags={"route": "a"})
    # Counters and gauges ride the 0.25 s flusher too: poll, a fixed sleep
    # sits on its edge.
    text = _scrape(lambda t: "my_app_events 5" in t and "my_app_qps" in t)
    assert "my_app_events 5" in text
    assert 'my_app_qps{route="a"} 7.5' in text
    # Every user family carries a TYPE header so scrapers classify counters
    # as counters (bare series default to untyped).
    assert "# TYPE my_app_events counter" in text
    assert "# TYPE my_app_qps gauge" in text


def _scrape(pred, deadline_s=10.0):
    """Poll /metrics until `pred(text)` holds (what a process records
    flushes on a short interval)."""
    info = _session_info()
    end = time.monotonic() + deadline_s
    text = ""
    while time.monotonic() < end:
        text = urllib.request.urlopen(info["metrics_url"], timeout=5).read().decode()
        if pred(text):
            return text
        time.sleep(0.25)
    return text


def test_histogram_bucket_exposition(cluster_rt):
    """Histograms export real cumulative `_bucket{le=...}` / `_sum` /
    `_count` series (percentile-capable), not a last-value gauge."""
    from ray_tpu.util.metrics import Histogram

    h = Histogram("obs_req_lat_s", "request latency", boundaries=[0.1, 1.0, 10.0])
    for v in (0.05, 0.5, 0.6, 5.0, 50.0):
        h.observe(v)
    text = _scrape(lambda t: "obs_req_lat_s_count 5" in t)
    assert "# TYPE obs_req_lat_s histogram" in text
    assert "# HELP obs_req_lat_s request latency" in text
    assert 'obs_req_lat_s_bucket{le="0.1"} 1' in text
    assert 'obs_req_lat_s_bucket{le="1.0"} 3' in text  # cumulative
    assert 'obs_req_lat_s_bucket{le="10.0"} 4' in text
    assert 'obs_req_lat_s_bucket{le="+Inf"} 5' in text
    assert "obs_req_lat_s_count 5" in text
    assert "obs_req_lat_s_sum 56." in text  # 0.05+0.5+0.6+5+50


def test_histogram_tagged_series(cluster_rt):
    from ray_tpu.util.metrics import Histogram

    h = Histogram("obs_tagged_s", boundaries=[1.0])
    h.observe(0.5, tags={"route": "a"})
    h.observe(2.0, tags={"route": "b"})
    text = _scrape(lambda t: t.count("obs_tagged_s_count") >= 2)
    assert 'obs_tagged_s_bucket{route="a",le="1.0"} 1' in text
    assert 'obs_tagged_s_bucket{route="b",le="1.0"} 0' in text
    assert 'obs_tagged_s_bucket{route="b",le="+Inf"} 1' in text


def test_metric_staleness_pruning(shutdown_only):
    """Series idle past the staleness window drop out of /metrics — gauges
    from dead replicas/workers must not persist forever."""
    os.environ["RAY_TPU_METRIC_STALENESS_S"] = "1.0"
    try:
        ray_tpu.init(num_cpus=2)
        from ray_tpu.util.metrics import Gauge

        Gauge("obs_stale_g").set(4.2)
        text = _scrape(lambda t: "obs_stale_g 4.2" in t)
        assert "obs_stale_g 4.2" in text
        time.sleep(1.5)
        text = _scrape(lambda t: "obs_stale_g" not in t, deadline_s=5.0)
        assert "obs_stale_g" not in text
    finally:
        os.environ.pop("RAY_TPU_METRIC_STALENESS_S", None)


def test_train_and_flight_metric_staleness(shutdown_only):
    """The flight-recorder PR's families — train_stage_step_seconds,
    train_pipeline_bubble_fraction, flight_spans_dropped_total — register
    through the lazy factories, export with their tags, and obey the same
    staleness window as every other family (a torn-down pipeline's stage
    series must not linger on /metrics forever)."""
    os.environ["RAY_TPU_METRIC_STALENESS_S"] = "1.0"
    try:
        ray_tpu.init(num_cpus=2)
        from ray_tpu.util.metrics import flight_metrics, train_metrics

        tm = train_metrics()
        tm["train_stage_step_seconds"].observe(
            0.25, tags={"stage": "0", "replica": "1"})
        tm["train_pipeline_bubble_fraction"].set(
            0.27, tags={"source": "trainer"})
        flight_metrics()["flight_spans_dropped_total"].inc(
            7, tags={"component": "worker"})
        text = _scrape(
            lambda t: 'train_pipeline_bubble_fraction{source="trainer"} 0.27'
            in t and "train_stage_step_seconds_count" in t
            and 'flight_spans_dropped_total{component="worker"} 7' in t
        )
        assert "# TYPE train_stage_step_seconds histogram" in text
        assert ('train_stage_step_seconds_count{replica="1",stage="0"} 1'
                in text
                or 'train_stage_step_seconds_count{stage="0",replica="1"} 1'
                in text)
        assert "# TYPE train_pipeline_bubble_fraction gauge" in text
        assert "# TYPE flight_spans_dropped_total counter" in text
        time.sleep(1.5)
        text = _scrape(
            lambda t: "train_pipeline_bubble_fraction" not in t,
            deadline_s=5.0,
        )
        assert "train_pipeline_bubble_fraction" not in text
        assert "train_stage_step_seconds" not in text
    finally:
        os.environ.pop("RAY_TPU_METRIC_STALENESS_S", None)


def test_rllib_podracer_metrics_exported(cluster_rt):
    """Both podracer planes feed the rllib_* families (satellite of the
    podracer PR): env-step counters tagged by plane, the learner-step
    latency histogram, and the Sebulba actor->learner queue-depth gauge."""
    from ray_tpu.rllib import PPOConfig

    # Anakin: fused plane, driver-side metrics.
    algo = (
        PPOConfig()
        .environment("CartPole-v1")
        .training(train_batch_size=256, minibatch_size=128, num_epochs=1)
        .debugging(seed=3)
        .podracer("anakin", num_envs=16, rollout_len=16)
        .build()
    )
    try:
        algo.train()
    finally:
        algo.stop()

    # Sebulba: split plane — the counter/histogram/gauge records originate
    # in the LEARNER WORKER process and must still reach /metrics.
    algo = (
        PPOConfig()
        .environment("CartPole-v1")
        .training(train_batch_size=256, minibatch_size=128, num_epochs=1)
        .debugging(seed=3)
        .podracer("sebulba", num_actors=1, envs_per_actor=8, rollout_len=32)
        .build()
    )
    try:
        algo.train()
        # This batch shape (8 envs x 32 steps ~ 6KB) sits BELOW the store
        # inline threshold: the transport must keep frames in the RPC
        # descriptor, not burn arena names (the arena path is asserted at
        # 90KB frames in test_podracer_sebulba.py).
        stats = algo._podracer.transport_stats
        assert all(a["pub_inline"] >= 1 and a["pub_arena"] == 0
                   for a in stats["actors"])
        assert stats["learner"]["fetch_inline"] >= 1
        # Histogram deltas flush from the learner WORKER on a 0.25s cadence;
        # give the flusher one tick before stop() SIGKILLs the gang.
        time.sleep(0.6)
    finally:
        algo.stop()

    text = _scrape(
        lambda t: 'rllib_env_steps_total{plane="anakin"}' in t
        and 'rllib_env_steps_total{plane="sebulba"}' in t
        and 'rllib_learner_step_seconds_count{plane="sebulba"}' in t
    )
    assert "# TYPE rllib_env_steps_total counter" in text
    assert 'rllib_env_steps_total{plane="anakin"} 256' in text
    assert 'rllib_env_steps_total{plane="sebulba"} 256' in text
    assert "# TYPE rllib_learner_step_seconds histogram" in text
    assert 'rllib_learner_step_seconds_count{plane="anakin"} 1' in text
    assert 'rllib_learner_step_seconds_count{plane="sebulba"} 1' in text
    # The gauge exists only where a queue exists; after the iteration the
    # learner has drained it back to 0.
    assert "# TYPE rllib_actor_learner_queue_depth gauge" in text
    assert 'rllib_actor_learner_queue_depth{plane="sebulba"} 0' in text


def test_tail_logs_returns_worker_output(cluster_rt):
    backend = cluster_rt

    @ray_tpu.remote
    def chatty():
        print("HELLO-FROM-WORKER-xyz")
        return 1

    assert ray_tpu.get(chatty.remote()) == 1
    deadline = time.monotonic() + 10
    seen = ""
    while time.monotonic() < deadline:
        resp = backend._request({"type": "tail_logs", "cursors": {}})
        seen = "".join(c["data"] for c in resp["logs"].values())
        if "HELLO-FROM-WORKER-xyz" in seen:
            break
        time.sleep(0.3)
    assert "HELLO-FROM-WORKER-xyz" in seen


def _run_cli(*args):
    env = dict(os.environ)
    env["PYTHONPATH"] = "/root/repo" + os.pathsep + env.get("PYTHONPATH", "")
    info = _session_info()      # not whichever session `session_latest` names
    env["RAY_TPU_ADDRESS"] = info["address"]
    env["RAY_TPU_AUTH_TOKEN"] = info.get("auth_token", "")
    return subprocess.run(
        [sys.executable, "-m", "ray_tpu.scripts.cli", *args],
        capture_output=True, text=True, timeout=60, env=env, cwd="/root/repo",
    )


def test_cli_status_and_lists(cluster_rt):
    @ray_tpu.remote
    def noop():
        return 1

    ray_tpu.get(noop.remote())
    r = _run_cli("status")
    assert r.returncode == 0, r.stderr
    assert "Cluster:" in r.stdout and "Nodes:" in r.stdout and "CPU" in r.stdout
    r = _run_cli("list", "workers")
    assert r.returncode == 0, r.stderr
    assert "worker_id" in r.stdout
    r = _run_cli("list", "nodes")
    assert "node0" in r.stdout
    r = _run_cli("timeline", "--tail", "5")
    assert r.returncode == 0, r.stderr
    r = _run_cli("logs")
    assert r.returncode == 0, r.stderr
    r = _run_cli("trace")
    assert r.returncode == 0, r.stderr
    assert "trace_id" in r.stdout
    r = _run_cli("flight", "--wait", "0.1")
    assert r.returncode == 0, r.stderr
    assert "flight spans:" in r.stdout


def test_streamed_items_are_counted_not_narrated_and_spans_stay(cluster_rt):
    """A streamed call's items are objects, collected once their refs are
    gone: the controller COUNTS the collections (`state_summary`, both
    forms) and writes no `object_gc` event, so 300 streamed items leave the
    50 spans recorded before them where they were."""
    from ray_tpu.util import flight

    backend = cluster_rt
    t = flight.now_ns()
    for i in range(50):
        flight.record("probe.marker", t + i, t + i + 1000, lane="test", seq=i)
    assert flight.flush() >= 50
    before = backend._request({"type": "state_summary", "counts_only": True})

    @ray_tpu.remote
    class Producer:
        def gen(self, n):
            for i in range(n):
                yield bytes(64) + i.to_bytes(4, "big")

    p = Producer.remote()
    items = [ray_tpu.get(r) for r in p.gen.options(num_returns="streaming").remote(300)]
    assert len(items) == 300 and len(set(items)) == 300
    deadline = time.monotonic() + 30.0      # the collector's grace, then its sweep
    while time.monotonic() < deadline:
        now = backend._request({"type": "state_summary", "counts_only": True})
        if now["object_gc_collections"] - before["object_gc_collections"] >= 300:
            break
        time.sleep(0.2)
    full = backend.state_summary()
    assert full["object_gc_collections"] - before["object_gc_collections"] >= 300
    assert full["object_gc_bytes"] - before["object_gc_bytes"] >= 300 * 68
    events = ray_tpu.timeline()
    assert not [e for e in events if e.get("event") == "object_gc"]
    markers = [e for e in events if e.get("name") == "probe.marker"]
    assert sorted(e["args"]["seq"] for e in markers) == list(range(50))
    r = _run_cli("status")
    assert r.returncode == 0 and "collected" in r.stdout, r.stdout + r.stderr


def test_cli_timeline_writes_chrome_trace(cluster_rt, tmp_path):
    @ray_tpu.remote
    def noop():
        return 1

    ray_tpu.get(noop.remote())
    out = str(tmp_path / "tl.json")
    r = _run_cli("timeline", "-o", out)
    assert r.returncode == 0, r.stderr
    events = json.load(open(out))
    assert isinstance(events, list) and events
    # Perfetto-loadable chrome-trace events, not raw controller dicts.
    assert all("ph" in e for e in events)
    assert any(e["ph"] == "X" for e in events)


def test_tail_logs_from_remote_node():
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(initialize_head=True, head_node_args={"num_cpus": 1})
    cluster.add_node(num_cpus=2, resources={"r1": 1.0})
    ray_tpu.init(address=cluster.address)
    try:
        @ray_tpu.remote(resources={"r1": 1.0})
        def chatty():
            print("REMOTE-NODE-LOG-LINE")
            return 1

        assert ray_tpu.get(chatty.remote()) == 1
        backend = api._global_runtime().backend
        deadline = time.monotonic() + 10
        seen = ""
        while time.monotonic() < deadline:
            resp = backend._request({"type": "tail_logs", "cursors": {}})
            seen = "".join(c["data"] for c in resp["logs"].values())
            if "REMOTE-NODE-LOG-LINE" in seen:
                break
            time.sleep(0.3)
        assert "REMOTE-NODE-LOG-LINE" in seen
    finally:
        ray_tpu.shutdown()
        cluster.shutdown()


def test_controller_ha_metrics_exported():
    """Recovery observability (docs/CONTROL_PLANE_HA.md): the WAL-enabled
    controller exports controller_log_bytes / controller_log_fsync_seconds
    while running, and controller_recoveries_total + the
    controller_recovery_seconds histogram after a kill -9 restore."""
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(initialize_head=True, head_node_args={"num_cpus": 2})
    ray_tpu.init(address=cluster.address)
    try:
        @ray_tpu.remote(num_cpus=0)
        class A:
            def ping(self):
                return 1

        a = A.options(name="ha-metrics", lifetime="detached").remote()
        assert ray_tpu.get(a.ping.remote(), timeout=60) == 1
        text = _scrape(lambda t: "controller_log_bytes" in t)
        assert "# TYPE controller_log_bytes gauge" in text
        # The log has at least the boot + registration records fsynced.
        assert "controller_log_fsync_seconds_count" in text

        time.sleep(1.2)  # one checkpoint (compaction path exercised too)
        cluster.kill_head()
        cluster.restart_head()
        backend = api._global_runtime().backend
        end = time.monotonic() + 30
        while time.monotonic() < end:
            try:
                backend._request({"type": "state_summary"}, timeout=5)
                break
            except Exception:  # noqa: BLE001 — reconnecting
                time.sleep(0.25)
        text = _scrape(lambda t: "controller_recoveries_total 1" in t)
        assert "controller_recoveries_total 1" in text
        assert "# TYPE controller_recovery_seconds histogram" in text
        assert "controller_recovery_seconds_count 1" in text
        assert 'controller_recovery_seconds_bucket{le="+Inf"} 1' in text
    finally:
        ray_tpu.shutdown()
        cluster.shutdown()


def test_node_system_metrics_reported():
    """Per-node cpu/mem/disk samples surface in the nodes API and the
    Prometheus exposition (reference: `reporter_agent.py:277`)."""
    import time as _t
    import urllib.request

    import ray_tpu

    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=2)
    try:
        from ray_tpu.core import api

        b = api._global_runtime().backend
        deadline = _t.monotonic() + 30
        sys_metrics = {}
        while _t.monotonic() < deadline:
            nodes = b._request({"type": "nodes"})["nodes"]
            sys_metrics = next(
                (n.get("SystemMetrics") or {} for n in nodes
                 if n["NodeID"] == "node0"),
                {},
            )
            if sys_metrics.get("mem_total_bytes"):
                break
            _t.sleep(0.5)
        assert sys_metrics.get("mem_total_bytes", 0) > 0
        assert sys_metrics.get("disk_total_bytes", 0) > 0
        assert "cpu_percent" in sys_metrics

        info = b._request({"type": "cluster_info"}) if False else None
        import json
        import os

        metrics_url = _session_info()["metrics_url"]
        text = urllib.request.urlopen(metrics_url, timeout=10).read().decode()
        assert "ray_tpu_node_mem_used_bytes" in text
        assert 'ray_tpu_node_cpu_percent{node="node0"}' in text
    finally:
        ray_tpu.shutdown()
