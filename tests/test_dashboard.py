"""Dashboard tests — JSON API + HTML page against a live cluster.

Reference analog: `dashboard/tests/` (aiohttp head + state aggregation).
"""

import json
import os
import time
import urllib.request

import pytest

import ray_tpu

pytestmark = pytest.mark.cluster


def _dashboard_url():
    from ray_tpu.core import api

    # this test's own session: under xdist `session_latest` may be another's
    info_path = os.path.join(api._global_runtime().backend.session_dir, "address.json")
    with open(info_path) as f:
        return json.load(f)["dashboard_url"]


def _get(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.status, resp.headers.get("Content-Type", ""), resp.read()


@pytest.fixture
def dash(cluster_runtime):
    yield _dashboard_url()


def test_index_page(dash):
    status, ctype, body = _get(dash + "/")
    assert status == 200 and "text/html" in ctype
    assert b"ray_tpu dashboard" in body


def test_cluster_api(dash):
    status, ctype, body = _get(dash + "/api/cluster")
    assert status == 200 and "json" in ctype
    data = json.loads(body)
    assert data["nodes_alive"] >= 1
    assert "CPU" in json.dumps(data["resources"])
    assert data["summary"]["num_workers"] >= 0


def test_live_state_visible(dash):
    @ray_tpu.remote
    class Sleeper:
        def ping(self):
            return "pong"

    a = Sleeper.options(name="dash_probe").remote()
    assert ray_tpu.get(a.ping.remote()) == "pong"

    data = json.loads(_get(dash + "/api/actors")[2])
    names = [x["name"] for x in data["actors"]]
    assert "dash_probe" in names

    data = json.loads(_get(dash + "/api/workers")[2])
    assert len(data["workers"]) >= 1

    data = json.loads(_get(dash + "/api/nodes")[2])
    assert any(n["Alive"] for n in data["nodes"])

    data = json.loads(_get(dash + "/api/events?limit=50")[2])
    assert isinstance(data["events"], list) and data["events"]


def test_tasks_api_shows_running(dash):
    # Deadline-based poll, generous on cold runs: the first scrape races
    # worker spawn (~2s cold interpreter boot without the forkserver), so a
    # fixed 20x0.1s loop flaked when the task had not even dispatched yet.
    # The task sleeps long enough that a poll tick always lands inside its
    # RUNNING window once dispatched.
    @ray_tpu.remote
    def slow():
        time.sleep(3.0)
        return 1

    ref = slow.remote()
    seen_running = False
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline:
        data = json.loads(_get(dash + "/api/tasks")[2])
        if any(t["state"] == "RUNNING" and t["name"] == "slow" for t in data["tasks"]):
            seen_running = True
            break
        time.sleep(0.1)
    assert seen_running
    assert ray_tpu.get(ref) == 1


def test_traces_api(dash):
    @ray_tpu.remote
    def traced_child(x):
        return x + 1

    @ray_tpu.remote
    def traced_root():
        return ray_tpu.get(traced_child.remote(1))

    assert ray_tpu.get(traced_root.remote()) == 2

    rows = []
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        rows = json.loads(_get(dash + "/api/traces")[2])["traces"]
        if any(r["name"] == "traced_root" for r in rows):
            break
        time.sleep(0.2)
    row = next(r for r in rows if r["name"] == "traced_root")
    assert row["n_tasks"] >= 2  # root + child under one trace

    detail = json.loads(
        _get(dash + f"/api/traces?trace_id={row['trace_id']}")[2]
    )
    assert detail["trace_id"] == row["trace_id"]
    names = {t["name"] for t in detail["tasks"]}
    assert "traced_root" in names
    kids = {c["name"] for t in detail["tasks"] for c in t["children"]}
    assert "traced_child" in kids

    import urllib.error

    with pytest.raises(urllib.error.HTTPError) as ei:
        _get(dash + "/api/traces?trace_id=nope")
    assert ei.value.code == 404


def test_unknown_api_404(dash):
    import urllib.error

    with pytest.raises(urllib.error.HTTPError) as ei:
        _get(dash + "/api/nope")
    assert ei.value.code == 404
