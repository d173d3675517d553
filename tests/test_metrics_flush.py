"""`util.metrics`: a record is a local accumulate, the process's flusher
ships what was written every 0.25 s (no cluster: `metric_sink` is a runtime
whose backend notes the messages it is handed, and their thread).

Holding `_FLUSHER._flush_lock` around a group of records makes them one
interval whatever the flusher thread's phase: no flush can fall between
them — and a record that waited on that lock would deadlock right there.
"""

import sys
import threading
import time

import pytest

import ray_tpu
from ray_tpu.util import metrics
from ray_tpu.util.metrics import Counter, Gauge, Histogram


def _one_interval():
    return metrics._FLUSHER._flush_lock


def test_counter_increments_of_one_interval_arrive_as_their_sum(metric_sink):
    with _one_interval():
        Counter("mf_events", "events seen").inc(3)     # temporary instances:
        Counter("mf_events", "events seen").inc(2)     # the table is by series
        c = Counter("mf_events", "events seen")
        c.inc(4, tags={"route": "a"})
        c.inc(1, tags={"route": "b"})
        c.inc(6, tags={"route": "a"})
    metrics.flush()
    assert [m[3] for m in metric_sink.series("mf_events")] == [5.0]
    assert [m[3] for m in metric_sink.series("mf_events", route="a")] == [10.0]
    assert [m[3] for m in metric_sink.series("mf_events", route="b")] == [1.0]
    (msg,) = metric_sink.series("mf_events")
    assert msg[2] == "counter" and msg[5] == {"help": "events seen"}


def test_gauge_of_one_interval_arrives_as_its_last_value(metric_sink):
    with _one_interval():
        Gauge("mf_depth").set(7.5, tags={"route": "a"})
        Gauge("mf_depth").set(1.0, tags={"route": "b"})
        Gauge("mf_depth").set(2.5, tags={"route": "a"})
        g = Gauge("mf_depth").set_default_tags({"replica": "r0"})
        g.set(9.0)
        g.set(0.0, tags={"route": 3})       # tag values ship as strings
    metrics.flush()
    assert [m[3] for m in metric_sink.series("mf_depth", route="a")] == [2.5]
    assert [m[3] for m in metric_sink.series("mf_depth", route="b")] == [1.0]
    assert [m[3] for m in metric_sink.series("mf_depth", replica="r0")] == [9.0]
    assert [m[3] for m in metric_sink.series(
        "mf_depth", replica="r0", route=3)] == [0.0]
    assert {m[2] for m in metric_sink.sent} == {"gauge"}


def test_histogram_rides_the_same_table(metric_sink):
    with _one_interval():
        h = Histogram("mf_lat_s", "latency", boundaries=[0.1, 1.0])
        for v in (0.05, 0.5, 0.6, 5.0):
            h.observe(v)
        Histogram("mf_lat_s", "latency", boundaries=[0.1, 1.0]).observe(0.1)
        # Another grid under the same name restarts the delta, as the
        # controller restarts the series: never an index past the buckets.
        Histogram("mf_grid_s", boundaries=[1.0]).observe(0.5)
        Histogram("mf_grid_s", boundaries=[1.0, 2.0, 3.0]).observe(2.5)
    metrics.flush()
    (msg,) = metric_sink.series("mf_lat_s")
    assert msg[2] == "histogram" and msg[3] == 0.0
    assert list(msg[5]["boundaries"]) == [0.1, 1.0]
    assert msg[5]["buckets"] == [2, 2, 1]           # le semantics: 0.1 is in
    assert msg[5]["count"] == 5 and msg[5]["sum"] == pytest.approx(6.25)
    assert msg[5]["help"] == "latency"
    (grid,) = metric_sink.series("mf_grid_s")
    assert grid[5]["buckets"] == [0, 0, 1, 0] and grid[5]["count"] == 1


def test_series_not_written_since_the_last_flush_is_not_sent_again(metric_sink):
    """Or a gauge set once would never age out under the controller's
    staleness sweep."""
    with _one_interval():
        Gauge("mf_once").set(4.2)
        Counter("mf_twice").inc(1)
    metrics.flush()
    n = len(metric_sink.sent)
    metrics.flush()
    metrics.flush()
    assert len(metric_sink.sent) == n
    Counter("mf_twice").inc(2)
    metrics.flush()
    assert [m[1:4] for m in metric_sink.sent[n:]] == [("mf_twice", "counter", 2.0)]
    assert len(metric_sink.series("mf_once")) == 1


def test_prune_series_drops_pending_entries_before_the_prune(metric_sink):
    with _one_interval():
        Gauge("mf_q").set(3.0, tags={"replica": "r1", "app": "a"})
        Counter("mf_tok").inc(8, tags={"replica": "r1"})
        Gauge("mf_q").set(5.0, tags={"replica": "r2", "app": "a"})
        # prune_series waits for the interval to end (its prune must land
        # behind a flush in flight), so it runs beside this thread.
        t = threading.Thread(
            target=metrics.prune_series, args=({"replica": "r1"},))
        t.start()
    t.join(10)
    assert not t.is_alive()
    metrics.flush()
    assert metric_sink.pruned == [{"replica": "r1"}]
    assert not metric_sink.series("mf_q", replica="r1", app="a")
    assert not metric_sink.series("mf_tok", replica="r1")
    assert [m[3] for m in metric_sink.series(
        "mf_q", replica="r2", app="a")] == [5.0]


@pytest.mark.parametrize("how", ["flush", "shutdown"])
def test_pending_ships_at(metric_sink, how):
    """`metrics.flush()` is one synchronous flush: when it returns, what was
    recorded before it is with the backend. `ray_tpu.shutdown()` makes one
    before the runtime goes, so a driver that counts and exits loses
    nothing."""
    Counter("mf_bye").inc(5)
    Gauge("mf_last").set(1.5)
    {"flush": metrics.flush, "shutdown": ray_tpu.shutdown}[how]()
    assert {m[1]: m[3] for m in metric_sink.sent} == {"mf_bye": 5.0, "mf_last": 1.5}
    assert metric_sink.down == (how == "shutdown")
    assert ray_tpu.is_initialized() == (how == "flush")


def test_process_without_a_runtime_keeps_nothing(request):
    """A record without a runtime boots none and is DROPPED, not kept: a
    runtime this process starts later receives none of it."""
    ray_tpu.shutdown()
    r0 = metrics.records_total
    Counter("mf_orphan_total").inc(4)
    Gauge("mf_orphan").set(2.0)
    Histogram("mf_orphan_s").observe(0.3)
    assert not ray_tpu.is_initialized()
    assert metrics.records_total - r0 == 3      # counted, not kept
    assert not [k for k in metrics._FLUSHER._pending if k[0].startswith("mf_orphan")]
    sink = request.getfixturevalue("metric_sink")   # the runtime comes later
    Counter("mf_after_total").inc(1)
    metrics.flush()
    assert {m[1] for m in sink.sent} == {"mf_after_total"}


def test_recording_thread_never_sends_and_totals_count(metric_sink):
    """Records of this thread leave on `metrics-flusher`; the module's two
    totals count every record and every message."""
    r0, s0 = metrics.records_total, metrics.sends_total
    g = Gauge("mf_busy")
    c = Counter("mf_busy_total")

    def seen():
        return (sum(m[3] for m in metric_sink.series("mf_busy_total")) == 200
                and [m[3] for m in metric_sink.series("mf_busy")][-1:] == [199.0])

    for i in range(200):
        g.set(float(i))
        c.inc(1)
    for _ in range(100):                     # at most 10 s; two ticks do
        if seen():
            break
        time.sleep(0.1)
    assert seen(), metric_sink.sent
    assert {m[0] for m in metric_sink.sent} == {"metrics-flusher"}
    assert metrics.records_total - r0 == 400
    assert metrics.sends_total - s0 == len(metric_sink.sent) < 40
    assert type(metrics.records_total) is int and type(metrics.sends_total) is int


def test_no_increment_is_lost_between_recorders_and_flushes(metric_sink):
    """More recording threads than cores and a thread that flushes as fast
    as it can, under a short switch interval: the sums that arrive add up to
    what was counted, and each gauge ends on its thread's last value."""
    threads, per = 16, 2000
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    stop = threading.Event()

    def count(i):
        c, g = Counter("mf_stress_total"), Gauge("mf_stress")
        for n in range(per):
            c.inc(1, tags={"lane": i % 4})
            g.set(n, tags={"thread": i})

    def flush():
        while not stop.is_set():
            metrics.flush()

    try:
        ts = [threading.Thread(target=count, args=(i,)) for i in range(threads)]
        fl = threading.Thread(target=flush)
        for t in ts + [fl]:
            t.start()
        for t in ts:
            t.join(60)
        stop.set()
        fl.join(60)
        assert not any(t.is_alive() for t in ts + [fl])
    finally:
        stop.set()
        sys.setswitchinterval(old)
    metrics.flush()
    total = sum(m[3] for m in metric_sink.sent if m[1] == "mf_stress_total")
    assert total == threads * per
    for i in range(threads):
        assert metric_sink.series("mf_stress", thread=i)[-1][3] == per - 1
