"""ray.util extras: ActorPool, Queue, multiprocessing Pool, joblib backend.

Reference analogs: `python/ray/util/{actor_pool,queue,multiprocessing,joblib}`.
"""

import threading
import time

import pytest

import ray_tpu
from ray_tpu.util import ActorPool, Empty, Full, Queue

pytestmark = pytest.mark.cluster


# -------------------------------------------------------------- ActorPool
def test_actor_pool_map_ordered(cluster_runtime):
    @ray_tpu.remote
    class Worker:
        def work(self, x):
            time.sleep(0.05 * (x % 3))
            return x * 10

    pool = ActorPool([Worker.remote() for _ in range(3)])
    out = list(pool.map(lambda a, v: a.work.remote(v), range(8)))
    assert out == [x * 10 for x in range(8)]  # submission order preserved


def test_actor_pool_map_unordered(cluster_runtime):
    @ray_tpu.remote
    class Worker:
        def work(self, x):
            time.sleep(0.2 if x == 0 else 0.0)
            return x

    pool = ActorPool([Worker.remote() for _ in range(2)])
    out = list(pool.map_unordered(lambda a, v: a.work.remote(v), range(4)))
    assert sorted(out) == [0, 1, 2, 3]


def test_actor_pool_submit_get_next(cluster_runtime):
    @ray_tpu.remote
    class W:
        def f(self, x):
            return x + 1

    pool = ActorPool([W.remote()])
    pool.submit(lambda a, v: a.f.remote(v), 1)
    pool.submit(lambda a, v: a.f.remote(v), 2)
    assert pool.has_next()
    assert pool.get_next() == 2
    assert pool.get_next() == 3
    assert not pool.has_next()


# ------------------------------------------------------------------ Queue
def test_queue_fifo_roundtrip(cluster_runtime):
    q = Queue()
    for i in range(5):
        q.put(i)
    assert q.qsize() == 5 and not q.empty()
    assert [q.get() for _ in range(5)] == list(range(5))
    assert q.empty()


def test_queue_nowait_and_maxsize(cluster_runtime):
    q = Queue(maxsize=2)
    q.put_nowait("a")
    q.put_nowait("b")
    assert q.full()
    with pytest.raises(Full):
        q.put_nowait("c")
    assert q.get_nowait() == "a"
    with pytest.raises(Empty):
        Queue().get_nowait()


def test_queue_blocking_get_timeout(cluster_runtime):
    q = Queue()
    t0 = time.monotonic()
    with pytest.raises(Empty):
        q.get(timeout=0.3)
    assert time.monotonic() - t0 >= 0.25


def test_queue_cross_task_producer_consumer(cluster_runtime):
    q = Queue()

    @ray_tpu.remote
    def producer(q, n):
        for i in range(n):
            q.put(i)
        return n

    ref = producer.remote(q, 4)
    got = [q.get(timeout=10) for _ in range(4)]
    assert sorted(got) == [0, 1, 2, 3]
    assert ray_tpu.get(ref) == 4


# -------------------------------------------------- multiprocessing Pool
def test_mp_pool_map_and_starmap(cluster_runtime):
    from ray_tpu.util.multiprocessing import Pool

    with Pool() as p:
        assert p.map(lambda x: x * x, range(6)) == [0, 1, 4, 9, 16, 25]
        assert p.starmap(lambda a, b: a + b, [(1, 2), (3, 4)]) == [3, 7]
        assert p.apply(lambda a, b: a * b, (3, 4)) == 12
        r = p.apply_async(lambda: "async")
        assert r.get(timeout=30) == "async"
        assert sorted(p.imap_unordered(lambda x: -x, range(3))) == [-2, -1, 0]
    with pytest.raises(ValueError):
        p.map(lambda x: x, [1])  # closed


# ------------------------------------------------------------------ joblib
def test_joblib_backend(cluster_runtime):
    import joblib

    from ray_tpu.util.joblib import register_ray_tpu

    register_ray_tpu()
    with joblib.parallel_backend("ray_tpu", n_jobs=4):
        out = joblib.Parallel()(joblib.delayed(lambda x: x**2)(i) for i in range(8))
    assert out == [i**2 for i in range(8)]


# ---------------------------------------------------- system metrics latch
def test_tpu_hbm_used_pct_cooldown_not_permanent(monkeypatch):
    """A slow/failed TPU stats sample must pause sampling for a cooldown and
    then RETRY — the r5 permanent latch killed the metric for the process
    lifetime on one transient hiccup (ADVICE r5 #2)."""
    import time as _time

    from ray_tpu.util import system_metrics as sm

    monkeypatch.setattr(sm, "_tpu_bad_streak", 0)
    monkeypatch.setattr(sm, "_tpu_retry_at", 0.0)

    sm._tpu_sample_failed()
    first_cooldown = sm._tpu_retry_at - _time.monotonic()
    assert 0 < first_cooldown <= sm._TPU_COOLDOWN_S + 1
    # In cooldown: short-circuits to 0.0 without touching jax.
    assert sm.tpu_hbm_used_pct() == 0.0

    # Consecutive failures back off exponentially, capped.
    sm._tpu_sample_failed()
    second_cooldown = sm._tpu_retry_at - _time.monotonic()
    assert second_cooldown > first_cooldown
    for _ in range(10):
        sm._tpu_sample_failed()
    assert sm._tpu_retry_at - _time.monotonic() <= sm._TPU_COOLDOWN_MAX_S + 1

    # After the cooldown expires the sampler RETRIES (the regression): a
    # failing stats path increments the streak again instead of staying off.
    import jax

    jax.devices()  # ensure a backend exists so the probe reaches devices()
    monkeypatch.setattr(sm, "_tpu_retry_at", 0.0)
    streak_before = sm._tpu_bad_streak

    def boom():
        raise RuntimeError("transient stats failure")

    monkeypatch.setattr(jax, "devices", boom)
    assert sm.tpu_hbm_used_pct() == 0.0
    assert sm._tpu_bad_streak == streak_before + 1, "sampler did not retry"

    # And a healthy (fast, non-TPU) sample resets nothing harmful: with the
    # real devices() on CPU the probe reports 0.0 without re-latching.
    monkeypatch.undo()
