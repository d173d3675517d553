"""What has to hold for the program to meet the chip as itself: chips are
counted without attaching to them, a TPU worker is pinned to the TPU and to
its own chips, the compile cache can be placed from outside, and nothing
answers to a platform or a device it does not know. CPU-only and fast — the
run on the chip itself is `chip_smoke.py`."""

import os
import subprocess
import sys

import pytest

from ray_tpu.util.accelerators import tpu

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def fake_dev(tmp_path, monkeypatch):
    """An empty /dev stand-in. The host's TPU variables are hidden, and what
    the test (or `claim_chips`) sets is taken back afterwards."""
    names = (tpu.TPU_VISIBLE_CHIPS_ENV, "TPU_CHIPS_PER_HOST_BOUNDS",
             "TPU_HOST_BOUNDS", "TPU_ACCELERATOR_TYPE")
    saved = {name: os.environ.pop(name, None) for name in names}
    monkeypatch.setattr(tpu, "_DEV_ROOT", str(tmp_path))
    monkeypatch.setattr(tpu, "_claim", None)
    tpu.detect_num_chips.cache_clear()
    yield tmp_path
    for name, old in saved.items():
        os.environ.pop(name, None)
        if old is not None:
            os.environ[name] = old
    tpu.detect_num_chips.cache_clear()


def test_chip_count_from_device_nodes(fake_dev):
    assert tpu.detect_num_chips() == 0
    (fake_dev / "vfio").mkdir()
    for name in ("vfio", "0", "1"):  # /dev/vfio/vfio is the container node
        (fake_dev / "vfio" / name).touch()
    tpu.detect_num_chips.cache_clear()
    assert tpu.detect_num_chips() == 2
    for i in range(4):  # the accel driver's nodes win over vfio groups
        (fake_dev / f"accel{i}").touch()
    tpu.detect_num_chips.cache_clear()
    assert tpu.detect_num_chips() == 4
    # A pod-type marker is not a chip: v5litepod-4 is set on 1-chip machines.
    os.environ["TPU_ACCELERATOR_TYPE"] = "v5litepod-8"
    tpu.detect_num_chips.cache_clear()
    assert tpu.detect_num_chips() == 4
    os.environ[tpu.TPU_VISIBLE_CHIPS_ENV] = "2"
    tpu.detect_num_chips.cache_clear()
    assert tpu.detect_num_chips() == 1


def test_spawn_env_pins_the_platform():
    env = tpu.worker_spawn_env({"JAX_PLATFORMS": "cpu"}, tpu=True)
    assert env["JAX_PLATFORMS"] == "tpu" and env["RAY_TPU_WORKER_TPU"] == "1"
    assert env[tpu.COMPILE_CACHE_ENV] == os.path.join(REPO, ".jax_cache")
    # every program is kept, not only those that took a second to compile;
    # a threshold given from outside is left alone
    assert env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] == "0"
    given = {"JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "2.5"}
    assert tpu.worker_spawn_env(given, tpu=True)[
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] == "2.5"
    for inherited in ({}, {"JAX_PLATFORMS": "tpu"}, {"JAX_PLATFORMS": "tpu,cpu"}):
        env = tpu.worker_spawn_env(dict(inherited), tpu=False)
        assert env["JAX_PLATFORMS"] == "cpu" and env["RAY_TPU_WORKER_TPU"] == "0"
    assert tpu.worker_spawn_env({"JAX_PLATFORMS": "cuda"}, tpu=False)[
        "JAX_PLATFORMS"] == "cuda"


def test_compile_cache_placement(tmp_path):
    given = {tpu.COMPILE_CACHE_ENV: "/somewhere/else"}
    assert tpu.place_compile_cache(given) == "/somewhere/else"
    assert given == {tpu.COMPILE_CACHE_ENV: "/somewhere/else"}
    env = {k: v for k, v in os.environ.items() if k != tpu.COMPILE_CACHE_ENV}
    env["PYTHONPATH"] = REPO
    code = ("from ray_tpu.util.accelerators.tpu import place_compile_cache;"
            "print(place_compile_cache())")
    procs = [
        subprocess.Popen([sys.executable, "-c", code], env=env, cwd=cwd,
                         stdout=subprocess.PIPE, text=True)
        for cwd in (REPO, str(tmp_path))
    ]
    outs = [p.communicate(timeout=60)[0].strip() for p in procs]
    assert outs == [os.path.join(REPO, ".jax_cache")] * 2


def test_claim_chips_pins_a_subset(fake_dev, monkeypatch):
    for i in range(4):
        (fake_dev / f"accel{i}").touch()
    lock_dir = fake_dev / "session"
    lock_dir.mkdir()
    assert tpu.claim_chips(1, str(lock_dir)) == [0]
    assert os.environ[tpu.TPU_VISIBLE_CHIPS_ENV] == "0"
    assert os.environ["TPU_CHIPS_PER_HOST_BOUNDS"] == "1,1,1"
    assert tpu.claim_chips(1, str(lock_dir)) == [0]  # idempotent
    with pytest.raises(RuntimeError, match="cannot re-attach"):
        tpu.claim_chips(2, str(lock_dir))
    # What another worker of the same runtime would see (flock is held per
    # open file, so forgetting the claim while keeping it open stands in).
    first = tpu._claim
    monkeypatch.setattr(tpu, "_claim", None)
    assert tpu.claim_chips(2, str(lock_dir)) == [1, 2]
    assert os.environ[tpu.TPU_VISIBLE_CHIPS_ENV] == "1,2"
    second = tpu._claim
    monkeypatch.setattr(tpu, "_claim", None)
    with pytest.raises(RuntimeError, match="only 1 of this host's 4 are free"):
        tpu.claim_chips(4, str(lock_dir))
    for _, locks in (first, second):
        for f in locks:
            f.close()
    del os.environ[tpu.TPU_VISIBLE_CHIPS_ENV]
    assert tpu.claim_chips(4, str(lock_dir)) == [0, 1, 2, 3]
    assert tpu.TPU_VISIBLE_CHIPS_ENV not in os.environ  # whole host: defaults
    for f in tpu._claim[1]:
        f.close()


def test_unknown_device_kind_has_no_peak():
    from benchmarks.peaks import peak

    assert peak("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(ValueError, match="TPU v9"):
        peak("TPU v9")


def test_on_tpu_is_exact(monkeypatch):
    import jax

    from ray_tpu.ops import attention

    # Exact: no other platform's name counts as the TPU.
    for backend, want in (("tpu", True), ("tpu-proxy", False), ("cpu", False)):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert attention._on_tpu() is want


@pytest.mark.parametrize("busy_opens, waited", [(0, False), (3, True)])
def test_claim_waits_for_a_vfio_node_its_last_holder_is_still_letting_go(
        fake_dev, monkeypatch, caplog, busy_opens, waited):
    """A vfio group takes one opener at a time and its last holder can be gone
    from /proc before the kernel lets the node go: the claim probes each
    claimed node until it opens, so libtpu's first device query does not meet
    "Device or resource busy"; anything but EBUSY is libtpu's to report."""
    import errno

    (fake_dev / "vfio").mkdir()
    for name in ("vfio", "0", "1"):
        (fake_dev / "vfio" / name).touch()
    lock_dir = fake_dev / "session"
    lock_dir.mkdir()
    real_open, calls, naps = os.open, [], []

    def flaky_open(path, flags, *a):
        if os.sep + "vfio" + os.sep in str(path):
            calls.append(path)
            if len(calls) <= busy_opens:
                raise OSError(errno.EBUSY, "Device or resource busy")
        return real_open(path, flags, *a)

    monkeypatch.setattr(os, "open", flaky_open)
    monkeypatch.setattr("time.sleep", naps.append)
    assert tpu.claim_chips(2, str(lock_dir)) == [0, 1]
    assert len(calls) == 2 + busy_opens and bool(naps) == waited
    assert any("vfio nodes busy at 3 opens" in r.message for r in caplog.records) == waited
    for f in tpu._claim[1]:
        f.close()
    # a node that cannot be opened for another reason does not hold the claim up
    monkeypatch.setattr(tpu, "_claim", None)
    monkeypatch.setattr(os, "open", lambda path, flags, *a: (_ for _ in ()).throw(
        OSError(errno.EACCES, "Permission denied")) if "vfio" in str(path)
        else real_open(path, flags, *a))
    assert tpu.claim_chips(2, str(lock_dir)) == [0, 1]
    for f in tpu._claim[1]:
        f.close()
