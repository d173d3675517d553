"""Remote-driver (client) mode + accelerator plugin layer tests.

Reference analogs: `python/ray/util/client` (Ray Client) and
`python/ray/_private/accelerators/` (AcceleratorManager plugins).
"""

import os
import subprocess
import sys

import numpy as np
import pytest

import ray_tpu
from ray_tpu.cluster_utils import Cluster
from ray_tpu.util.accelerators import (
    AcceleratorManager,
    NvidiaGPUAcceleratorManager,
    TPUAcceleratorManager,
    detect_node_accelerator_resources,
    get_accelerator_manager_for_resource,
    register_accelerator_manager,
)

pytestmark = pytest.mark.cluster


# ------------------------------------------------------------- client mode
@pytest.fixture
def standalone_cluster():
    cluster = Cluster(initialize_head=True, head_node_args={"num_cpus": 2})
    yield cluster
    cluster.shutdown()


def test_client_mode_tasks_and_objects(standalone_cluster):
    """A ray:// driver runs tasks and moves objects purely over RPC."""
    ray_tpu.init(address=f"ray://{standalone_cluster.address}")
    try:
        backend = ray_tpu.core.api._global_runtime().backend
        assert backend.remote_client

        @ray_tpu.remote
        def double(x):
            return x * 2

        assert ray_tpu.get(double.remote(21)) == 42

        # Large array: put ships inline over RPC; get fetches the packed
        # frame from the controller (no shm attach either way).
        arr = np.arange(200_000, dtype=np.float32)  # ~800 KB > inline cap
        ref = ray_tpu.put(arr)
        np.testing.assert_array_equal(ray_tpu.get(ref), arr)

        # Worker-produced big object read back through the client path.
        @ray_tpu.remote
        def make_big():
            return np.ones((300, 1000), np.float64)

        out = ray_tpu.get(make_big.remote())
        assert out.shape == (300, 1000) and float(out.sum()) == 300_000.0
    finally:
        ray_tpu.shutdown()


def test_client_mode_from_separate_process(standalone_cluster):
    """Full isolation: a different interpreter acts as the remote driver."""
    code = f"""
import ray_tpu
ray_tpu.init(address="ray://{standalone_cluster.address}")

@ray_tpu.remote
def add(a, b):
    return a + b

assert ray_tpu.get(add.remote(2, 3)) == 5

@ray_tpu.remote
class Counter:
    def __init__(self): self.n = 0
    def bump(self): self.n += 1; return self.n

c = Counter.remote()
assert ray_tpu.get([c.bump.remote() for _ in range(3)]) == [1, 2, 3]
print("CLIENT_OK")
"""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        timeout=60,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert "CLIENT_OK" in out.stdout, out.stderr[-2000:]


# ------------------------------------------------------ accelerator layer
def test_manager_registry():
    assert isinstance(get_accelerator_manager_for_resource("TPU"), TPUAcceleratorManager)
    assert isinstance(get_accelerator_manager_for_resource("GPU"), NvidiaGPUAcceleratorManager)
    assert get_accelerator_manager_for_resource("NPU") is None


def test_tpu_manager_detection(monkeypatch, tmp_path):
    """The node's TPU resource is the count of accelerator device nodes."""
    from ray_tpu.util.accelerators import tpu

    monkeypatch.delenv("TPU_VISIBLE_CHIPS", raising=False)
    monkeypatch.setattr(tpu, "_DEV_ROOT", str(tmp_path))
    for i in range(4):
        (tmp_path / f"accel{i}").touch()
    tpu.detect_num_chips.cache_clear()
    mgr = TPUAcceleratorManager()
    assert mgr.get_current_node_num_accelerators() == 4
    res = detect_node_accelerator_resources()
    assert res.get("TPU") == 4.0
    tpu.detect_num_chips.cache_clear()


def test_tpu_pod_head_resource(monkeypatch):
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "0,1,2,3")
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-16")
    monkeypatch.setenv("TPU_WORKER_ID", "0")
    from ray_tpu.util.accelerators import tpu

    tpu.detect_num_chips.cache_clear()
    res = detect_node_accelerator_resources()
    assert res.get("TPU-v5litepod-16-head") == 1.0
    # Non-head workers don't advertise the gang resource.
    monkeypatch.setenv("TPU_WORKER_ID", "1")
    res = detect_node_accelerator_resources()
    assert "TPU-v5litepod-16-head" not in res
    tpu.detect_num_chips.cache_clear()


def test_fractional_tpu_validation():
    mgr = TPUAcceleratorManager()
    mgr.validate_resource_request_quantity(0.5)  # ok: divides a chip
    mgr.validate_resource_request_quantity(2.0)
    with pytest.raises(ValueError):
        mgr.validate_resource_request_quantity(0.3)


def test_custom_manager_registration():
    class NPUManager(AcceleratorManager):
        resource_name = "NPU"

        def get_current_node_num_accelerators(self):
            return 2

    register_accelerator_manager(NPUManager())
    try:
        assert detect_node_accelerator_resources().get("NPU") == 2.0
    finally:
        from ray_tpu.util.accelerators import accelerator

        accelerator._MANAGERS.pop("NPU", None)
