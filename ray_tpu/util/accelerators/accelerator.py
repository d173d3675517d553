"""Accelerator manager plugin layer.

Reference analog: `python/ray/_private/accelerators/accelerator.py`
(`AcceleratorManager` ABC) with per-vendor implementations
(`tpu.py`, `nvidia_gpu.py`, ...) consulted at node start to autodetect
resources and at task launch to pin visible devices.

Here TPU is the first-class citizen (device-node detection, pod-type gang
resources); NVIDIA GPU detection exists for mixed CPU/GPU fleets; new
accelerators register via `register_accelerator_manager`.
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional


class AcceleratorManager:
    """One per accelerator family. All methods are static-like (managers are
    stateless singletons)."""

    # e.g. "TPU" / "GPU" — the resource key users request.
    resource_name: str = ""

    def get_current_node_num_accelerators(self) -> int:
        """How many devices of this family this node carries."""
        raise NotImplementedError

    def get_current_node_accelerator_type(self) -> Optional[str]:
        """e.g. 'v5litepod-16' or 'A100'; None if undetectable."""
        return None

    def get_visible_accelerator_ids_env_var(self) -> Optional[str]:
        """Env var used to pin a worker to specific devices."""
        return None

    def get_extra_node_resources(self) -> Dict[str, float]:
        """Additional custom resources this node should advertise (e.g. the
        TPU pod-head gang resource)."""
        return {}

    def validate_resource_request_quantity(self, quantity: float) -> None:
        if quantity < 0:
            raise ValueError(f"{self.resource_name} request must be >= 0")


class TPUAcceleratorManager(AcceleratorManager):
    resource_name = "TPU"

    def get_current_node_num_accelerators(self) -> int:
        from . import tpu

        return tpu.detect_num_chips()

    def get_current_node_accelerator_type(self) -> Optional[str]:
        from . import tpu

        return tpu.get_accelerator_type()

    def get_visible_accelerator_ids_env_var(self) -> Optional[str]:
        from . import tpu

        return tpu.TPU_VISIBLE_CHIPS_ENV

    def get_extra_node_resources(self) -> Dict[str, float]:
        """Pod head advertises `TPU-<type>-head: 1` so a multi-host slice
        gang can STRICT_SPREAD one bundle per host onto the pod (reference:
        `_private/accelerators/tpu.py:199,277-313`)."""
        from . import tpu

        accel = tpu.get_accelerator_type()
        if accel and tpu.get_worker_id() == 0:
            return {tpu.pod_resource_name(accel): 1.0}
        return {}

    def validate_resource_request_quantity(self, quantity: float) -> None:
        super().validate_resource_request_quantity(quantity)
        if 0 < quantity < 1 and (1 / quantity) % 1 != 0:
            raise ValueError(
                "fractional TPU requests must evenly divide one chip "
                f"(got {quantity})"
            )


class NvidiaGPUAcceleratorManager(AcceleratorManager):
    resource_name = "GPU"

    def get_current_node_num_accelerators(self) -> int:
        visible = os.environ.get("CUDA_VISIBLE_DEVICES")
        if visible is not None:
            # "-1" (and any negative id) is the standard hide-all marker.
            return len([
                c for c in visible.split(",")
                if c.strip() != "" and not c.strip().startswith("-")
            ])
        try:
            entries = os.listdir("/proc/driver/nvidia/gpus")
            return len(entries)
        except OSError:
            return 0

    def get_visible_accelerator_ids_env_var(self) -> Optional[str]:
        return "CUDA_VISIBLE_DEVICES"


_MANAGERS: Dict[str, AcceleratorManager] = {
    "TPU": TPUAcceleratorManager(),
    "GPU": NvidiaGPUAcceleratorManager(),
}


def register_accelerator_manager(manager: AcceleratorManager):
    if not manager.resource_name:
        raise ValueError("accelerator manager needs a resource_name")
    _MANAGERS[manager.resource_name] = manager


def get_all_accelerator_managers() -> List[AcceleratorManager]:
    return list(_MANAGERS.values())


def get_accelerator_manager_for_resource(
    resource_name: str,
) -> Optional[AcceleratorManager]:
    return _MANAGERS.get(resource_name)


def detect_node_accelerator_resources() -> Dict[str, float]:
    """Autodetected accelerator resources for this node (used by init when
    the user does not specify them)."""
    out: Dict[str, float] = {}
    for mgr in _MANAGERS.values():
        n = mgr.get_current_node_num_accelerators()
        if n > 0:
            out[mgr.resource_name] = float(n)
            out.update(mgr.get_extra_node_resources())
    return out
