"""Continuous-batching LLM inference engine (vLLM-style iteration-level
scheduling + paged KV cache) — see `ray_tpu/serve/README.md`.

Layering:
  * `kv_manager` — paged KV block map: free list, per-sequence block
    tables, admission-by-budget, hot-prefix digest (no JAX imports).
  * `scheduler` — iteration-level working-set former: admit / retire /
    preempt every decode step; shape buckets for XLA; speculative draft
    funding inside the step budget (no JAX imports).
  * `spec` — n-gram prompt-lookup draft proposer for speculative decoding
    (no JAX imports).
  * `engine` — the driver loop over `models/gpt.py`'s `prefill_paged` /
    `decode_step_paged` / `verify_step_paged`, streaming tokens per
    iteration.
  * `deployment` — `LLMDeployment`, the engine wired through the Serve
    controller/router/streaming planes (`fleet_state` telemetry feeds the
    fleet routing/autoscaling planes in `serve/fleet/`).

`InferenceEngine` / `LLMDeployment` import JAX and the model stack, so they
resolve lazily; the schedulers stay importable in lightweight contexts.
"""

from .kv_manager import KVBlockManager, KVCacheExhausted, KVStats
from .scheduler import PrefillChunk, Scheduler, SchedulerOutput, Sequence

__all__ = [
    "KVBlockManager",
    "KVCacheExhausted",
    "KVStats",
    "PrefillChunk",
    "Scheduler",
    "SchedulerOutput",
    "Sequence",
    "EngineOptions",
    "InferenceEngine",
    "RequestOutput",
    "LLMDeployment",
    "LLMReplica",
]

_LAZY = {
    "EngineOptions": "engine",
    "InferenceEngine": "engine",
    "RequestOutput": "engine",
    "LLMDeployment": "deployment",
    "LLMReplica": "deployment",
}


def __getattr__(name):
    mod = _LAZY.get(name)
    if mod is None:
        raise AttributeError(name)
    import importlib

    return getattr(importlib.import_module(f".{mod}", __name__), name)
