"""Elastic training bench: recovery MTTR + async checkpoint save overlap.

Two measurements (ISSUE 4 satellite; written to `--out`):

  * recovery — boot the multiprocess cluster, run a 2-worker elastic gang
    with per-step collectives, SIGKILL one member after the gang has
    committed a few checkpoints, and measure MTTR: the wall seconds from
    the kill to the re-formed gang's first completed post-restore step
    (detection + mesh abort + backoff + restart + restore). Also reports
    the supervisor's own death→reformed-gang recovery time.
  * ckpt_overlap — AsyncShardWriter on a multi-MB shard: save() block
    time (what the training step pays) vs background write time (what a
    synchronous save would have stalled), per save and aggregated.

Run (CPU):
    JAX_PLATFORMS=cpu python scripts/bench_elastic.py --out BENCH_ELASTIC_r01.json
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def bench_ckpt_overlap(shard_mb: float, saves: int) -> dict:
    import numpy as np

    from ray_tpu.train.elastic import AsyncShardWriter, ElasticState

    root = tempfile.mkdtemp(prefix="rtpu-bench-elastic-")
    w = AsyncShardWriter(root, 0, 1, gen="bench")
    n = int(shard_mb * (1 << 20) / 8)
    tree = {"w": np.random.default_rng(0).standard_normal(n)}
    blocks, writes = [], []
    for step in range(1, saves + 1):
        t0 = time.monotonic()
        w.save(step, tree, ElasticState(step=step))
        blocks.append(time.monotonic() - t0)
        assert w.flush(timeout=120.0), "writer stalled"
        writes.append(w.last_write_s)
    w.close()
    return {
        "shard_mb": shard_mb,
        "saves": saves,
        "save_block_s": {
            "mean": sum(blocks) / len(blocks),
            "max": max(blocks),
        },
        "bg_write_s": {
            "mean": sum(writes) / len(writes),
            "max": max(writes),
        },
        # The step pays block; a synchronous save would pay block + write.
        "overlap_fraction": 1.0
        - (sum(blocks) / max(sum(blocks) + sum(writes), 1e-9)),
    }


def bench_recovery(total_steps: int, kill_after_step: int) -> dict:
    import ray_tpu
    from ray_tpu.core import api
    from ray_tpu.train import FailureConfig, RunConfig, ScalingConfig
    from ray_tpu.train.backend_executor import BackendExecutor
    from ray_tpu.train.data_parallel_trainer import CollectiveBackend
    from ray_tpu.train.elastic import ShardedCheckpoint

    def _gang_loop(config):
        import numpy as _np

        from ray_tpu import collective as _coll
        from ray_tpu import train as _train
        from ray_tpu.train import elastic as _elastic

        sess = _elastic.elastic_session()
        tree = sess.restore()
        x = tree["x"] if tree is not None else _np.zeros(2)
        for step in range(sess.state.step, config["total_steps"]):
            g = _coll.allreduce(
                _np.full(2, float(step + 1)),
                group_name=config["collective_group"],
            )
            x = x + 0.1 * g
            _train.report({"step": step, "x0": float(x[0])})
            sess.save(step + 1, {"x": x})
        sess.flush()

    storage = tempfile.mkdtemp(prefix="rtpu-bench-recovery-")
    ray_tpu.init(num_cpus=4)
    try:
        backend = CollectiveBackend()
        run_cfg = RunConfig(
            storage_path=storage,
            failure_config=FailureConfig(max_failures=2, backoff_base_s=0.05),
        )
        ex = BackendExecutor(
            backend, ScalingConfig(num_workers=2), run_cfg,
            experiment_name="bench_elastic",
        )
        ex.start()
        victim_hex = ex.worker_group.actor_ids()[1]
        elastic_root = os.path.join(
            run_cfg.resolve_storage(), "elastic", ex.elastic_run_ns
        )
        marks = {}

        def killer():
            deadline = time.monotonic() + 180
            while time.monotonic() < deadline:
                found = ShardedCheckpoint.latest_committed(elastic_root)
                if found is not None and found[0] >= kill_after_step:
                    break
                time.sleep(0.02)
            rt = api._global_runtime().backend
            workers = rt._request({"type": "list_workers"})["workers"]
            pid = next(
                (w.get("pid") for w in workers if w.get("actor") == victim_hex),
                0,
            )
            if pid:
                os.kill(pid, signal.SIGKILL)
                marks["kill_t"] = time.monotonic()
                marks["killed_step"] = ShardedCheckpoint.latest_committed(
                    elastic_root
                )[0]

        th = threading.Thread(target=killer, daemon=True)
        th.start()
        t_run = time.monotonic()
        result = ex.run(
            _gang_loop,
            {"collective_group": backend.group_name,
             "total_steps": total_steps, },
        )
        t_done = time.monotonic()
        sup = ex._supervisor
        ex.shutdown()
        if result.error is not None:
            raise RuntimeError(f"bench run failed: {result.error}")
        # First post-restore commit timestamp approximates "first step after
        # resume" (every step commits).
        return {
            "total_steps": total_steps,
            "killed_at_committed_step": marks.get("killed_step"),
            "restarts": sup.attempts,
            "supervisor_recovery_s": sup.last_recovery_s,
            "kill_to_run_complete_s": (
                t_done - marks["kill_t"] if "kill_t" in marks else None
            ),
            "total_run_s": t_done - t_run,
            "final_step": result.metrics.get("step"),
        }
    finally:
        ray_tpu.shutdown()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_ELASTIC_r01.json")
    ap.add_argument("--shard-mb", type=float, default=32.0)
    ap.add_argument("--saves", type=int, default=5)
    ap.add_argument("--total-steps", type=int, default=12)
    ap.add_argument("--kill-after-step", type=int, default=4)
    ap.add_argument("--skip-recovery", action="store_true")
    args = ap.parse_args()

    out = {
        "bench": "elastic_training",
        "host": os.uname().nodename,
        "ts": time.time(),
        "ckpt_overlap": bench_ckpt_overlap(args.shard_mb, args.saves),
    }
    if not args.skip_recovery:
        out["recovery"] = bench_recovery(args.total_steps, args.kill_after_step)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
    print(json.dumps(out, indent=2))
    print(f"\nwrote {args.out}")


if __name__ == "__main__":
    main()
