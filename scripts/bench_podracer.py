"""Podracer RL bench — records BENCH_RL_podracer.json.

Three executions of PPO CartPole, A/B'd:

  * ``envrunner`` — the classic path (EnvRunner sampling + LearnerGroup),
                    measured by the SAME probe `scripts/rl_perf.py` emits,
                    so the baseline row here and the rl_perf artifact line
                    are one definition;
  * ``anakin``    — env dynamics fused into the learner jit
                    (`podracer("anakin")`): rollout + GAE + SGD epochs in
                    ONE compiled program, no host round-trip per step;
  * ``sebulba``   — actor gang + learner split (`podracer("sebulba")`):
                    trajectory frames over the block-transport arena/bulk
                    planes, param broadcasts over compiled-DAG channels.

Recorded per mode: steady env-steps/s (after jit warmup), per-iteration
learner-step seconds, the learning bar (reward 150; Anakin additionally a
greedy eval return — perf means nothing if the plane learns a different
policy), and for Sebulba the transport rung counters proving frames rode
arena segments. The acceptance claim lives in ``summary``:
``anakin_speedup_x >= 20`` over the envrunner baseline on the same host.

Usage: python scripts/bench_podracer.py [--record] [--quick]
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("RAY_TPU_LOG_TO_DRIVER", "0")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(REPO, "BENCH_RL_podracer.json")

# Anakin's recorded operating point: throughput-shaped (wide batch, few
# epochs) AND still solves CartPole — both halves of the acceptance bar.
ANAKIN_ENVS = 512
ANAKIN_ROLLOUT = 64

SEBULBA_ACTORS = 2
SEBULBA_ENVS = 32   # x 128 steps ~ 90KB/frame: above the inline threshold,
SEBULBA_ROLLOUT = 128  # so frames ride arena segments (asserted below).


def bench_anakin(quick: bool) -> dict:
    from ray_tpu.rllib import PPOConfig

    algo = (
        PPOConfig()
        .environment("CartPole-v1")
        .training(
            train_batch_size=ANAKIN_ENVS * ANAKIN_ROLLOUT,
            minibatch_size=4096,
            num_epochs=1,
            lr=1e-3,
        )
        .debugging(seed=0)
        .podracer("anakin", num_envs=ANAKIN_ENVS, rollout_len=ANAKIN_ROLLOUT)
        .build()
    )
    per_iter = ANAKIN_ENVS * ANAKIN_ROLLOUT
    iters = 4 if quick else 20
    algo.train()  # warmup: jit compile of the fused program
    best = 0.0
    reached_at = None
    step_s = []
    t0 = time.perf_counter()
    for _ in range(iters):
        result = algo.train()
        best = max(best, result["episode_reward_mean"])
        if reached_at is None and best >= 150:
            reached_at = result["timesteps_total"]
        step_s.append(result["info"]["fused_step_seconds"])
    wall = time.perf_counter() - t0
    eval_ret = algo.evaluate()["episode_reward_mean"]
    algo.stop()
    return {
        "env_steps_per_sec": round(iters * per_iter / wall, 1),
        "fused_step_s_median": round(statistics.median(step_s), 5),
        "steps_measured": iters * per_iter,
        "best_reward": round(best, 1),
        "reward150_at_steps": reached_at,
        "eval_reward": round(eval_ret, 1),
        "shape": {
            "num_envs": ANAKIN_ENVS, "rollout_len": ANAKIN_ROLLOUT,
            "num_epochs": 1, "minibatch_size": 4096, "lr": 1e-3,
        },
    }


def bench_sebulba(quick: bool) -> dict:
    import ray_tpu
    from ray_tpu.rllib import PPOConfig

    per_iter = SEBULBA_ACTORS * SEBULBA_ENVS * SEBULBA_ROLLOUT
    algo = (
        PPOConfig()
        .environment("CartPole-v1")
        .training(
            train_batch_size=per_iter,
            minibatch_size=2048,
            num_epochs=2,
            lr=1e-3,
        )
        .debugging(seed=0)
        .podracer(
            "sebulba",
            num_actors=SEBULBA_ACTORS,
            envs_per_actor=SEBULBA_ENVS,
            rollout_len=SEBULBA_ROLLOUT,
        )
        .build()
    )
    iters = 3 if quick else 12
    algo.train()  # warmup: worker-side jit + first broadcast
    best = 0.0
    step_s = []
    t0 = time.perf_counter()
    for _ in range(iters):
        result = algo.train()
        best = max(best, result["episode_reward_mean"])
        step_s.append(result["info"]["learner_step_seconds"])
    wall = time.perf_counter() - t0
    stats = algo._podracer.transport_stats
    learner_stats = dict(stats["learner"])
    actor_arena = sum(a["pub_arena"] for a in stats["actors"])
    algo.stop()
    ray_tpu.shutdown()
    return {
        "env_steps_per_sec": round(iters * per_iter / wall, 1),
        "learner_step_s_median": round(statistics.median(step_s), 5),
        "steps_measured": iters * per_iter,
        "best_reward": round(best, 1),
        "transport": {
            "actor_pub_arena_total": actor_arena,
            "learner_fetch": learner_stats,
            "frames_ride_arena": bool(
                actor_arena > 0
                and learner_stats["fetch_local"] + learner_stats["fetch_span"]
                > 0
                and learner_stats["fetch_inline"] == 0
            ),
        },
        "shape": {
            "num_actors": SEBULBA_ACTORS, "envs_per_actor": SEBULBA_ENVS,
            "rollout_len": SEBULBA_ROLLOUT, "num_epochs": 2,
            "minibatch_size": 2048, "lr": 1e-3,
        },
    }


def run(record: bool, quick: bool):
    from scripts.rl_perf import ppo_cartpole_probe

    print("== envrunner (classic path, rl_perf probe) ==", flush=True)
    env_probe = ppo_cartpole_probe(max_iters=6 if quick else 60)
    print(json.dumps(env_probe), flush=True)

    print("== anakin (fused plane) ==", flush=True)
    anakin = bench_anakin(quick)
    print(json.dumps(anakin), flush=True)

    print("== sebulba (split plane) ==", flush=True)
    sebulba = bench_sebulba(quick)
    print(json.dumps(sebulba), flush=True)

    speedup = anakin["env_steps_per_sec"] / env_probe["value"]
    out = {
        "bench": "podracer_rl",
        "host": {"nproc": os.cpu_count(), "note": "CPU jax; shared box"},
        "env": "CartPole-v1",
        "modes": {
            "envrunner": {
                "env_steps_per_sec": env_probe["value"],
                "rl_probe": env_probe,
            },
            "anakin": anakin,
            "sebulba": sebulba,
        },
        "summary": {
            "anakin_speedup_x": round(speedup, 1),
            "anakin_speedup_bar": 20.0,
            "bar_met": bool(speedup >= 20.0),
            "learning_parity": {
                "envrunner_bar_met": env_probe["extra"]["bar_met"],
                "anakin_eval_reward": anakin["eval_reward"],
                "anakin_solves": bool(anakin["eval_reward"] >= 150.0),
            },
            "sebulba_frames_ride_arena":
                sebulba["transport"]["frames_ride_arena"],
        },
        "quick": quick,
    }
    print(json.dumps(out["summary"], indent=2))
    if record:
        with open(OUT, "w") as f:
            json.dump(out, f, indent=2)
        print(f"recorded -> {OUT}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--record", action="store_true")
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    run(args.record, args.quick)


if __name__ == "__main__":
    main()
