"""RLlib performance probes — BASELINE.md north-star RL metrics.

Reference analog: `rllib/tuned_examples/ppo/cartpole-ppo.yaml` (reward 150
within 100k env steps) and the env-steps/sec targets in BASELINE.json.
Run: `python scripts/rl_perf.py` — one JSON line per probe.

`ppo_cartpole_probe()` is importable: `scripts/bench_podracer.py` records
the same EnvRunner measurement as the baseline row of
BENCH_RL_podracer.json, so the classic-path number in both artifacts is one
definition.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Rollout policy steps are tiny; RL sampling belongs on host CPU (the TPU is
# for the big learners).
os.environ["JAX_PLATFORMS"] = "cpu"


def ppo_cartpole_probe(max_iters: int = 60) -> dict:
    """Classic EnvRunner-path PPO on CartPole: env-steps/s plus the
    learning bar (reward 150 within 100k steps). Returns the probe dict."""
    from ray_tpu.rllib import PPOConfig

    algo = (
        PPOConfig()
        .environment("CartPole-v1")
        .env_runners(num_env_runners=0, num_envs_per_env_runner=8)
        .training(train_batch_size=2048, lr=3e-4)
        .debugging(seed=0)
        .build()
    )
    total_steps = 0
    best = 0.0
    reached_at = None
    t0 = time.perf_counter()
    for _ in range(max_iters):
        result = algo.train()
        total_steps = result["timesteps_total"]
        best = max(best, result["episode_reward_mean"])
        if reached_at is None and best >= 150:
            reached_at = total_steps
        if reached_at is not None and total_steps >= 40_000:
            break
    wall = time.perf_counter() - t0
    algo.stop()
    return {
        "rl_probe": "ppo_cartpole_env_steps_per_sec",
        "value": round(total_steps / wall, 1),
        "unit": "env-steps/s",
        "extra": {
            "best_reward": round(best, 1),
            "reward150_at_steps": reached_at,
            "baseline_bar": "reward 150 within 100k steps",
            "bar_met": bool(reached_at is not None and reached_at <= 100_000),
        },
    }


def main():
    print(json.dumps(ppo_cartpole_probe()), flush=True)


if __name__ == "__main__":
    main()
