"""Decision Transformer — offline RL as sequence modeling.

Reference analog: `rllib/algorithms/dt/dt.py` + `dt_torch_model.py` —
return-conditioned behavior cloning: interleave (return-to-go, state,
action) tokens, train a causal transformer to predict actions, act at eval
time by conditioning on a target return. TPU redesign: the transformer
REUSES this framework's GPT block stack (`models/gpt._layer_loop` — the same
jitted lax.scan layers, norms, and attention the LLM path uses) under
custom continuous-input embeddings; the whole update is the shared
`make_supervised_update` scan program (one XLA call per iteration).
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...models.gpt import GPTConfig, _LAYER_KEYS, _layer_loop, _norm, init_params
from ..core.learner import Learner
from ..offline import EpisodeDataset
from .algorithm import Algorithm
from .algorithm_config import AlgorithmConfig
from .bc import make_supervised_update


class DTConfig(AlgorithmConfig):
    def __init__(self):
        super().__init__()
        self.lr = 1e-3
        self.context_length: int = 20      # K timesteps (3K tokens)
        self.embed_dim: int = 128
        self.num_layers: int = 3
        self.num_heads: int = 4
        self.train_batch_size = 512        # subsequences per iteration
        self.minibatch_size = 128
        self.num_epochs = 2
        self.target_return: Optional[float] = None  # eval conditioning
        self.rtg_scale: float = 100.0      # normalize returns-to-go
        self.max_ep_len: int = 1000        # timestep-embedding table size
        self.dataset: Optional[EpisodeDataset] = None
        self.num_env_runners = 0           # offline: env used for eval only

    def offline_data(self, dataset: EpisodeDataset) -> "DTConfig":
        self.dataset = dataset
        return self

    def validate(self):
        super().validate()
        if self.dataset is None:
            raise ValueError("DT needs offline_data(dataset=EpisodeDataset)")
        if self.target_return is None:
            raise ValueError("DT needs training(target_return=...) for eval")
        if self.train_batch_size % self.minibatch_size != 0:
            raise ValueError("train_batch_size must divide into minibatches")


class DTModule:
    """Return-conditioned causal transformer over (rtg, obs, act) tokens,
    discrete actions. Satisfies the Learner contract (init/forward)."""

    def __init__(self, obs_dim: int, n_actions: int, cfg: DTConfig):
        self.obs_dim = obs_dim
        self.n_actions = n_actions
        self.K = cfg.context_length
        self.max_ep_len = cfg.max_ep_len
        D = cfg.embed_dim
        # The GPT block stack config: ref attention (3K tokens is tiny),
        # f32 masters, no remat.
        self.block_cfg = GPTConfig(
            vocab_size=128, n_layers=cfg.num_layers, d_model=D,
            n_heads=cfg.num_heads, d_head=D // cfg.num_heads, d_mlp=4 * D,
            max_seq=3 * cfg.context_length, attn_impl="ref", remat=False,
            dtype=jnp.float32,
        )

    def init(self, rng):
        D = self.block_cfg.d_model
        k = jax.random.split(rng, 8)
        gpt_params = init_params(k[0], self.block_cfg)
        blocks = {key: gpt_params[key] for key in _LAYER_KEYS if key in gpt_params}

        def n(key, shape, s=0.02):
            return jax.random.normal(key, shape, jnp.float32) * s

        return {
            "blocks": blocks,
            "w_rtg": n(k[1], (1, D)),
            "w_obs": n(k[2], (self.obs_dim, D)),
            "b_tok": jnp.zeros((D,), jnp.float32),
            "act_embed": n(k[3], (self.n_actions, D)),
            "time_embed": n(k[4], (self.max_ep_len, D)),
            "ln_f_w": jnp.ones((D,), jnp.float32),
            "ln_f_b": jnp.zeros((D,), jnp.float32),
            "w_head": n(k[5], (D, self.n_actions)),
            "b_head": jnp.zeros((self.n_actions,), jnp.float32),
        }

    def forward(self, params, rtg, obs, actions, timesteps):
        """rtg/obs/actions/timesteps [B, K] (+obs_dim) -> action logits at
        every STATE token [B, K, A]."""
        B, K = rtg.shape
        te = params["time_embed"][timesteps]  # [B, K, D]
        h_rtg = rtg[..., None] @ params["w_rtg"] + params["b_tok"] + te
        h_obs = obs @ params["w_obs"] + params["b_tok"] + te
        h_act = params["act_embed"][actions] + te
        # Interleave to (rtg_0, s_0, a_0, rtg_1, s_1, a_1, ...).
        x = jnp.stack([h_rtg, h_obs, h_act], axis=2).reshape(B, 3 * K, -1)

        positions = jnp.arange(3 * K)

        x, _ = _layer_loop(self.block_cfg, None, positions)(x, params["blocks"])
        x = _norm(x, params["ln_f_w"], params["ln_f_b"], "layernorm")
        h_state = x[:, 1::3]  # the state-token positions predict actions
        return h_state @ params["w_head"] + params["b_head"]


def make_dt_update(module: DTModule, opt, cfg: DTConfig):
    def loss_fn(params, mb):
        logits = module.forward(
            params, mb["rtg"], mb["obs"], mb["actions"], mb["timesteps"]
        )
        logp = jax.nn.log_softmax(logits)
        ll = jnp.take_along_axis(logp, mb["actions"][..., None], -1)[..., 0]
        mask = mb["mask"]
        loss = -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
        acc = ((logits.argmax(-1) == mb["actions"]) * mask).sum() / jnp.maximum(
            mask.sum(), 1.0
        )
        return loss, {"dt_loss": loss, "action_accuracy": acc}

    return make_supervised_update(opt, cfg, loss_fn)


class DT(Algorithm):
    config_class = DTConfig

    def setup(self):
        self._np_rng = np.random.default_rng(self.config.seed)
        super().setup()
        # One jitted eval forward for the algorithm's lifetime — a fresh
        # jax.jit per evaluate() would re-trace + re-compile every iteration.
        self._fwd = jax.jit(self.module.forward)

    def _make_module(self):
        obs_dim = int(np.prod(self.observation_space.shape))
        return DTModule(obs_dim, self.action_space.n, self.config)

    def _make_learner(self) -> Learner:
        from ..utils.optim import make_optimizer

        cfg = self.config
        opt = make_optimizer(cfg)
        learner = Learner(
            self.module, make_dt_update(self.module, opt, cfg), seed=cfg.seed
        )
        learner.opt_state = opt.init(learner.params)
        return learner

    def training_step(self) -> Dict:
        cfg = self.config
        batch = cfg.dataset.sample_subsequences(
            self._np_rng, cfg.train_batch_size, cfg.context_length
        )
        batch["rtg"] = batch["rtg"] / cfg.rtg_scale
        batch["actions"] = batch["actions"].astype(np.int32)
        metrics = self.learner_group.update(batch)
        self._weights = self.learner_group.get_weights()
        ev = self.evaluate()
        if "episode_reward_mean" in ev:
            self._episode_returns.append(ev["episode_reward_mean"])
        return {
            "_env_steps_this_iter": 0,
            "num_offline_transitions_this_iter": cfg.train_batch_size,
            "info": {"learner": metrics},
            "evaluation": ev,
        }

    # DT acting is HISTORY-conditioned — the stateless eval-runner path
    # can't serve it, so evaluation is a local conditioned rollout
    # (reference: `dt.py` get_next_action on a running context).
    def evaluate(self, n_episodes: int = 5) -> Dict:
        from ..env import make_env

        cfg = self.config
        K = cfg.context_length
        params = self._weights
        fwd = self._fwd
        env = make_env(cfg.env, 1, **cfg.env_config)
        returns, lengths = [], []
        for ep in range(n_episodes):
            obs, _ = env.reset(seed=1000 + ep)
            obs_h = [np.asarray(obs[0], np.float32)]
            act_h: list = []
            rtg_h = [cfg.target_return]
            total, t = 0.0, 0
            while t < cfg.max_ep_len - 1:
                n = min(len(obs_h), K)
                o = np.zeros((1, K, self.module.obs_dim), np.float32)
                a = np.zeros((1, K), np.int32)
                r = np.zeros((1, K), np.float32)
                ts = np.zeros((1, K), np.int32)
                o[0, K - n:] = np.stack(obs_h[-n:])
                # Action slots: past actions; the CURRENT step's action slot
                # is a placeholder the causal mask keeps invisible to its
                # own state token.
                past = (act_h + [0])[-n:]
                a[0, K - n:] = past
                r[0, K - n:] = np.asarray(rtg_h[-n:]) / cfg.rtg_scale
                ts[0, K - n:] = np.arange(max(0, t - n + 1), t + 1)
                logits = fwd(params, r, o, a, ts)
                action = int(np.asarray(logits[0, -1]).argmax())
                obs, rew, term, trunc, _ = env.step(np.array([action]))
                reward = float(rew[0])
                total += reward
                act_h.append(action)
                rtg_h.append(rtg_h[-1] - reward)
                obs_h.append(np.asarray(obs[0], np.float32))
                t += 1
                if bool(term[0] or trunc[0]):
                    break
            returns.append(total)
            lengths.append(t)
        env.close()
        return {
            "episode_reward_mean": float(np.mean(returns)),
            "episode_len_mean": float(np.mean(lengths)),
            "episodes_this_eval": n_episodes,
        }


DTConfig.algo_class = DT
