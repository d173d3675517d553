"""KV-cache generation (models/gpt.py prefill/decode_step/make_generate).

Correctness bar: the cached decode path must reproduce the full forward's
logits exactly (same math, different dataflow), for both GPT-2-style
(learned pos, layernorm) and GPT-J-style (rotary, parallel block) configs.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import GPTConfig, init_params
from ray_tpu.models.gpt import decode_step, forward, init_cache, make_generate, prefill


def _cfg(**kw):
    base = dict(
        vocab_size=128, n_layers=2, d_model=64, n_heads=4, d_head=16,
        d_mlp=128, max_seq=64, attn_impl="ref", remat=False,
        dtype=jnp.float32,  # exact comparison needs f32 end to end
    )
    return GPTConfig(**{**base, **kw})


@pytest.mark.parametrize("cfg", [
    _cfg(),
    _cfg(pos="rotary", rotary_dim=16, parallel_block=True,
         tie_embeddings=False, norm="rmsnorm", activation="swiglu"),
    # The one block brings the expert MLP to the dense cache too.
    _cfg(activation="reglu", mlp_type="moe", moe_experts=4, moe_top_k=2,
         moe_routing="dropless"),
], ids=["gpt2-style", "gptj-style", "dropless-experts"])
def test_decode_matches_forward(cfg):
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab_size)

    ref_logits = forward(params, tokens, cfg)  # [B, S, V]

    # Prefill on the first 6 tokens, then decode the rest one at a time.
    S0 = 6
    cache = init_cache(cfg, 2, 12)
    logits, cache = prefill(params, tokens[:, :S0], cfg, cache)
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(ref_logits[:, S0 - 1]), rtol=2e-4, atol=2e-4
    )
    for t in range(S0, 12):
        logits, cache = decode_step(params, tokens[:, t], cache, cfg)
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(ref_logits[:, t]), rtol=2e-4, atol=2e-4,
            err_msg=f"decode step {t}",
        )
    assert int(cache["len"]) == 12


def test_generate_greedy_matches_stepwise():
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 5), 0, cfg.vocab_size)

    gen = jax.jit(make_generate(cfg, max_new_tokens=8))
    out = np.asarray(gen(params, prompt, jax.random.PRNGKey(2)))
    assert out.shape == (2, 8)

    # Greedy reference: repeatedly run the FULL forward and take argmax.
    seq = np.asarray(prompt)
    for _ in range(8):
        logits = forward(params, jnp.asarray(seq), cfg)
        nxt = np.asarray(jnp.argmax(logits[:, -1], -1))[:, None]
        seq = np.concatenate([seq, nxt], axis=1)
    np.testing.assert_array_equal(out, seq[:, 5:])


def test_generate_temperature_shapes():
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompt = jnp.zeros((3, 4), jnp.int32)
    gen = jax.jit(make_generate(cfg, max_new_tokens=1, temperature=0.8))
    out = np.asarray(gen(params, prompt, jax.random.PRNGKey(0)))
    assert out.shape == (3, 1)
    assert (out >= 0).all() and (out < cfg.vocab_size).all()
