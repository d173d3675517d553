"""Decompose the train step: forward, forward+backward, optimizer, attention.

Finds where the 755ms step goes. Run on the real TPU.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import optax

from ray_tpu.models import GPTConfig, gpt2_medium, init_params, loss_fn
from ray_tpu.ops import flash_attention
from ray_tpu.ops.attention import attention_reference


def _fence(out):
    """Fence by a host transfer of one element."""
    leaf = jax.tree_util.tree_leaves(out)[0]
    _ = float(jnp.asarray(leaf).ravel()[0])


def timeit(fn, *args, n=6):
    out = fn(*args)
    _fence(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    _fence(out)
    return (time.perf_counter() - t0) / n * 1000


def main():
    B, S = 16, 1024
    cfg = gpt2_medium(max_seq=S, attn_impl="flash", remat=True)
    params = jax.jit(lambda key: init_params(key, cfg))(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S + 1), 0, cfg.vocab_size)
    batch = {"tokens": tokens}

    fwd = jax.jit(lambda p, b: loss_fn(p, b, cfg))
    grad = jax.jit(lambda p, b: jax.value_and_grad(loss_fn)(p, b, cfg))
    print(json.dumps({"fwd_ms": round(timeit(fwd, params, batch), 1)}), flush=True)
    print(json.dumps({"fwd_bwd_ms": round(timeit(grad, params, batch), 1)}), flush=True)

    opt = optax.adamw(3e-4, weight_decay=0.1)
    opt_state = opt.init(params)
    _, grads = grad(params, batch)

    def apply(params, opt_state, grads):
        updates, opt_state = opt.update(grads, opt_state, params)
        params = jax.tree_util.tree_map(lambda p, u: p + u.astype(p.dtype), params, updates)
        return params, opt_state

    applyj = jax.jit(apply)
    print(json.dumps({"opt_ms": round(timeit(applyj, params, opt_state, grads), 1)}), flush=True)

    # attention alone, bench shapes
    H, Dh = cfg.n_heads, cfg.d_head
    q = jax.random.normal(jax.random.PRNGKey(2), (B, H, S, Dh), jnp.bfloat16)
    k = jax.random.normal(jax.random.PRNGKey(3), (B, H, S, Dh), jnp.bfloat16)
    v = jax.random.normal(jax.random.PRNGKey(4), (B, H, S, Dh), jnp.bfloat16)
    fa = jax.jit(lambda q, k, v: flash_attention(q, k, v))
    ra = jax.jit(lambda q, k, v: attention_reference(q, k, v))
    print(json.dumps({"flash_fwd_ms": round(timeit(fa, q, k, v), 2),
                      "ref_fwd_ms": round(timeit(ra, q, k, v), 2)}), flush=True)

    fg = jax.jit(jax.grad(lambda q, k, v: flash_attention(q, k, v).sum(), argnums=(0, 1, 2)))
    rg = jax.jit(jax.grad(lambda q, k, v: attention_reference(q, k, v).astype(jnp.float32).sum(), argnums=(0, 1, 2)))
    print(json.dumps({"flash_fwdbwd_ms": round(timeit(fg, q, k, v), 2),
                      "ref_fwdbwd_ms": round(timeit(rg, q, k, v), 2)}), flush=True)

    # per-layer matmul-only model (no attention) to bound the matmul time
    cfg_ref = gpt2_medium(max_seq=S, attn_impl="ref", remat=True)
    grad_ref = jax.jit(lambda p, b: jax.value_and_grad(loss_fn)(p, b, cfg_ref))
    print(json.dumps({"fwd_bwd_ref_attn_ms": round(timeit(grad_ref, params, batch), 1)}), flush=True)

    # no-remat forward for comparison
    cfg_nr = gpt2_medium(max_seq=S, attn_impl="flash", remat=False)
    fwd_nr = jax.jit(lambda p, b: loss_fn(p, b, cfg_nr))
    try:
        print(json.dumps({"fwd_noremat_ms": round(timeit(fwd_nr, params, batch), 1)}), flush=True)
    except Exception as e:  # noqa: BLE001
        print(json.dumps({"fwd_noremat_error": repr(e)[:160]}), flush=True)


if __name__ == "__main__":
    main()
