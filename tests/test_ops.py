"""Kernel correctness vs the XLA reference, incl. ring/Ulysses on the fake
8-device mesh. The Pallas compiled path itself is exercised on real TPU by
the train cells of BENCHMARK.json; here the interpret path + CPU fallbacks guard the math."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from ray_tpu.ops import (
    apply_rope,
    attention_reference,
    flash_attention,
    layernorm,
    ring_attention,
    rmsnorm,
    rope_frequencies,
    ulysses_attention,
)
from ray_tpu.ops.attention import _flash_fwd_pallas
from ray_tpu.parallel import make_mesh, shard_fn


def _rand(*shape, key=0, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(key), shape, dtype=dtype)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_pallas_interpret_matches_reference(causal):
    B, H, S, D = 1, 2, 256, 64
    q, k, v = (_rand(B, H, S, D, key=i) for i in range(3))
    ref = attention_reference(q, k, v, causal=causal)
    out = _flash_fwd_pallas(q, k, v, causal, 1.0 / D**0.5, 128, 128, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize(
    "S,Skv,causal",
    [
        (200, 200, False),  # ragged vs 128 blocks
        (200, 200, True),
        (1, 128, True),     # decode over cached prefix (end-aligned)
        (64, 192, True),    # chunked prefill
    ],
)
def test_flash_ragged_and_decode_shapes(S, Skv, causal):
    q = _rand(1, 2, S, 32, key=0)
    k = _rand(1, 2, Skv, 32, key=1)
    v = _rand(1, 2, Skv, 32, key=2)
    ref = attention_reference(q, k, v, causal)
    out = _flash_fwd_pallas(q, k, v, causal, 32**-0.5, 128, 128, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-3, rtol=2e-3)


def test_flash_fallback_grad():
    B, H, S, D = 1, 2, 64, 32
    q, k, v = (_rand(B, H, S, D, key=i) for i in range(3))

    def loss(q, k, v):
        return flash_attention(q, k, v).sum()

    g = jax.grad(loss)(q, k, v)
    assert g.shape == q.shape and bool(jnp.isfinite(g).all())


@pytest.mark.parametrize(
    "causal,S,Skv,D",
    [
        (True, 256, 256, 64),
        (False, 256, 256, 64),
        (True, 200, 200, 32),   # ragged vs 128 blocks
        (True, 64, 192, 32),    # chunked prefill (end-aligned rows)
    ],
)
def test_flash_bwd_kernel_matches_reference(causal, S, Skv, D):
    from ray_tpu.ops.attention import _flash_bwd_pallas

    scale = 1.0 / D**0.5
    q = _rand(1, 2, S, D, key=0)
    k = _rand(1, 2, Skv, D, key=1)
    v = _rand(1, 2, Skv, D, key=2)
    g = _rand(1, 2, S, D, key=7)

    ref_grads = jax.vjp(
        lambda q_, k_, v_: attention_reference(q_, k_, v_, causal, scale), q, k, v
    )[1](g)

    o, lse = _flash_fwd_pallas(q, k, v, causal, scale, 128, 128,
                               interpret=True, return_lse=True)
    dq, dk, dv = _flash_bwd_pallas(q, k, v, o, lse, g, causal, scale, 128, 128,
                                   interpret=True)
    for got, want in zip((dq, dk, dv), ref_grads):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_full(causal):
    n = 8
    mesh = make_mesh(sp=n)
    B, H, S, D = 1, 2, 8 * 16, 32
    q, k, v = (_rand(B, H, S, D, key=i) for i in range(3))
    ref = attention_reference(q, k, v, causal=causal)

    fn = shard_fn(
        functools.partial(ring_attention, axis="sp", causal=causal),
        mesh,
        in_specs=(P(None, None, "sp", None),) * 3,
        out_specs=P(None, None, "sp", None),
    )
    out = jax.jit(fn)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-3, rtol=2e-3)


def test_ring_attention_grad_finite():
    mesh = make_mesh(jax.devices()[:4], sp=4)
    B, H, S, D = 1, 2, 64, 16
    q, k, v = (_rand(B, H, S, D, key=i) for i in range(3))

    def loss(q, k, v):
        fn = shard_fn(
            functools.partial(ring_attention, axis="sp", causal=True),
            mesh,
            in_specs=(P(None, None, "sp", None),) * 3,
            out_specs=P(None, None, "sp", None),
        )
        return (fn(q, k, v) ** 2).sum()

    g = jax.jit(jax.grad(loss))(q, k, v)
    assert bool(jnp.isfinite(g).all())


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_matches_full(causal):
    n = 4
    mesh = make_mesh(jax.devices()[:n], sp=n)
    B, H, S, D = 1, 4, 64, 16  # H divisible by n
    q, k, v = (_rand(B, H, S, D, key=i) for i in range(3))
    ref = attention_reference(q, k, v, causal=causal)

    fn = shard_fn(
        functools.partial(ulysses_attention, axis="sp", causal=causal),
        mesh,
        in_specs=(P(None, None, "sp", None),) * 3,
        out_specs=P(None, None, "sp", None),
    )
    out = jax.jit(fn)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-3, rtol=2e-3)


def test_rmsnorm_matches_manual():
    x = _rand(4, 256)
    w = _rand(256, key=9) * 0.1 + 1.0
    out = rmsnorm(x, w)
    expected = x * (1.0 / np.sqrt((np.asarray(x) ** 2).mean(-1, keepdims=True) + 1e-6)) * np.asarray(w)
    np.testing.assert_allclose(np.asarray(out), expected, atol=1e-5, rtol=1e-5)


def test_rmsnorm_grad():
    x = _rand(4, 128)
    w = jnp.ones(128)
    g = jax.grad(lambda x_: rmsnorm(x_, w).sum())(x)
    assert bool(jnp.isfinite(g).all())


def test_layernorm():
    x = _rand(4, 64)
    out = layernorm(x, jnp.ones(64), jnp.zeros(64))
    np.testing.assert_allclose(np.asarray(out).mean(-1), 0.0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out).std(-1), 1.0, atol=1e-2)


def test_rope_rotation_preserves_norm():
    cos, sin = rope_frequencies(64, 128)
    x = _rand(1, 2, 128, 64)
    out = apply_rope(x, cos, sin)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(out), axis=-1),
        np.linalg.norm(np.asarray(x), axis=-1),
        rtol=1e-5,
    )


def test_rope_relative_property():
    # <rope(q, m), rope(k, n)> depends only on m - n.
    cos, sin = rope_frequencies(32, 64)
    q = _rand(1, 1, 1, 32, key=1)[0, 0, 0]
    k = _rand(1, 1, 1, 32, key=2)[0, 0, 0]

    def dot_at(m, n):
        qr = apply_rope(q[None], cos, sin, positions=jnp.array([m]))[0]
        kr = apply_rope(k[None], cos, sin, positions=jnp.array([n]))[0]
        return float(qr @ kr)

    np.testing.assert_allclose(dot_at(5, 3), dot_at(10, 8), rtol=1e-4)
    np.testing.assert_allclose(dot_at(20, 3), dot_at(30, 13), rtol=1e-4)
