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


def _lse_reference(q, k, causal, scale):
    S, Skv = q.shape[2], k.shape[2]
    logits = jnp.einsum("bhsd,bhtd->bhst", q, k) * scale
    if causal:
        seen = jnp.arange(S)[:, None] + (Skv - S) >= jnp.arange(Skv)[None, :]
        logits = jnp.where(seen, logits, -1e30)
    return jax.nn.logsumexp(logits, axis=-1).reshape(-1, 1, S)


# The walk inside a grid step (default blocks of 1,024, sub-tiles by the head
# width): the two train cells' shapes, a ragged tail inside the last sub-tile,
# a sequence shorter than one sub-tile, a diagonal that starts at no tile
# corner (S != Skv), and no diagonal at all.
WALK_SHAPES = [
    (1024, 1024, True, 64, 1024),
    (2048, 2048, True, 256, 1024),
    (1000, 1000, True, 64, 1024),
    (100, 100, True, 64, 1024),
    (300, 1000, True, 64, 1024),
    (1024, 1024, False, 64, 1024),
    (1000, 1000, False, 64, 1024),
    (640, 640, True, 64, 256),     # a 3 x 3 grid of blocks of one sub-tile, ragged
]


@pytest.mark.parametrize(
    "S,Skv,causal,D,block",
    [
        (200, 200, False, 32, 128),  # ragged vs 128 blocks
        (200, 200, True, 32, 128),
        (1, 128, True, 32, 128),     # decode over cached prefix (end-aligned)
        (64, 192, True, 32, 128),    # chunked prefill
        *WALK_SHAPES,
    ],
)
def test_flash_ragged_and_decode_shapes(S, Skv, causal, D, block):
    q = _rand(1, 2, S, D, key=0)
    k = _rand(1, 2, Skv, D, key=1)
    v = _rand(1, 2, Skv, D, key=2)
    ref = attention_reference(q, k, v, causal)
    out, lse = _flash_fwd_pallas(q, k, v, causal, D**-0.5, block, block,
                                 interpret=True, return_lse=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(lse[:, :, :S]),
                               np.asarray(_lse_reference(q, k, causal, D**-0.5)),
                               atol=2e-3, rtol=2e-3)


def test_flash_fallback_grad():
    B, H, S, D = 1, 2, 64, 32
    q, k, v = (_rand(B, H, S, D, key=i) for i in range(3))

    def loss(q, k, v):
        return flash_attention(q, k, v).sum()

    g = jax.grad(loss)(q, k, v)
    assert g.shape == q.shape and bool(jnp.isfinite(g).all())


@pytest.mark.parametrize(
    "S,Skv,causal,D,block",
    [
        (256, 256, True, 64, 128),
        (256, 256, False, 64, 128),
        (200, 200, True, 32, 128),   # ragged vs 128 blocks
        (64, 192, True, 32, 128),    # chunked prefill (end-aligned rows)
        *WALK_SHAPES,
    ],
)
def test_flash_bwd_kernel_matches_reference(S, Skv, causal, D, block):
    from ray_tpu.ops.attention import _flash_bwd_pallas

    scale = 1.0 / D**0.5
    q = _rand(1, 2, S, D, key=0)
    k = _rand(1, 2, Skv, D, key=1)
    v = _rand(1, 2, Skv, D, key=2)
    g = _rand(1, 2, S, D, key=7)

    ref_grads = jax.vjp(
        lambda q_, k_, v_: attention_reference(q_, k_, v_, causal, scale), q, k, v
    )[1](g)

    o, lse = _flash_fwd_pallas(q, k, v, causal, scale, block, block,
                               interpret=True, return_lse=True)
    dq, dk, dv = _flash_bwd_pallas(q, k, v, o, lse, g, causal, scale, block, block,
                                   interpret=True)
    for got, want in zip((dq, dk, dv), ref_grads):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-2, rtol=2e-2)


# The kernels' operand form, by `heads_a_step`: (batch, heads, S, Skv, Dh,
# block, causal, each head's gain on v and dO). A pair of heads of 64 a grid
# step in the halves of a 128-lane column block of [B, S, H·Dh] (with heads a
# thousand times apart: a share of one that leaked into the other's lanes would
# drown it), a 2 x 2 grid of blocks, Skv != S with a ragged tail; four heads of
# 32 a step; and the shapes that stay [B·H, S, Dh], a head a row: heads of
# whole lane tiles (128, 256), an odd count of 64, two of 32.
OPERAND_FORMS = {
    "pair-of-64": (2, 4, 256, 256, 64, 128, True, None),
    "pair-of-64-a-thousand-apart": (1, 2, 256, 256, 64, 256, True, (1.0, 1e3)),
    "pair-of-64-apart-the-other-way": (1, 2, 256, 256, 64, 128, True, (1e3, 1.0)),
    "pair-of-64-ragged-Skv-longer": (1, 4, 200, 333, 64, 128, True, None),
    "pair-of-64-no-diagonal": (2, 2, 200, 200, 64, 128, False, None),
    "four-of-32": (1, 4, 256, 256, 32, 128, True, (1.0, 1e3, 1.0, 1e3)),
    "rows-three-of-128": (2, 3, 256, 256, 128, 128, True, None),
    "rows-two-of-256": (2, 2, 300, 300, 256, 256, True, None),
    "rows-three-of-64": (2, 3, 256, 256, 64, 128, True, None),
    "rows-two-of-32": (2, 2, 200, 200, 32, 128, True, None),
}


@pytest.mark.parametrize("case", list(OPERAND_FORMS))
def test_flash_operand_forms_match_reference(case):
    """Forward, lse, dq, dk and dv against `attention_reference` and its
    gradient, a head at a time and to that head's own scale."""
    from ray_tpu.ops.attention import _flash_bwd_pallas, heads_a_step

    B, H, S, Skv, D, block, causal, gains = OPERAND_FORMS[case]
    assert bool(heads_a_step(H, D)) == (not case.startswith("rows"))
    scale = D**-0.5
    gain = jnp.asarray(gains or (1.0,) * H)[None, :, None, None]
    q = _rand(B, H, S, D, key=0)
    k = _rand(B, H, Skv, D, key=1)
    v = _rand(B, H, Skv, D, key=2) * gain
    g = _rand(B, H, S, D, key=7) * gain
    ref, vjp = jax.vjp(lambda q_, k_, v_: attention_reference(q_, k_, v_, causal, scale),
                       q, k, v)
    o, lse = _flash_fwd_pallas(q, k, v, causal, scale, block, block, interpret=True,
                               return_lse=True)
    grads = _flash_bwd_pallas(q, k, v, o, lse, g, causal, scale, block, block,
                              interpret=True)
    np.testing.assert_allclose(np.asarray(lse[:, :, :S]),
                               np.asarray(_lse_reference(q, k, causal, scale)),
                               atol=2e-3, rtol=2e-3)
    for name, got, want, tol in zip(("o", "dq", "dk", "dv"), (o, *grads), (ref, *vjp(g)),
                                    (2e-3, 2e-2, 2e-2, 2e-2)):
        for h in range(H):
            size = float(jnp.abs(want[:, h]).max())
            np.testing.assert_allclose(
                np.asarray(got[:, h]) / size, np.asarray(want[:, h]) / size,
                atol=tol, rtol=tol, err_msg=f"{name}, head {h}")


@pytest.mark.parametrize(
    "heads,head_dim,want",
    [
        (20, 64, 2),     # gpt2-large: a pair fills 128 lanes
        (8, 32, 4), (8, 16, 8),
        (16, 256, 0),    # gptj-6b: a head of whole lane tiles stays a row
        (3, 128, 0),
        (3, 64, 0),      # an odd count of halves
        (2, 32, 0),      # two quarters
        (4, 96, 0), (4, 192, 0),    # no whole lane tiles
        (1, 64, 0),
    ],
)
def test_flash_heads_a_step_rule(heads, head_dim, want):
    from ray_tpu.ops.attention import heads_a_step

    assert heads_a_step(heads, head_dim) == want


@pytest.mark.parametrize(
    "grid,tile,seqs,causal,want",
    [
        # one block a side at 1,024: 10 of 16 pairs, the 4 on the diagonal masked
        ((1, 1), 256, (1024, 1024), True, {(0, None, None): (10, 4)}),
        # a 2 x 2 grid: the diagonal blocks walk the triangle, the block below
        # them runs whole with no mask, the one above has no walk at all
        ((2, 2), 256, (2048, 2048), True, {(0, None, None): (10, 4), (None,) * 3: (16, 0)}),
        # no diagonal, a ragged tail of 24 columns: every pair, the last column's masked
        ((1, 1), 256, (1000, 1000), False, {(None, 1000, None): (16, 4)}),
        # S != Skv: the diagonal starts 700 columns in, at no tile corner
        ((1, 1), 256, (300, 1000), True, {(-700, 1000, None): (8, 3)}),
    ],
)
def test_flash_walk_enumerates_the_pairs_at_trace_time(grid, tile, seqs, causal, want):
    from ray_tpu.ops.attention import _NO_MASK, _walks

    bq, bk = (min(1024, -(-n // tile) * tile) for n in seqs)
    walks, whole = _walks(*grid, bq, bk, tile, tile, causal, *seqs, False)
    got = {key: (len(pairs), sum(mask != _NO_MASK for *_, mask in pairs))
           for key, pairs in walks.items()}
    assert got == want
    assert whole == (not causal or grid == (1, 1))


@pytest.mark.parametrize("kernel", ["fwd", "dq", "dkv"])
def test_flash_walk_skips_the_pairs_above_the_diagonal(kernel):
    """NaN in every row that only a skipped sub-tile pair would read: a mask
    alone does not keep it out (0 x NaN in the product behind it), a pair
    that is never computed does. Keys and values past a q sub-tile's own
    rows for the forward and dq; queries and dO before a k sub-tile's own
    rows for dk and dv."""
    from ray_tpu.ops.attention import _flash_bwd_pallas, _sub_tile

    S, D = 1024, 64
    scale = D**-0.5
    tile = _sub_tile({"fwd": "flash_fwd", "dq": "flash_bwd_dq", "dkv": "flash_bwd_dkv"}[kernel], D)
    assert S // tile > 1, "one sub-tile a block: nothing to skip"
    q, k, v, g = (_rand(1, 2, S, D, key=i) for i in range(4))
    want, vjp = jax.vjp(lambda q_, k_, v_: attention_reference(q_, k_, v_, True, scale),
                        q, k, v)
    want = dict(zip(("o", "dq", "dk", "dv"), (want, *vjp(g))))
    o, lse = _flash_fwd_pallas(q, k, v, True, scale, 1024, 1024, interpret=True,
                               return_lse=True)
    for a in range(S // tile):
        mine = slice(a * tile, (a + 1) * tile)
        if kernel == "dkv":
            poison = (jnp.arange(S) < a * tile)[:, None]
            got = dict(zip(("dq", "dk", "dv"), _flash_bwd_pallas(
                jnp.where(poison, jnp.nan, q), k, v, o, lse, jnp.where(poison, jnp.nan, g),
                True, scale, 1024, 1024, interpret=True)))
            names = ("dk", "dv")
        else:
            poison = (jnp.arange(S) >= (a + 1) * tile)[:, None]
            kp, vp = jnp.where(poison, jnp.nan, k), jnp.where(poison, jnp.nan, v)
            if kernel == "fwd":
                got = {"o": _flash_fwd_pallas(q, kp, vp, True, scale, 1024, 1024,
                                              interpret=True)}
                names = ("o",)
            else:
                got = dict(zip(("dq", "dk", "dv"), _flash_bwd_pallas(
                    q, kp, vp, o, lse, g, True, scale, 1024, 1024, interpret=True)))
                names = ("dq",)
        for name in names:
            np.testing.assert_allclose(np.asarray(got[name][:, :, mine]),
                                       np.asarray(want[name][:, :, mine]),
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
