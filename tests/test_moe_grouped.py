"""The grouped form of the dropless expert layer (`ops/moe.py`
`dropless_experts(grouped_k=k)`), the one form the served programs take: the
assignments sorted by expert into row tiles, only the tiles the routing fills
computed, none for a routing that is empty. All on the CPU: the layout
and the combine are plain JAX, the tiles a `lax` loop off the TPU, and the
Pallas kernels the TPU runs are held to that loop in interpret mode."""

import functools

import numpy as np
import pytest

# (experts the router sees, top-k, scoring, held range or None = all)
ROUTINGS = {
    "64-of-64-top6-softmax": (64, 6, "softmax", None),
    "12-of-192-top8-sigmoid-first": (192, 8, "sigmoid", (0, 12)),
    "12-of-192-top8-sigmoid-middle": (192, 8, "sigmoid", (96, 12)),
}


def _layer(N, routing, alike=False, D=32, F=16, dtype="float32", seed=0):
    """(x [N, D], combine [N, held], the held experts' three weights)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import moe

    X, k, scoring, held = ROUTINGS[routing] if isinstance(routing, str) else routing
    first, count = held or (0, X)
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (1 if alike else N, D))
    x = jnp.tile(x, (N, 1)) if alike else x
    router = jax.random.normal(ks[1], (D, X))
    if alike and held:      # tokens alike: see to it that some choice is held
        router = router.at[:, first].add(x[0] * 4)
    idx, w = moe.dropless_route(x @ router, k, scoring, 2.5 if scoring == "sigmoid" else 1.0)
    combine = moe.dropless_combine(idx, w, X)[:, first:first + count]
    w_gate, w_in = (jax.random.normal(kk, (count, D, F)) * 0.3 for kk in ks[2:4])
    w_out = jax.random.normal(ks[4], (count, F, D)) * 0.3
    cast = lambda a: a.astype(dtype)
    return cast(x), combine, (cast(w_gate), cast(w_in), cast(w_out)), k


@pytest.mark.parametrize("rows_tile", [4, 128])
@pytest.mark.parametrize("alike", [False, True], ids=["ragged", "whole-tiles"])
@pytest.mark.parametrize("routing", list(ROUTINGS))
@pytest.mark.parametrize("tokens", [16, 100, 512])
def test_grouped_equals_dense(tokens, routing, alike, rows_tile, monkeypatch):
    """Tokens alike all choose the same experts: each group is `tokens` rows,
    whole tiles of 4 (and of 128 at 512 tokens); random tokens leave every
    group's last tile part empty."""
    import jax.numpy as jnp

    from ray_tpu.ops import moe

    monkeypatch.setattr(moe, "GROUP_ROWS", rows_tile)
    x, combine, experts, k = _layer(tokens, routing, alike)
    sizes = np.asarray((combine > 0).sum(axis=0))
    assert sizes.sum() > 0
    assert (sizes % rows_tile == 0).all() == (alike and tokens % rows_tile == 0)
    dense = moe.dropless_experts(x, combine, *experts, "swiglu")
    grouped = moe.dropless_experts(x, combine, *experts, "swiglu", grouped_k=k)
    assert grouped.dtype == dense.dtype and grouped.shape == dense.shape
    assert float(jnp.abs(dense).max()) > 0.05
    assert float(jnp.abs(grouped - dense).max()) < 1e-5


@pytest.mark.parametrize("routing", list(ROUTINGS))
@pytest.mark.parametrize("tokens", [16, 100, 512])
def test_groups_layout_places_every_held_assignment_once(tokens, routing):
    from ray_tpu.ops import moe

    _, combine, _, k = _layer(tokens, routing)
    X = combine.shape[1]
    rows, tile_expert, tile_first, tiles = (
        np.asarray(a) for a in moe.dropless_groups(combine, k, 8))
    chosen = np.asarray(combine > 0)
    T = tile_expert.shape[0]
    sizes = chosen.sum(axis=0)
    assert rows.shape == chosen.shape and T == tokens * min(k, X) // 8 + X
    assert tiles == sum(-(-int(s) // 8) for s in sizes) <= T
    assert (np.diff(tile_expert[:tiles]) >= 0).all()          # sorted by expert
    assert (rows[~chosen] == -1).all()
    for e in range(X):      # a group's rows: its tokens in their order, 0 .. size-1
        assert rows[chosen[:, e], e].tolist() == list(range(sizes[e]))
        mine = [t for t in range(tiles) if tile_expert[t] == e]
        assert len(mine) == -(-int(sizes[e]) // 8)            # whole tiles, its own
        assert tile_first[mine].tolist() == [8 * i for i in range(len(mine))]
    # every held assignment has one row of one tile: tile t holds the tokens
    # whose row in its expert's group lies in tile_first[t] .. + 7
    seen = np.zeros_like(chosen, dtype=int)
    for t in range(tiles):
        at = rows[:, tile_expert[t]] - tile_first[t]
        seen[(at >= 0) & (at < 8), tile_expert[t]] += 1
    assert (seen == chosen).all()


def _reference(x, combine, experts, act="swiglu"):
    """The sum as written, one (token, expert) pair at a time, float64."""
    x, combine = np.asarray(x, np.float64), np.asarray(combine, np.float64)
    w_gate, w_in, w_out = (np.asarray(a, np.float64) for a in experts)
    y = np.zeros_like(x)
    for n, e in zip(*np.nonzero(combine)):
        g, u = x[n] @ w_gate[e], x[n] @ w_in[e]
        g = g / (1 + np.exp(-g)) if act == "swiglu" else np.maximum(g, 0)
        y[n] += combine[n, e] * ((g * u) @ w_out[e])
    return y


def test_an_expert_nobody_chose_is_in_no_tile():
    from ray_tpu.ops import moe

    x, combine, experts, k = _layer(24, (8, 2, "softmax", None))
    combine = combine.at[:, 3].set(0.0)                 # nobody's choice
    _, tile_expert, _, tiles = moe.dropless_groups(combine, k, 4)
    assert 3 not in np.asarray(tile_expert)[:int(tiles)].tolist()
    # its weights may hold anything: they are never read
    broken = tuple(a.at[3].set(np.nan) for a in experts)
    y = moe.dropless_experts(x, combine, *broken, "swiglu", grouped_k=k)
    assert np.abs(np.asarray(y) - _reference(x, combine, experts)).max() < 1e-5


def test_every_token_on_one_expert():
    import jax.numpy as jnp

    from ray_tpu.ops import moe

    x, _, experts, _ = _layer(300, (8, 1, "softmax", None))
    combine = jnp.zeros((300, 8)).at[:, 5].set(1.0)
    y = moe.dropless_experts(x, combine, *experts, "swiglu", grouped_k=1)
    assert np.abs(np.asarray(y) - _reference(x, combine, experts)).max() < 1e-5
    dense = moe.dropless_experts(x, combine, *experts, "swiglu")
    assert float(jnp.abs(y - dense).max()) < 1e-5


def _tiles_by(tiles_by, monkeypatch):
    """Steer `dropless_experts(grouped_k=)` to the plain tiles (what the CPU
    takes) or to the kernels the TPU runs, in interpret mode."""
    from ray_tpu.ops import attention, moe

    if tiles_by != "plain":
        monkeypatch.setattr(attention, "_on_tpu", lambda: True)
        monkeypatch.setattr(moe, "_grouped_pallas", functools.partial(
            moe._grouped_pallas, interpret=True))


TILES_BY = ["plain", "pallas-interpret"]


@pytest.mark.parametrize("tiles_by", TILES_BY)
def test_an_empty_routing_reads_no_expert_and_gives_zeros(tiles_by, monkeypatch):
    """No assignment on an expert held here (or every token padding): no
    tile, so neither kernel runs. EVERY expert's weights are NaN: one block
    fetched and multiplied, even under rows that are all padding, would
    show."""
    import jax.numpy as jnp

    from ray_tpu.ops import moe

    _tiles_by(tiles_by, monkeypatch)
    x, combine, experts, k = _layer(16, (16, 2, "softmax", (4, 2)), D=128, F=128)
    broken = tuple(a * jnp.nan for a in experts)
    assert int(moe.dropless_groups(jnp.zeros_like(combine), k, moe.GROUP_ROWS)[3]) == 0
    y = moe.dropless_experts(x, jnp.zeros_like(combine), *broken, "swiglu", grouped_k=k)
    assert y.shape == x.shape and (np.asarray(y) == 0).all()


DECODE_ROUTINGS = ["64-of-64-top6-softmax", "12-of-192-top8-sigmoid-first"]


@pytest.mark.parametrize("tiles_by", TILES_BY)
@pytest.mark.parametrize("routing", DECODE_ROUTINGS)
@pytest.mark.parametrize("tokens", [1, 2, 4, 8])
def test_a_decode_steps_few_tokens_take_the_grouped_form(tokens, routing, tiles_by,
                                                         monkeypatch):
    """The one served form at a decode bucket's lanes: a tile of 128 rows of
    which `tokens` or fewer are real, against the dense reference. Of the 192
    experts 12 are held, so some of these steps route nothing here (both
    forms then give zeros) and some do: both kinds are among the seeds."""
    import jax.numpy as jnp

    from ray_tpu.ops import moe

    _tiles_by(tiles_by, monkeypatch)
    filled = []
    for seed in range(6):
        x, combine, experts, k = _layer(tokens, routing, D=128, F=128, seed=seed)
        dense = moe.dropless_experts(x, combine, *experts, "swiglu")
        grouped = moe.dropless_experts(x, combine, *experts, "swiglu", grouped_k=k)
        assert grouped.dtype == dense.dtype and grouped.shape == dense.shape
        assert float(jnp.abs(grouped - dense).max()) < 1e-5 * max(
            1.0, float(jnp.abs(dense).max()))
        filled.append(bool((combine > 0).any()))
        assert filled[-1] == (float(jnp.abs(dense).max()) > 0)
    assert any(filled) and (all(filled) or "192" in routing)


@pytest.mark.parametrize("tiles_by", TILES_BY)
@pytest.mark.parametrize("routing", DECODE_ROUTINGS)
def test_a_padding_lane_routes_nowhere(routing, tiles_by, monkeypatch):
    """A step of 3 lanes in the bucket of 4 through `_dropless_mlp`: the
    padding lane's logits choose experts no real lane does, whose weights are
    NaN. Outside `valid` it is routed nowhere: the real lanes' outputs are
    finite and the unpadded step's, the load counts the real lanes' alone."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt

    _tiles_by(tiles_by, monkeypatch)
    X, k, scoring, held = ROUTINGS[routing]
    first, count = held or (0, X)
    D, F = 128, 128
    cfg = gpt.GPTConfig(
        vocab_size=64, n_layers=1, n_heads=2, d_model=D, d_mlp=F, max_seq=32,
        mlp_type="moe", moe_routing="dropless", moe_experts=X, moe_top_k=k,
        moe_scoring=scoring, moe_held=held, activation="swiglu", dtype=jnp.float32)
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    # the padding lane is all in feature 0, which the real lanes lack; the
    # router sends feature 0 to the first k held experts and every other
    # feature away from them
    x = jnp.abs(jax.random.normal(ks[0], (4, 1, D))).at[:, :, 0].set(0.0)
    x = x.at[3].set(0.0).at[3, 0, 0].set(10.0)
    router = jax.random.normal(ks[1], (D, X))
    router = router.at[:, first:first + k].set(-5.0).at[0, first:first + k].set(5.0)
    if held:    # and the real lanes to some held expert past those
        router = router.at[1:, first + k].set(5.0)
    w_gate, w_in = (jax.random.normal(kk, (count, D, F)) * 0.3 for kk in ks[2:4])
    w_out = jax.random.normal(ks[4], (count, F, D)) * 0.3
    experts = tuple(a.at[:k].set(jnp.nan) for a in (w_gate, w_in, w_out))
    valid = jnp.asarray([True, True, True, False])[:, None]

    y, load = gpt._dropless_mlp(cfg, router, experts, x, x, valid=valid)
    alone, load3 = gpt._dropless_mlp(cfg, router, experts, x[:3], x[:3])
    assert np.isfinite(np.asarray(y[:3])).all() and float(jnp.abs(y[:3]).max()) > 0.01
    assert float(jnp.abs(y[:3] - alone).max()) < 1e-5 * float(jnp.abs(alone).max())
    assert (np.asarray(y[3]) == 0).all()
    assert np.allclose(np.asarray(load), np.asarray(load3))
    assert load.shape == ((5,) if held else (2,))
    # unmasked, the same lane does choose them
    bad, _ = gpt._dropless_mlp(cfg, router, experts, x, x)
    assert np.isnan(np.asarray(bad[3])).all()


@pytest.mark.parametrize("routing", list(ROUTINGS))
def test_bfloat16_operands_stay_within_the_dense_forms_distance(routing):
    import jax.numpy as jnp

    from ray_tpu.ops import moe

    x, combine, experts, k = _layer(100, routing, D=128, F=64)
    exact = _reference(x, combine, experts)
    lo = lambda a: a.astype(jnp.bfloat16)
    x16, experts16 = lo(x), tuple(lo(a) for a in experts)
    err = {}
    for form, kw in (("dense", {}), ("grouped", {"grouped_k": k})):
        y = moe.dropless_experts(x16, combine, *experts16, "swiglu", **kw)
        assert y.dtype == jnp.bfloat16
        err[form] = np.abs(np.asarray(y, np.float64) - exact).mean()
    assert 0 < err["grouped"] <= 1.05 * err["dense"], err


@pytest.mark.parametrize("stacked", [False, True], ids=["one-layer", "stacks"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("tokens", [40, 200])
def test_the_kernels_in_interpret_mode_are_the_plain_tiles(tokens, dtype, stacked):
    """The two Pallas kernels against the `lax` loop they stand for: the
    same tiles, each expert's blocks fetched out of the stacks at (layer,
    expert), the rows picked out of the tokens and added back to them inside
    the kernels."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import moe

    x, combine, experts, k = _layer(tokens, (8, 3, "softmax", None), D=256, F=128,
                                    dtype=dtype)
    layout = moe.dropless_groups(combine, k, 16)
    assert 0 < int(layout[3]) < layout[1].shape[0]
    layer = None
    if stacked:     # layer 1 of three, the others never to be read
        experts = tuple(jnp.stack([a * jnp.nan, a, a * jnp.nan]) for a in experts)
        layer = jnp.int32(1)
    plain = moe._grouped_plain(x, combine, *experts, "reglu", layer, *layout, 16)
    kernel = jax.jit(
        lambda x, c, g, u, d, *layout: moe._grouped_pallas(
            x, c, g, u, d, "reglu", layer, *layout, 16, interpret=True)
    )(x, combine, *experts, *layout)
    assert kernel.shape == plain.shape == x.shape
    assert kernel.dtype == plain.dtype == jnp.float32
    size = float(jnp.abs(plain).max())
    assert size > 0.1
    # float32 but for the addend's two bfloat16 terms (16 digits); under
    # bfloat16 a hidden value may round the other way after another order of sums
    tol = 2e-5 if dtype == "float32" else 1e-3
    assert float(jnp.abs(kernel - plain).max()) < tol * size


@pytest.mark.parametrize("tiles_by", TILES_BY)
def test_the_grouped_form_differentiates_as_the_dense_one(tiles_by, monkeypatch):
    """`forward` takes this form too and has been differentiable: the plain
    loop's derivative is the backward pass, of the kernels as well."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import moe

    _tiles_by(tiles_by, monkeypatch)
    x, combine, experts, k = _layer(40, (8, 3, "softmax", None), D=128, F=128)

    def loss(form):
        return lambda x, combine, *w: jnp.sum(jnp.sin(moe.dropless_experts(
            x, combine, *w, "swiglu", **form)))

    want = jax.grad(loss({}), argnums=(0, 1, 2, 3, 4))(x, combine, *experts)
    got = jax.grad(loss({"grouped_k": k}), argnums=(0, 1, 2, 3, 4))(x, combine, *experts)
    # a column a token did not choose is in no tile: the dense form's slope
    # there is what the expert WOULD add, which `dropless_combine` multiplies by 0
    want = (want[0], want[1] * (combine > 0), *want[2:])
    assert not (np.asarray(got[1])[np.asarray(combine == 0)]).any()
    for g, w in zip(got, want):
        assert g.shape == w.shape and float(jnp.abs(w).max()) > 1e-3
        # the kernels' forward keeps 16 digits of a row's addend: so does cos(y)
        tol = 1e-4 if tiles_by == "plain" else 1e-3
        assert float(jnp.abs(g - w).max()) < tol * max(1.0, float(jnp.abs(w).max()))


def test_a_long_step_goes_through_in_pieces(monkeypatch):
    import jax.numpy as jnp

    from ray_tpu.ops import moe

    monkeypatch.setattr(moe, "_GROUP_TOKENS", 64)
    x, combine, experts, k = _layer(200, "64-of-64-top6-softmax")
    dense = moe.dropless_experts(x, combine, *experts, "swiglu")
    grouped = moe.dropless_experts(x, combine, *experts, "swiglu", grouped_k=k)
    assert grouped.shape == dense.shape
    assert float(jnp.abs(grouped - dense).max()) < 1e-5


@pytest.mark.parametrize("rows,cols,itemsize,want", [
    (2048, 7168, 2, 512), (768, 2560, 2, 1280), (128, 256, 4, 256), (16, 100, 4, 100)])
def test_a_weight_block_divides_the_width_within_its_budget(rows, cols, itemsize, want):
    from ray_tpu.ops import moe

    got = moe._weight_tile(rows, cols, itemsize)
    assert got == want and cols % got == 0
    assert got == cols or rows * got * itemsize <= moe._BLOCK_BYTES
