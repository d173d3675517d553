"""Mixture-of-Experts layer with expert parallelism (absent from the
reference — SURVEY.md §2.6 "Expert parallel (EP/MoE): Absent"; first-class
here per the build plan).

TPU-idiomatic GShard/Switch design: token→expert routing is expressed as
dense one-hot dispatch/combine tensors and einsums — static shapes, no
sorts/gathers, everything lands on the MXU, and under pjit the expert axis
of the weights shards over the `ep` mesh axis (XLA inserts the all-to-alls).

Top-1 (Switch) and top-2 (GShard) gating with capacity dropping and the
standard load-balancing auxiliary loss.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 8
    top_k: int = 2                  # 1 = Switch, 2 = GShard
    capacity_factor: float = 1.25
    d_model: int = 768
    d_ff: int = 3072
    aux_loss_weight: float = 0.01
    activation: str = "gelu"        # gelu | swiglu (adds w_gate per expert)
    dtype: object = jnp.bfloat16

    def capacity(self, num_tokens: int) -> int:
        c = int(self.capacity_factor * num_tokens * self.top_k / self.num_experts)
        return max(c, 4)


def moe_init(rng, cfg: MoEConfig) -> Dict[str, jnp.ndarray]:
    """Params with logical dims:
    w_router (embed, experts); w_in (experts, embed, mlp); w_out (experts, mlp, embed).
    """
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    E, D, F = cfg.num_experts, cfg.d_model, cfg.d_ff
    s = 0.02
    params = {
        "w_router": jax.random.normal(k1, (D, E), jnp.float32) * s,
        "w_in": jax.random.normal(k2, (E, D, F), jnp.float32) * s,
        "w_out": jax.random.normal(k3, (E, F, D), jnp.float32) * s,
    }
    if cfg.activation == "swiglu":
        params["w_gate"] = jax.random.normal(k4, (E, D, F), jnp.float32) * s
    return params


def _one_hot_dispatch(gate_idx, probs, mask, capacity, num_experts):
    """Build dispatch/combine slices for one routing choice.

    gate_idx [N] expert per token; mask [N] tokens still in play;
    returns (dispatch [N, E, C] one-hot, gate_probs [N] prob of this choice,
    kept [N] capacity mask).
    """
    expert_mask = jax.nn.one_hot(gate_idx, num_experts, dtype=jnp.float32) * mask[:, None]
    # Position of each token within its expert's buffer (cumulative count).
    position = jnp.cumsum(expert_mask, axis=0) * expert_mask  # [N, E]
    position = position.sum(axis=-1) - 1.0                    # [N], -1 if masked
    kept = (position >= 0) & (position < capacity)
    pos_oh = jax.nn.one_hot(position.astype(jnp.int32), capacity, dtype=jnp.float32)
    dispatch = expert_mask[:, :, None] * pos_oh[:, None, :] * kept[:, None, None]
    gate_probs = (probs * expert_mask).sum(axis=-1)
    return dispatch, gate_probs, kept


def moe_router(x_flat, w_router, cfg: MoEConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x_flat [N, D] → (combine [N, E, C], aux_loss scalar).

    combine holds the gating weight of each (token, expert, slot); dispatch
    is its boolean support.
    """
    N = x_flat.shape[0]
    E = cfg.num_experts
    C = cfg.capacity(N)
    logits = x_flat.astype(jnp.float32) @ w_router  # router math in f32
    probs = jax.nn.softmax(logits, axis=-1)        # [N, E]

    gate1 = jnp.argmax(probs, axis=-1)
    disp1, p1, kept1 = _one_hot_dispatch(
        gate1, probs, jnp.ones(N, jnp.float32), C, E
    )

    # Load-balancing aux loss (Switch eq. 4): E * Σ_e f_e · P_e
    me = jax.nn.one_hot(gate1, E, dtype=jnp.float32).mean(axis=0)  # token fraction
    pe = probs.mean(axis=0)                                        # mean router prob
    aux = E * jnp.sum(me * pe)

    if cfg.top_k == 1:
        combine = disp1 * p1[:, None, None]
        return combine, aux

    # Top-2: mask out the first choice, route the remainder.
    probs2 = probs * (1.0 - jax.nn.one_hot(gate1, E, dtype=jnp.float32))
    gate2 = jnp.argmax(probs2, axis=-1)
    # Second-choice buffer positions start after all first-choice tokens.
    first_counts = jax.nn.one_hot(gate1, E, dtype=jnp.float32).sum(axis=0)  # [E]
    expert_mask2 = jax.nn.one_hot(gate2, E, dtype=jnp.float32)
    position2 = jnp.cumsum(expert_mask2, axis=0) * expert_mask2
    position2 = (position2 + first_counts[None, :] * expert_mask2).sum(axis=-1) - 1.0
    kept2 = (position2 >= 0) & (position2 < C)
    pos2_oh = jax.nn.one_hot(position2.astype(jnp.int32), C, dtype=jnp.float32)
    disp2 = expert_mask2[:, :, None] * pos2_oh[:, None, :] * kept2[:, None, None]
    p2 = (probs * expert_mask2).sum(axis=-1)

    # Renormalize the two gate probs over the kept choices.
    denom = p1 * kept1 + p2 * kept2
    denom = jnp.maximum(denom, 1e-9)
    combine = disp1 * (p1 * kept1 / denom)[:, None, None] + disp2 * (
        p2 * kept2 / denom
    )[:, None, None]
    return combine, aux


def moe_forward(params, x, cfg: MoEConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x [..., D] → (y [..., D], aux_loss). Shard w_in/w_out on `ep` via
    logical dim "experts"; the dispatch einsum's [E, C, D] intermediate then
    shards on ep and XLA places the token all-to-alls on ICI."""
    orig_shape = x.shape
    D = orig_shape[-1]
    x_flat = x.reshape(-1, D)

    combine, aux = moe_router(x_flat, params["w_router"], cfg)
    combine = combine.astype(cfg.dtype)
    dispatch = (combine > 0).astype(cfg.dtype)

    xc = x_flat.astype(cfg.dtype)
    expert_in = jnp.einsum("nec,nd->ecd", dispatch, xc)         # [E, C, D]
    h = jnp.einsum("ecd,edf->ecf", expert_in, params["w_in"].astype(cfg.dtype))
    if cfg.activation == "swiglu":
        g = jnp.einsum("ecd,edf->ecf", expert_in, params["w_gate"].astype(cfg.dtype))
        h = jax.nn.silu(g) * h
    else:
        h = jax.nn.gelu(h)
    expert_out = jnp.einsum("ecf,efd->ecd", h, params["w_out"].astype(cfg.dtype))
    y = jnp.einsum("nec,ecd->nd", combine, expert_out)          # [N, D]
    return y.reshape(orig_shape), cfg.aux_loss_weight * aux


MOE_LOGICAL_DIMS = {
    "w_router": ("embed", "experts"),
    "w_in": ("experts", "embed", "mlp"),
    "w_out": ("experts", "mlp", "embed"),
    "w_gate": ("experts", "embed", "mlp"),
}


# ------------------------------------------------------- dropless top-k
# Beside the capacity-dropping GShard path above (training configurations):
# routing for any k with NO capacity, so no token is ever dropped, and gate
# weights that are a softmax over the k kept logits (the same as a softmax
# over all experts renormalised over the kept ones). Static shapes without a
# capacity leave two schedules of the same sum (`dropless_experts`): every
# expert applied to every token under an [N, X] combine matrix that is zero
# where a token did not choose the expert (dense: the reference), and the
# assignments sorted by expert and each expert's rows, padded to whole row
# tiles, put through that expert once (`grouped`: every served step, of one
# token or of a chunk): as many tiles as the step's routing fills, a run-time
# count, and none at all for a routing that chose no expert held here.


def dropless_route(logits, top_k: int, scoring: str = "softmax", scale: float = 1.0, bias=None):
    """logits [N, X] (any float) -> (idx [N, k] int32, weights [N, k] f32): the k largest
    logits of each token and, as `scoring` says, the softmax over those k ("softmax") or
    their sigmoids over the sum of those k, times `scale` ("sigmoid": it keeps the logits'
    order). `bias` [X]: a selection bias, `_route_biased` (chosen by score + bias)."""
    if bias is not None: return _route_biased(logits, top_k, scoring, scale, bias)
    vals, idx = jax.lax.top_k(logits.astype(jnp.float32), top_k)
    if scoring == "softmax":
        return idx.astype(jnp.int32), jax.nn.softmax(vals, axis=-1)
    if scoring != "sigmoid":
        raise ValueError(f"dropless scoring {scoring!r}: softmax | sigmoid")
    kept = jax.nn.sigmoid(vals)
    return idx.astype(jnp.int32), kept / kept.sum(axis=-1, keepdims=True) * scale


def dropless_combine(idx, weights, num_experts: int):
    """[N, k] choices -> combine [N, X] f32 (a token's gate weight at each
    expert it chose, 0 elsewhere)."""
    return (jax.nn.one_hot(idx, num_experts, dtype=jnp.float32)
            * weights[..., None]).sum(axis=-2)


def _gated(act: str, g, u):
    if act == "reglu":
        return jax.nn.relu(g) * u
    if act == "swiglu":
        return jax.nn.silu(g) * u
    return _ungated(act, g, u)      # an expert of two matrices, or a refusal


# Rows a tile of the grouped form: the MXU's own height. An expert with one
# token still costs a whole tile, whose time is the expert's bytes, not its
# rows (set on the chip, PERF.md §6, PR 35); a constant, not a config field.
GROUP_ROWS = 128


def dropless_groups(combine, top_k: int, rows_tile: int):
    """The grouped form's layout, plain JAX, from combine [N, X] whose rows
    have at most `top_k` nonzero columns: expert e's rows are the tokens that
    chose it, in their order, and its first row starts a whole tile of
    `rows_tile`. Returns

    * rows [N, X] int32: token n's row in expert e's group, -1 where it did
      not choose e;
    * tile_expert [T] int32: the expert whose weights tile t meets;
    * tile_first [T] int32: the row of that expert's group tile t starts at;
    * tiles, int32 scalar: the tiles the routing fills, the first ones.

    Tile t holds the tokens with rows[:, tile_expert[t]] in tile_first[t] ..
    + rows_tile - 1. T = N * min(top_k, X) // rows_tile + X bounds every
    routing: a shape. What is computed is `tiles`, a run-time value."""
    N, X = combine.shape
    T = N * min(top_k, X) // rows_tile + X
    chosen = combine > 0
    count = jnp.cumsum(chosen, axis=0, dtype=jnp.int32)          # [N, X]
    tiles_of = -(-count[-1] // rows_tile)                        # [X]
    tile_end = jnp.cumsum(tiles_of)
    t = jnp.arange(T, dtype=jnp.int32)
    past = t[:, None] >= tile_end[None, :]                       # [T, X] experts done
    tile_first = (t - (past * tiles_of[None, :]).sum(axis=1)) * rows_tile
    return (jnp.where(chosen, count - 1, -1),
            jnp.minimum(past.sum(axis=1), X - 1).astype(jnp.int32),
            tile_first.astype(jnp.int32), tile_end[-1].astype(jnp.int32))


def _one(a, layer, e):
    """Expert e's slice of a stacked weight, read where it lies."""
    if layer is None:
        return jax.lax.dynamic_index_in_dim(a, e, 0, keepdims=False)
    return jax.lax.dynamic_slice(a, (layer, e, 0, 0), (1, 1) + a.shape[2:])[0, 0]


def _grouped_plain(x, combine, w_gate, w_in, w_out, activation, layer,
                   rows, tile_expert, tile_first, tiles, rows_tile):
    """y [N, D] f32 of the grouped form from its layout (`dropless_groups`):
    the rows of tile t picked out of the tokens, through expert
    tile_expert[t], each weighted by its token's combine weight and added to
    that token, for the first `tiles` tiles; a trip past them is skipped. A
    `lax` loop that differentiates: the path off the TPU, the kernels'
    backward pass, and what they are held to."""
    dt = x.dtype

    def run(t, y):
        e = tile_expert[t]
        mine = jax.lax.dynamic_index_in_dim(rows, e, 1, keepdims=False) - tile_first[t]
        pick = mine[None, :] == jnp.arange(rows_tile)[:, None]           # [TM, N]
        tile = jnp.dot(pick.astype(dt), x)            # one 1 a row: exact
        g, u = (None if w is None else jnp.dot(     # w_gate None: two matrices, w_in [F, D]
                    tile, (_one(w, layer, e).T if w_gate is None else _one(w, layer, e)).astype(dt),
                    preferred_element_type=jnp.float32)
                for w in (w_gate, w_in))
        o = jnp.dot(_gated(activation, g, u).astype(dt),
                    _one(w_out, layer, e).astype(dt),
                    preferred_element_type=jnp.float32)
        w = jax.lax.dynamic_index_in_dim(combine, e, 1, keepdims=False)  # [N]
        return y + jnp.dot(pick.T.astype(jnp.float32), o,
                           precision=jax.lax.Precision.HIGHEST) * w[:, None]

    def body(t, y):
        return jax.lax.cond(t < tiles, run, lambda t, y: y, t, y)

    return jax.lax.fori_loop(0, tile_expert.shape[0], body,
                             jnp.zeros(x.shape, jnp.float32))


# Bytes of one weight block a grid step of the grouped kernels fetches (two of
# them for gate and up, each double-buffered); 4 MiB read no faster on the chip
# (PERF.md §6, PR 35).
_BLOCK_BYTES = 2 << 20


def _weight_tile(rows: int, cols: int, itemsize: int) -> int:
    """The most columns (a multiple of 128 that divides `cols`, or all of
    them) of a [rows, cols] weight whose block stays within `_BLOCK_BYTES`."""
    if cols % 128:
        return cols
    best = 128
    for n in range(1, cols // 128 + 1):
        if (cols // 128) % n == 0 and rows * n * 128 * itemsize <= _BLOCK_BYTES:
            best = n * 128
    return best


# Tokens one call of the kernels keeps in fast memory (the step's tokens as
# slabs of the model width, and a block of their float32 sums): a prefill
# chunk of the serving cells; a longer step goes through in such pieces.
_GROUP_TOKENS = 512


def _grouped_pallas(x, combine, w_gate, w_in, w_out, activation, layer,
                    rows, tile_expert, tile_first, tiles, rows_tile, interpret=False):
    """`_grouped_plain` as two Pallas kernels over a grid whose tile axis is
    `tiles`, a run-time bound, so what is fetched and multiplied follows the
    routing. Each step's weight block comes out of the [L, X, ...] stacks at
    (layer, tile_expert[t]) by the block index itself, as does that expert's
    column of `rows` and of `combine`: no slice of a stack is ever copied, and
    nothing of the layout's padded size exists beside the hidden rows
    [T * rows_tile, F]:

    * hidden: grid (tiles, slabs of the model width). The tokens stay in fast
      memory; a tile's rows are picked out of them by a one-hot product (exact:
      one 1 a row), then meet the expert's [slab, F] blocks of gate and up,
      summed over the slabs in float32 scratch; the gated product is rounded
      once, to the operands' type.
    * down: grid (column blocks of the model width, tiles). A tile's hidden
      rows through the expert's [F, block] of down; each row, weighted in
      float32 by its token's combine weight, is added to that token's sums
      [N, block] f32 by the transposed one-hot product, the addend split into
      two bfloat16 terms so that the sum keeps float32's digits.

    A routing that fills no tile (every assignment on experts held elsewhere,
    or every token padding) runs neither kernel: it fetches no block of any
    expert and gives zeros. x [N, D], N at most `_GROUP_TOKENS`."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N, D = x.shape
    dt = x.dtype
    if layer is None:       # one layer's weights: a stack of one
        w_gate, w_in, w_out = (a[None] for a in (w_gate, w_in, w_out))
        layer = 0
    F = w_gate.shape[-1]
    TM = rows_tile
    T = tile_expert.shape[0]
    # the model width a block: rows of a [td, F] slab of gate and up, columns
    # of an [F, td] block of down
    td = _weight_tile(F, D, w_gate.dtype.itemsize)
    nd = D // td
    Np = -(-N // 128) * 128                              # whole lane tiles of tokens
    slabs = jnp.pad(x, ((0, Np - N), (0, 0))).reshape(Np, nd, td).transpose(1, 0, 2)
    rows = jnp.pad(rows, ((0, Np - N), (0, 0)), constant_values=-1).T[:, None]
    weights = jnp.pad(combine, ((0, Np - N), (0, 0))).T[:, None]       # [X, 1, Np]
    scalars = (tile_expert, tile_first, jnp.asarray(layer, jnp.int32).reshape(1))
    params = pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=64 << 20)

    def picked(t, first, rows_ref):
        """[TM, Np] bool: row j of tile t is token n's."""
        return rows_ref[...] - first[t] == jax.lax.broadcasted_iota(
            jnp.int32, (TM, Np), 0)

    def hidden(te, first, l, rows_ref, x_ref, wg_ref, wu_ref, h_ref, g_acc, u_acc):
        k = pl.program_id(1)

        @pl.when(k == 0)
        def _():
            g_acc[...] = jnp.zeros_like(g_acc)
            u_acc[...] = jnp.zeros_like(u_acc)

        pick = jnp.where(picked(pl.program_id(0), first, rows_ref), 1.0, 0.0).astype(dt)
        tile = jnp.dot(pick, x_ref[k], preferred_element_type=jnp.float32).astype(dt)
        g_acc[...] += jnp.dot(tile, wg_ref[...].astype(dt),
                              preferred_element_type=jnp.float32)
        u_acc[...] += jnp.dot(tile, wu_ref[...].astype(dt),
                              preferred_element_type=jnp.float32)

        @pl.when(k == nd - 1)
        def _():
            h_ref[...] = _gated(activation, g_acc[...], u_acc[...]).astype(dt)

    def down(te, first, l, rows_ref, wt_ref, h_ref, wd_ref, y_ref):
        t = pl.program_id(1)

        @pl.when(t == 0)
        def _():
            y_ref[...] = jnp.zeros_like(y_ref)

        mine = picked(t, first, rows_ref)
        weight = jnp.sum(jnp.where(mine, wt_ref[...], 0.0), axis=1, keepdims=True)
        o = jnp.dot(h_ref[...], wd_ref[...].astype(dt),
                    preferred_element_type=jnp.float32) * weight       # [TM, td]
        pick = jnp.where(mine, 1.0, 0.0).astype(jnp.bfloat16)
        hi = o.astype(jnp.bfloat16)
        lo = (o - hi.astype(jnp.float32)).astype(jnp.bfloat16)
        back = lambda a: jax.lax.dot_general(      # pick^T a: each row to its token
            pick, a, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        y_ref[...] += back(hi) + back(lo)

    slab = pl.BlockSpec((None, None, td, F), lambda t, k, te, f, l: (l[0], te[t], k, 0))
    column = pl.BlockSpec((None, 1, Np), lambda n, t, te, f, l: (te[t], 0, 0))

    def kernels():
        h = pl.pallas_call(
            hidden, out_shape=jax.ShapeDtypeStruct((T * TM, F), dt),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3, grid=(tiles, nd),
                in_specs=[pl.BlockSpec((None, 1, Np), lambda t, k, te, f, l: (te[t], 0, 0)),
                          pl.BlockSpec((nd, Np, td), lambda t, k, te, f, l: (0, 0, 0)),
                          slab, slab],
                out_specs=pl.BlockSpec((TM, F), lambda t, k, te, f, l: (t, 0)),
                scratch_shapes=[pltpu.VMEM((TM, F), jnp.float32)] * 2),
            compiler_params=params, interpret=interpret, name="moe_grouped_hidden",
        )(*scalars, rows, slabs, w_gate, w_in)
        y = pl.pallas_call(
            down, out_shape=jax.ShapeDtypeStruct((Np, D), jnp.float32),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3, grid=(nd, tiles),
                in_specs=[column, column,
                          pl.BlockSpec((TM, F), lambda n, t, te, f, l: (t, 0)),
                          pl.BlockSpec((None, None, F, td),
                                       lambda n, t, te, f, l: (l[0], te[t], 0, n))],
                out_specs=pl.BlockSpec((Np, td), lambda n, t, te, f, l: (0, n))),
            compiler_params=params, interpret=interpret, name="moe_grouped_down",
        )(*scalars, rows, weights, h, w_out)
        return y[:N]

    return jax.lax.cond(tiles > 0, kernels, lambda: jnp.zeros((N, D), jnp.float32))


@functools.partial(jax.jit, static_argnames=("activation", "k", "rows_tile", "kernels"))
def _grouped_run(x, combine, w_gate, w_in, w_out, layer, *, activation, k,
                 rows_tile, kernels):
    """The grouped form from operands to y [N, D] f32, by the kernels or the
    plain loop. Jitted for its trace alone: a server's programs differ in
    table widths far more often than in tokens, and every program of one
    token count takes this trace (two kernels' worth) from the cache."""
    run = (_grouped_pallas_ungated if w_gate is None else _grouped_pallas) if kernels else _grouped_plain
    return run(x, combine, w_gate, w_in, w_out, activation, layer,
               *dropless_groups(combine, k, rows_tile), rows_tile)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 7))
def _grouped_experts(x, combine, w_gate, w_in, w_out, activation, layer, k):
    """The grouped form, y [N, D] f32: the kernels on the TPU (widths of whole
    lane tiles), else the plain loop, whose derivative is the backward pass
    of both."""
    from .attention import _on_tpu

    D, F = _lane_widths(w_gate, w_in)
    return _grouped_run(
        x, combine, w_gate, w_in, w_out, layer, activation=activation, k=k,
        rows_tile=GROUP_ROWS, kernels=_on_tpu() and D % 128 == 0 and F % 128 == 0)


def _grouped_fwd(x, combine, w_gate, w_in, w_out, activation, layer, k):
    return (_grouped_experts(x, combine, w_gate, w_in, w_out, activation, layer, k),
            (x, combine, w_gate, w_in, w_out, layer))


def _grouped_bwd(activation, k, saved, g):
    *operands, layer = saved
    _, vjp = jax.vjp(functools.partial(
        _grouped_run, layer=layer, activation=activation, k=k,
        rows_tile=GROUP_ROWS, kernels=False), *operands)
    return (*vjp(g), None)


_grouped_experts.defvjp(_grouped_fwd, _grouped_bwd)


def dropless_experts(x, combine, w_gate, w_in, w_out, activation: str,
                     layer=None, grouped_k: int = 0):
    """y [N, D] = sum_e combine[n, e] * W_out,e( act(W_gate,e x) * (W_in,e x) ).
    `w_gate` None: an expert is TWO matrices, W_out,e act(W_in,e x) ("relu2"), and `w_in`
    is kept [X, F, D], out-features first as `w_out` is: the model width fills whole lane
    tiles whatever F (1856 = 14.5 x 128: kept [D, F] the chip copied the whole stack a call).

    x [N, D]; combine [N, X] f32; weights [X, D, F] / [X, F, D], or with `layer` (a traced
    index) the whole stacks [L, X, ...] of which layer `layer` is read in place. Two forms:
    * dense (default): one [N, D] x [D, X*F] product for gate and up, the combine weights
      folded into the hidden activations, one [N, X*F] x [X*F, D] product down. Every
      expert meets every token: the reference the tests hold the other form to.
    * `grouped_k` = k > 0 (a token's nonzero columns are at most k): the
      tokens that chose an expert are its rows (`dropless_groups`), each
      expert's rows through its gate, up and down products once, a tile of
      `GROUP_ROWS` rows at a time and only the tiles the routing fills; a
      token's k rows weighted by its combine weights and summed in float32.
      An expert nobody chose is never read, a column or a row that is zero
      costs nothing, and a routing with no assignment here reads no expert:
      the cost follows the routing by itself, for one decode lane as for a
      prefill chunk, so every served step takes this form. On the TPU the
      tiles are two Pallas kernels that fetch each expert's blocks out of the
      stacks in place (`_grouped_pallas`).
    """
    N = x.shape[0]
    dt = x.dtype

    if grouped_k:       # in pieces of the tokens the kernels keep in fast memory
        return jnp.concatenate([
            _grouped_experts(x[i:i + _GROUP_TOKENS], combine[i:i + _GROUP_TOKENS],
                             w_gate, w_in, w_out, activation, layer, grouped_k)
            for i in range(0, N, _GROUP_TOKENS)]).astype(dt)
    if layer is not None:
        w_gate, w_in, w_out = (
            None if a is None else jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False)
            for a in (w_gate, w_in, w_out))
    g = None if w_gate is None else jnp.einsum("nd,xdf->nxf", x, w_gate.astype(dt))
    u = jnp.einsum("nd,xfd->nxf" if w_gate is None else "nd,xdf->nxf", x, w_in.astype(dt))
    h = _gated(activation, g, u) * combine[..., None].astype(dt)
    return jnp.einsum("nxf,xfd->nd", h, w_out.astype(dt))


def dropless_load(combine, valid=None, top_k: int = 0):
    """(experts with at least one token, the busiest expert's share of the
    assignments) of one layer's routing, f32 scalars; `valid` [N] leaves
    padding tokens out. `combine` cut to the columns of the experts held
    here, with `top_k` given: also (the assignments that fell on those
    columns, all the tokens' assignments = tokens x top_k, 1 if none fell
    here: the layer's routing is empty and its product reads no expert)."""
    chosen = combine > 0
    if valid is not None:
        chosen = chosen & valid[:, None]
    per = chosen.sum(axis=0).astype(jnp.float32)         # [X] assignments
    here = per.sum()
    load = ((per > 0).sum().astype(jnp.float32), per.max() / jnp.maximum(here, 1.0))
    if not top_k:
        return load
    tokens = combine.shape[0] if valid is None else valid.sum()
    return (*load, here, jnp.asarray(tokens * top_k, jnp.float32),
            (here == 0).astype(jnp.float32))


# ------------------------------------------- what stands below the kernels
# New code of this module goes HERE, behind the functions the served programs'
# Pallas bodies name by line (ROADMAP D20), each reached from its caller above.


def _lane_widths(w_gate, w_in):
    """(D, F) as `_grouped_experts`' lane-tile test reads them (its line is keyed: D20).
    Gated experts keep [.., D, F]: both widths are lane dimensions of a block and must be
    whole tiles of 128. Two-matrix experts keep `w_in` [.., F, D]: F is a SUBLANE dimension
    of every weight block (whole tiles of 8) and the full extent of the hidden rows'
    blocks, so where it is a multiple of 8 it is reported as one lane tile."""
    if w_gate is not None:
        return w_in.shape[-2:]
    F, D = w_in.shape[-2:]
    return D, 128 if F % 8 == 0 else F


def _route_biased(logits, top_k: int, scoring: str, scale: float, bias):
    """`dropless_route` under a selection bias [X]: scores s = sigmoid(logits)
    over all experts, the k experts with the largest s + bias CHOSEN, the
    weights the chosen experts' s (without the bias) over their sum, times
    `scale`: the bias moves which experts serve a token, never how much."""
    if scoring != "sigmoid":
        raise ValueError("a selection bias goes with sigmoid scores")
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, idx = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
    kept = jnp.take_along_axis(scores, idx, axis=-1)
    return idx.astype(jnp.int32), kept / kept.sum(axis=-1, keepdims=True) * scale


def _ungated(act: str, g, u):
    """The activation of an expert WITHOUT a gate matrix (g None)."""
    if g is None and act == "relu2":
        return jnp.square(jax.nn.relu(u))
    raise ValueError(
        f"dropless experts are gated (reglu | swiglu) or of two matrices (relu2, "
        f"no gate), got {act!r} with{'out' if g is None else ''} a gate")


def _grouped_pallas_ungated(x, combine, w_gate, w_in, w_out, activation, layer,
                            rows, tile_expert, tile_first, tiles, rows_tile,
                            interpret=False):
    """`_grouped_pallas` for experts of two matrices (`w_gate` None): the same
    layout, grids and down kernel, and a hidden kernel that streams ONE
    [slab, F] block a step (`moe_grouped_hidden_ungated`) where the gated one
    streams two: no stand-in matrix, no bytes read for a gate that is not
    there. `w_in` [L, X, F, D] (`dropless_experts`): a step's block is [F, slab],
    met by the tile's [rows, slab] over the slab. A function of its own, and not
    a branch of `_grouped_pallas`, because that one's kernels are keyed by their
    lines (ROADMAP D20)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N, D = x.shape
    dt = x.dtype
    if layer is None:       # one layer's weights: a stack of one
        w_in, w_out = w_in[None], w_out[None]
        layer = 0
    F = w_in.shape[-2]
    TM = rows_tile
    T = tile_expert.shape[0]
    td = _weight_tile(F, D, w_in.dtype.itemsize)
    nd = D // td
    Np = -(-N // 128) * 128                              # whole lane tiles of tokens
    slabs = jnp.pad(x, ((0, Np - N), (0, 0))).reshape(Np, nd, td).transpose(1, 0, 2)
    rows = jnp.pad(rows, ((0, Np - N), (0, 0)), constant_values=-1).T[:, None]
    weights = jnp.pad(combine, ((0, Np - N), (0, 0))).T[:, None]       # [X, 1, Np]
    scalars = (tile_expert, tile_first, jnp.asarray(layer, jnp.int32).reshape(1))
    params = pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=64 << 20)

    def picked(t, first, rows_ref):
        return rows_ref[...] - first[t] == jax.lax.broadcasted_iota(
            jnp.int32, (TM, Np), 0)

    def hidden(te, first, l, rows_ref, x_ref, wu_ref, h_ref, u_acc):
        k = pl.program_id(1)

        @pl.when(k == 0)
        def _():
            u_acc[...] = jnp.zeros_like(u_acc)

        pick = jnp.where(picked(pl.program_id(0), first, rows_ref), 1.0, 0.0).astype(dt)
        tile = jnp.dot(pick, x_ref[k], preferred_element_type=jnp.float32).astype(dt)
        u_acc[...] += jax.lax.dot_general(           # [TM, slab] x [F, slab] over the slab
            tile, wu_ref[...].astype(dt), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(k == nd - 1)
        def _():
            h_ref[...] = _ungated(activation, None, u_acc[...]).astype(dt)

    def down(te, first, l, rows_ref, wt_ref, h_ref, wd_ref, y_ref):
        t = pl.program_id(1)

        @pl.when(t == 0)
        def _():
            y_ref[...] = jnp.zeros_like(y_ref)

        mine = picked(t, first, rows_ref)
        weight = jnp.sum(jnp.where(mine, wt_ref[...], 0.0), axis=1, keepdims=True)
        o = jnp.dot(h_ref[...], wd_ref[...].astype(dt),
                    preferred_element_type=jnp.float32) * weight       # [TM, td]
        pick = jnp.where(mine, 1.0, 0.0).astype(jnp.bfloat16)
        hi = o.astype(jnp.bfloat16)
        lo = (o - hi.astype(jnp.float32)).astype(jnp.bfloat16)
        back = lambda a: jax.lax.dot_general(      # pick^T a: each row to its token
            pick, a, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        y_ref[...] += back(hi) + back(lo)

    column = pl.BlockSpec((None, 1, Np), lambda n, t, te, f, l: (te[t], 0, 0))

    def kernels():
        h = pl.pallas_call(
            hidden, out_shape=jax.ShapeDtypeStruct((T * TM, F), dt),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3, grid=(tiles, nd),
                in_specs=[pl.BlockSpec((None, 1, Np), lambda t, k, te, f, l: (te[t], 0, 0)),
                          pl.BlockSpec((nd, Np, td), lambda t, k, te, f, l: (0, 0, 0)),
                          pl.BlockSpec((None, None, F, td),
                                       lambda t, k, te, f, l: (l[0], te[t], 0, k))],
                out_specs=pl.BlockSpec((TM, F), lambda t, k, te, f, l: (t, 0)),
                scratch_shapes=[pltpu.VMEM((TM, F), jnp.float32)]),
            compiler_params=params, interpret=interpret,
            name="moe_grouped_hidden_ungated",
        )(*scalars, rows, slabs, w_in)
        y = pl.pallas_call(
            down, out_shape=jax.ShapeDtypeStruct((Np, D), jnp.float32),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3, grid=(nd, tiles),
                in_specs=[column, column,
                          pl.BlockSpec((TM, F), lambda n, t, te, f, l: (t, 0)),
                          pl.BlockSpec((None, None, F, td),
                                       lambda n, t, te, f, l: (l[0], te[t], 0, n))],
                out_specs=pl.BlockSpec((Np, td), lambda n, t, te, f, l: (0, n))),
            compiler_params=params, interpret=interpret, name="moe_grouped_down",
        )(*scalars, rows, weights, h, w_out)
        return y[:N]

    return jax.lax.cond(tiles > 0, kernels, lambda: jnp.zeros((N, D), jnp.float32))
