"""Attention over the paged KV pool: the one rule on which form a paged
program's attention takes, the four forms behind one entry, the two kernels,
and the host's count of the keys they cover.

The pool is {"k", "v"} of [depth, NB, BS, row] (`models/gpt.py` `kv_layout`;
a latent pool "k" alone, its values the key rows' first columns); a lane
names its blocks through a block table, block 0 the null block. A program of
`tokens` tokens a lane over tables `width` blocks wide takes ONE of

  * `DECODE_KERNEL` — one token a lane, on the chip: `paged_decode_attention`
    reads the pool as it lies, each lane's own blocks through its table from
    its window's first block to the block of its own position, whatever the
    other lanes hold and however wide the table.
  * `ONE_SHOT` — a table of one tile (`paged_attn_tiling`): gathered whole
    and soft-maxed in one shot.
  * `CHUNK_KERNEL` — more tokens over a wider table, on the chip:
    `paged_chunk_attention`, the key loop's tiles, bounds, mask and online
    softmax with the scores and the accumulator in fast memory.
  * `KEY_LOOP` — a loop over key tiles with an online softmax, each trip
    gathering only its tile's blocks through the table: the only form a CPU
    has for a table wider than a tile, what the tests hold the chunk kernel
    to, and the form of rows that fill no lane tile (heads of 64).

`paged_attn_form` is the rule, from shapes and the platform alone:
`PagedAttention` decides with it and the host counts with it
(`paged_attn_cover`, `serve/engine/engine.py`). Bounds are run-time values
from the step's positions and the layer's window (`paged_attn_trips`,
`paged_decode_span`), so shapes and program keys depend on (tokens, width)
alone; the mask, the same in every form, decides what a query sees, and the
bounds only skip keys for which it is false everywhere. Shapes follow [batch,
K/V heads, query rows, head_dim]: the R query heads of a K/V head fold into
its query axis, so multi-head attention is the same operations with R = 1.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import attention

_NEG_INF = -1e30
NO_WINDOW = 1 << 30     # a window no sequence reaches: a global layer's

# Keys one trip of the key loop covers. A table of at most this many keys is
# attended in one shot, with no loop at all. Set on the chip (PERF.md §6,
# PR 29); a constant, not a field of `GPTConfig`.
_ATTN_TILE_KEYS = 1024

DECODE_KERNEL, CHUNK_KERNEL, ONE_SHOT, KEY_LOOP = (
    "decode_kernel", "chunk_kernel", "one_shot", "key_loop")


def paged_attn_tiling(width: int, block_size: int):
    """(blocks a tile, tiles) into which a block table `width` blocks wide
    is cut: from static shapes alone."""
    tile = max(1, _ATTN_TILE_KEYS // block_size)
    return (width, 1) if width <= tile else (tile, -(-width // tile))


def paged_attn_form(tokens: int, width: int, block_size: int, key_row: int,
                    value_row: int, dtype) -> str:
    """The form of the attention of a paged program of `tokens` tokens a lane
    over tables `width` blocks wide, blocks of `block_size` tokens in a pool of
    `dtype`, a K/V head's key row `key_row` and value row `value_row` wide (a
    latent model's: the padded row and the values inside it). From shapes and
    the platform alone (`attention._on_tpu`, asked as that module's attribute:
    a rehearsal steers it). A kernel takes rows of whole 128-column lane tiles,
    on the chip: the decode kernel one token a lane over blocks of whole
    sublane tiles, whatever the width; the chunk kernel more over several tiles."""
    kernels = (attention._on_tpu() and key_row % 128 == 0 and value_row % 128 == 0)
    if kernels and tokens == 1 and block_size % (32 // jnp.dtype(dtype).itemsize) == 0:
        return DECODE_KERNEL
    if paged_attn_tiling(width, block_size)[1] == 1:
        return ONE_SHOT
    return CHUNK_KERNEL if kernels and tokens > 1 else KEY_LOOP


def paged_attn_trips(xp, first_pos, last_pos, real, window, tile_keys, tiles):
    """Run-time bounds of the key loop over a table of `tiles` tiles of
    `tile_keys` keys: (each lane's first tile [B], trips). Lane b's real
    queries lie at positions first_pos[b]..last_pos[b] and see no key
    outside tiles first[b]..first[b] + trips - 1: its last tile holds
    last_pos[b], its first one first_pos[b] - window + 1 (tile 0 under a
    global layer's `NO_WINDOW`), and the trips are the most any `real`
    lane needs. `xp` is `jax.numpy` inside the program and `numpy` on the
    host, which counts with the same arithmetic (`paged_attn_cover`)."""
    last = xp.minimum(last_pos // tile_keys, tiles - 1)
    first = xp.clip((first_pos - window + 1) // tile_keys, 0, last)
    return first, xp.where(real, last - first + 1, 1).max()


def paged_attn_cover(form: str, heads_by_window, width: int, block_size: int,
                     first_pos, last_pos, real):
    """What the attention of one dispatched paged program of `form` covers,
    counted on the host (numpy [B] positions of each lane's first and last
    real query, as `paged_attn_trips` takes them): (keys its bounds cover in
    a global layer, keys of the padded tables = lanes x width x block_size,
    query heads x keys in the window layers, in all layers). A layer under
    window w covers lanes x trips x tile keys (a table of one tile whole) or,
    under the decode kernel, the blocks each real lane's own position reaches
    from its window's first (`paged_decode_span`): the program's own bounds.
    `heads_by_window`: ((window or 0, query heads summed over the layers of
    that window and a looped model's passes), ...)."""
    lanes = np.size(last_pos)

    def keys(window):
        if form == DECODE_KERNEL:
            _, blocks = paged_decode_span(
                np, np.asarray(last_pos), real, window, block_size, width)
            return int(np.sum(blocks)) * block_size
        tile, tiles = paged_attn_tiling(width, block_size)
        _, trips = paged_attn_trips(
            np, first_pos, last_pos, real, window, tile * block_size, tiles)
        return lanes * int(trips) * tile * block_size

    run = keys(NO_WINDOW)
    in_window = sum(heads * keys(w) for w, heads in heads_by_window if w)
    every = in_window + run * sum(heads for w, heads in heads_by_window if not w)
    return run, lanes * width * block_size, in_window, every


class PagedAttention:
    """The attention of ONE paged program: built once, before the layer
    scan, from what is invariant over the layers, and called once a layer.

    pos [B, S] int32, the tokens' global positions; valid [B, S] bool (or
    True); block_tables [B, W] or [B, G, W] int32; `kv_heads` K/V heads whose
    key rows are `key_row` and value rows `value_row` wide (a latent pool: one
    head, the values the key row's first `value_row` columns); `scale` the
    scores' factor; `dtype` the pool's; `q_heads` the layers' query heads
    (`fold` ahead of layers of another count). `real` [B, S]: a real lane's
    valid slots (a padding lane's table is all null)."""

    def __init__(self, pos, valid, block_tables, block_size: int, kv_heads: int,
                 key_row: int, value_row: int, scale: float, dtype, q_heads: int):
        B, S = pos.shape
        W = block_tables.shape[-1]
        self.pos, self.scale = pos, scale
        self.heads = kv_heads, key_row, value_row
        self.form = paged_attn_form(S, W, block_size, key_row, value_row, dtype)
        self.width, (self.tile_blocks, self.tiles) = W, paged_attn_tiling(W, block_size)
        self.tile_keys = T = self.tile_blocks * block_size
        self.qpos = pos[:, None, :, None]
        self.real = real = jnp.broadcast_to(jnp.logical_and(valid, (block_tables != 0).any(
            axis=tuple(range(1, block_tables.ndim)))[:, None]), (B, S))
        if self.form == ONE_SHOT:
            self.kpos = jnp.arange(T)[None, None, None, :]
            self.seen = self.kpos <= self.qpos              # [B, 1, S, W*BS]
        elif self.form != DECODE_KERNEL:
            self.first_pos = jnp.where(real, pos, NO_WINDOW).min(axis=1)
            self.last_pos = jnp.where(real, pos, 0).max(axis=1)
            self.real_lane = real.any(axis=1)
        self._row_pos = {}
        self.fold(q_heads)

    def fold(self, q_heads: int):
        """Ahead of layers of `q_heads` query heads, outside their scan: the
        position of each query row as a K/V head's R = q_heads / kv_heads
        heads fold into its query axis, [B, R*S]: the chunk kernel's one
        operand that the head count shapes."""
        R = q_heads // self.heads[0]
        if self.form == CHUNK_KERNEL and R not in self._row_pos:
            self._row_pos[R] = jnp.tile(self.pos, (1, R))

    def __call__(self, q, kk, vv, slot, table, window):
        """Attention of q [B, H, S, key_row] over the rows `table` [B, W]
        names in the pool (kk, vv; vv None: a latent pool) at the layer's
        `slot`, causally and under `window` (None or an int32 scalar: query j
        sees key positions above pos[b, j] - window) -> [B, H, S, value_row]
        in the pool's dtype."""
        Hkv, Dh, Dv = self.heads
        B, H, S, _ = q.shape
        R = H // Hkv
        if R > 1:   # the R query heads of a K/V head ride its query axis
            q = q.reshape(B, Hkv, R * S, Dh)
        reach = NO_WINDOW if window is None else window
        if self.form == DECODE_KERNEL:      # the pool as it lies: each lane's own blocks
            out = paged_decode_attention(
                q, kk, vv, slot, table, self.pos[:, 0], self.real[:, 0], reach,
                dv=Dv, sm_scale=self.scale)
        elif self.form == ONE_SHOT:
            scores, gv = self._scores(q, kk, vv, slot, table, self.kpos, self.seen, window)
            probs = jax.nn.softmax(scores, axis=-1)
            out = jnp.einsum("bhst,bthd->bhsd", probs.astype(gv.dtype), gv)
        else:
            out = self._tiled(q, kk, vv, slot, table, reach, window, R)
        return out.reshape(B, H, S, Dv) if R > 1 else out

    def _scores(self, q, kk, vv, slot, blocks, kp, seen, window):
        """Masked float32 scores [B, Hkv, R*S, n*BS] of q [B, Hkv, R*S, Dh]
        against the rows of `blocks` [B, n] (key positions `kp`), and those
        blocks' V rows [B, n*BS, Hkv, Dv]: of a latent pool (`vv` None) the
        gathered key rows' first Dv columns."""
        Hkv, Dh, Dv = self.heads
        B, R = q.shape[0], q.shape[2] // self.pos.shape[1]
        gk = kk[slot, blocks].reshape(B, -1, Hkv, Dh)
        gv = gk[..., :Dv] if vv is None else vv[slot, blocks].reshape(B, -1, Hkv, Dv)
        mask = seen if window is None else seen & (kp > self.qpos - window)
        if R > 1:
            mask = jnp.tile(mask, (1, 1, R, 1))
        scores = jnp.einsum(
            "bhsd,bthd->bhst", q, gk, preferred_element_type=jnp.float32) * self.scale
        return jnp.where(mask, scores, _NEG_INF), gv

    def _tiled(self, q, kk, vv, slot, table, reach, window, R):
        """A table of several tiles: the chunk kernel, or the key loop."""
        Hkv, Dh, Dv = self.heads
        B, T, NT, TB = q.shape[0], self.tile_keys, self.tiles, self.tile_blocks
        first, trips = paged_attn_trips(
            jnp, self.first_pos, self.last_pos, self.real_lane, reach, T, NT)
        table = jnp.pad(table, ((0, 0), (0, NT * TB - self.width)))
        if self.form == CHUNK_KERNEL:   # the table's rows gathered once, densely: 0.05 ms a layer
            return paged_chunk_attention(
                q, kk[slot, table].reshape(B, NT * T, Hkv * Dh),
                None if vv is None else vv[slot, table].reshape(B, NT * T, Hkv * Dv),
                self._row_pos[R], first, trips, reach, tile_keys=T, dv=Dv,
                sm_scale=self.scale)

        def trip(j, carry):
            m, l, acc = carry
            tile = first + j                           # [B]; past the table: masked
            cols = jnp.minimum(tile, NT - 1)[:, None] * TB + jnp.arange(TB)
            kp = (tile[:, None] * T + jnp.arange(T))[:, None, None, :]
            scores, gv = self._scores(
                q, kk, vv, slot, jnp.take_along_axis(table, cols, axis=1),
                kp, kp <= self.qpos, window)
            m_new = jnp.maximum(m, scores.max(axis=-1))
            p = jnp.exp(scores - m_new[..., None])
            fade = jnp.exp(m - m_new)
            acc = acc * fade[..., None] + jnp.einsum(
                "bhst,bthd->bhsd", p.astype(gv.dtype), gv, preferred_element_type=jnp.float32)
            return m_new, l * fade + p.sum(axis=-1), acc

        rows = q.shape[:3]
        _, l, acc = jax.lax.fori_loop(0, trips, trip, (
            jnp.full(rows, _NEG_INF, jnp.float32), jnp.zeros(rows, jnp.float32),
            jnp.zeros(rows + (Dv,), jnp.float32)))
        return (acc / l[..., None]).astype(kk.dtype)


# ----------------------------------------------------- paged chunk kernel
#
# A prefill chunk's attention over a paged table wider than one key tile
# (`PagedAttention`, `CHUNK_KERNEL`): the flash forward's tiling and online
# softmax with the run-time bounds of the paged key loop. A tile of query rows
# keeps its running maximum, sum and accumulator in VMEM across all key
# tiles; the scores never reach HBM; the mask is made from positions a tile
# at a time.

PAGED_CHUNK_KERNEL = "paged_chunk_attn"
_CHUNK_Q_ROWS = 1024    # query rows a grid step holds (PERF.md §6, PR 41)


def _chunk_q_tile(rows: int) -> int:
    """Query rows a grid step: the largest whole number of sublane tiles of
    at most `_CHUNK_Q_ROWS` rows that divides `rows` (a multiple of 16)."""
    return max(d for d in range(16, min(rows, _CHUNK_Q_ROWS) + 1, 16) if rows % d == 0)


def _paged_chunk_kernel(first_ref, trips_ref, window_ref, qpos_ref, q_ref, k_ref,
                        *rest, tile_keys: int, nt: int, dv: int, sm_scale: float):
    """One (query tile, key tile) grid step. Scalar prefetch: each lane's
    first key tile, the step's trips, the layer's window. `rest` is ([v_ref,]
    o_ref, acc, m, l): without a value operand the key rows' first `dv`
    columns are the values (a latent pool)."""
    from jax.experimental import pallas as pl

    v_ref = rest[0] if len(rest) == 5 else None
    o_ref, acc_ref, m_ref, l_ref = rest[-4:]
    j = pl.program_id(3)
    tile = first_ref[pl.program_id(0)] + j

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(j < trips_ref[0])
    def _tile():
        k = k_ref[...]                                      # [T, Dh]
        s = jax.lax.dot_general(
            q_ref[...], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # [Tq, T] f32
        # a tile past the table is the last one again under positions no
        # query reaches: masked whole, as in the plain loop
        kp = tile * tile_keys + jax.lax.broadcasted_iota(
            jnp.int32, (1, tile_keys), 1)
        qp = qpos_ref[...]                                  # [Tq, 1]
        seen = jnp.logical_and(kp <= qp, kp > qp - window_ref[0])
        s = jnp.where(seen, s, _NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        fade = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * fade + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new
        v = k[:, :dv] if v_ref is None else v_ref[...]
        acc_ref[...] = acc_ref[...] * fade + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == nt - 1)
    def _flush():
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def paged_chunk_attention(q, keys, values, qpos, first, trips, window, *,
                          tile_keys: int, dv: int, sm_scale: float,
                          interpret: bool = False):
    """Causal (and windowed) attention of a chunk's folded query rows over
    the rows of its table, gathered densely: q [B, Hkv, rows, Dh] (row i of
    lane b at position qpos[b, i]); keys [B, NT * tile_keys, Hkv * Dh] as the
    pool lays them, values [B, NT * tile_keys, Hkv * dv] or None (a latent
    pool: the key rows' first `dv` columns); first [B] int32 and trips
    (`paged_attn_trips`): lane b attends key tiles first[b] .. first[b] +
    trips - 1 and no other is fetched or computed; window an int32 scalar (a
    global layer's is a window no sequence reaches). Query row i sees key
    position p where qpos - window < p <= qpos. bf16 products summed in
    float32, float32 online softmax -> [B, Hkv, rows, dv] in the keys' dtype.
    Dh and dv fill whole lane tiles."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, Hkv, rows, Dh = q.shape
    nt = keys.shape[1] // tile_keys
    rows_p = -(-rows // 16) * 16
    if rows_p != rows:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, rows_p - rows), (0, 0)))
        qpos = jnp.pad(qpos, ((0, 0), (0, rows_p - rows)))
    tq = _chunk_q_tile(rows_p)

    def key_tile(b, h, i, j, first, trips, window):
        # past the trips: the last tile fetched again, which is no fetch
        return (b, jnp.minimum(first[b] + jnp.minimum(j, trips[0] - 1), nt - 1), h)

    def query_tile(b, h, i, j, *_):
        return (b, h, i, 0)

    operands = [qpos[..., None], q, keys]
    in_specs = [pl.BlockSpec((None, tq, 1), lambda b, h, i, j, *_: (b, i, 0)),
                pl.BlockSpec((None, None, tq, Dh), query_tile),
                pl.BlockSpec((None, tile_keys, Dh), key_tile)]
    if values is not None:
        operands.append(values)
        in_specs.append(pl.BlockSpec((None, tile_keys, dv), key_tile))
    out = pl.pallas_call(
        functools.partial(_paged_chunk_kernel, tile_keys=tile_keys, nt=nt, dv=dv,
                          sm_scale=sm_scale),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, rows_p, dv), keys.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B, Hkv, rows_p // tq, nt),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((None, None, tq, dv), query_tile),
            scratch_shapes=[pltpu.VMEM((tq, dv), jnp.float32),
                            pltpu.VMEM((tq, 1), jnp.float32),
                            pltpu.VMEM((tq, 1), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 << 20),
        interpret=interpret, name=PAGED_CHUNK_KERNEL,
    )(first.astype(jnp.int32), jnp.asarray(trips, jnp.int32).reshape(1),
      jnp.asarray(window, jnp.int32).reshape(1), *operands)
    return out[:, :, :rows]


# ---------------------------------------------------- paged decode kernel
#
# A decode step's attention (`PagedAttention`, `DECODE_KERNEL`: one token a
# lane): the pool stays in HBM as it lies and a lane's program fetches ITS
# blocks through its table, from its window's first block to the block of its
# own position, a group of blocks a double-buffered DMA; scores and the online
# softmax's state in VMEM. A padding lane fetches nothing. The multi-page
# copy scheme is `jax.experimental.pallas.ops.tpu.paged_attention`'s; the
# layout (whole block rows, every K/V head at once, the R query heads of a
# K/V head as R rows), the window and the latent pool are this repo's.

PAGED_DECODE_KERNEL = "paged_decode_attn"
# A DMA group: at most this many bytes of key and value rows and at most this
# many keys (narrow rows: a group is multiplied whole, however few of its keys
# a short lane holds). Set on the chip (PERF.md §6, PR 44).
_DECODE_GROUP_BYTES = 1 << 20
_DECODE_GROUP_KEYS = 512
# Tables reach the kernel at ONE width a lane count, as wide as the pool has
# blocks or as a scalar operand of this many bytes holds (`decode_table_width`):
# the engine builds a decode step's tables at it, so a server warms ONE decode
# program a lane bucket; a narrower table is padded to it, one trace either way.
_DECODE_TABLE_BYTES = 32 << 10


def paged_decode_span(xp, pos, real, window, block_size: int, width: int):
    """(first block, blocks) of its table that a decode lane at position `pos`
    attends under `window` (a global layer's: one no sequence reaches): from
    the block of pos - window + 1 to the block of pos, and none for a lane
    that is not `real`. `xp` is `jax.numpy` in the program, which hands the
    kernel the step's [B] of each as scalar operands, and `numpy` on the host,
    which counts keys with the same arithmetic (`paged_attn_cover`)."""
    last = xp.minimum(pos // block_size, width - 1)
    first = xp.minimum(xp.maximum(pos - window + 1, 0) // block_size, last)
    return first, xp.where(real, last - first + 1, 0)


def _paged_decode_kernel(slot_ref, table_ref, first_ref, blocks_ref, pos_ref, window_ref,
                         q_ref, k_hbm, *rest, bs: int, gb: int, width: int,
                         dv: int, sm_scale: float):
    """One lane. Scalar prefetch: the layer's pool slot, the tables [B * W],
    each lane's first block and blocks (`paged_decode_span`), its position,
    the layer's window. `q_ref` [M, key row]: the lane's query rows, each in
    its own K/V head's columns. `rest` is ([v_hbm,] o_ref, kbuf, [vbuf,] sem,
    turn, m, l, acc): without a value pool the key rows' first `dv` columns
    are the values (a latent pool). While a lane's last group is multiplied
    the NEXT lane's first is on its way: `turn` carries, from lane to lane,
    which of the two buffers that group is in."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    latent = len(rest) == 7
    if latent:
        (o_ref, kbuf, *scratch), v_hbm, vbuf = rest, None, None
    else:
        v_hbm, o_ref, kbuf, vbuf, *scratch = rest
    sem, turn, m_ref, l_ref, acc_ref = scratch
    b, lanes = pl.program_id(0), pl.num_programs(0)
    window, slot = window_ref[0], slot_ref[0]
    # The body is traced once a lane count and lowered in every decode program
    # a server warms, inside its set-up, so it is written to be cheap there:
    # `lax` called by name (an operator on a traced value, like a `jax.numpy`
    # call, is a dispatch of its own, five times a `lax` call's cost), and ONE
    # branch (a padding lane's loops run no trip, a copy nobody is to start
    # is a loop of no block).
    lax = jax.lax
    add, sub, mul = lax.add, lax.sub, lax.mul

    def wide(col, like):        # [M, 1] beside [M, n]
        return lax.broadcast_in_dim(col, like.shape, (0, 1))

    def held(lane, g):          # blocks of the lane's group g
        return lax.min(sub(blocks_ref[lane], mul(g, gb)), gb)

    def each(lane, g, buf, blocks, act):
        """`act` on the copy of each of the first `blocks` blocks of the
        lane's group g: a start and its wait name the same copies."""
        entry = add(add(mul(lane, width), first_ref[lane]), mul(g, gb))

        def block(i, _):
            phys = table_ref[add(entry, i)]
            act(pltpu.make_async_copy(
                k_hbm.at[slot, phys], kbuf.at[buf, i], sem.at[buf, 0]))
            if not latent:
                act(pltpu.make_async_copy(
                    v_hbm.at[slot, phys], vbuf.at[buf, i], sem.at[buf, 1]))
            return 0

        lax.fori_loop(0, blocks, block, 0)

    def start(copy):
        copy.start()

    def wait(copy):
        copy.wait()

    @pl.when(lax.eq(b, 0))
    def _first_lane():      # rows no copy has written yet are multiplied under a weight of 0
        for rows in [kbuf] if latent else [kbuf, vbuf]:
            rows[...] = lax.full(rows.shape, 0, rows.dtype)
        turn[0] = 0

    first, blocks, pos, buf0 = first_ref[b], blocks_ref[b], pos_ref[b], turn[0]
    groups = lax.div(add(blocks, gb - 1), gb)
    after = lax.min(add(b, 1), sub(lanes, 1))
    last_lane = lax.eq(add(b, 1), lanes)
    # a lane before this one sent for its first group; else it does, here
    sent = lax.bitwise_and(lax.gt(b, 0), lax.gt(blocks_ref[lax.max(sub(b, 1), 0)], 0))
    each(b, 0, buf0, lax.select(sent, 0, lax.min(blocks, gb)), start)
    m_ref[...] = lax.full(m_ref.shape, _NEG_INF, m_ref.dtype)
    l_ref[...] = lax.full(l_ref.shape, 0, l_ref.dtype)
    acc_ref[...] = lax.full(acc_ref.shape, 0, acc_ref.dtype)

    def group(g, _):
        buf = lax.rem(add(buf0, g), 2)
        # on its way while this group is multiplied: the lane's next group,
        # or behind its last the next lane's first (of no block: a padding lane)
        more = lax.lt(add(g, 1), groups)
        lane, nxt = lax.select(more, b, after), lax.select(more, add(g, 1), 0)
        each(lane, nxt, sub(1, buf), lax.select(
            lax.bitwise_and(lax.bitwise_not(more), last_lane), 0, held(lane, nxt)), start)
        each(b, g, buf, held(b, g), wait)
        k = lax.reshape(kbuf[buf], (gb * bs, kbuf.shape[-1]))          # [T, key row]
        s = mul(lax.dot_general(q_ref[...], k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32),
                jnp.float32(sm_scale))                                  # [M, T] f32
        kp = add(lax.broadcasted_iota(jnp.int32, (1, gb * bs), 1),
                 mul(add(first, mul(g, gb)), bs))
        seen = lax.bitwise_and(lax.le(kp, pos), lax.gt(kp, sub(pos, window)))
        s = lax.select(wide(seen, s), s, lax.full(s.shape, _NEG_INF, s.dtype))
        m_prev = m_ref[...]
        m_new = lax.max(m_prev, lax.reduce_max(s, (1,))[:, None])
        p = lax.exp(sub(s, wide(m_new, s)))
        fade = lax.exp(sub(m_prev, m_new))
        l_ref[...] = add(mul(l_ref[...], fade), lax.reduce_sum(p, (1,))[:, None])
        m_ref[...] = m_new
        v = (lax.slice_in_dim(k, 0, dv, axis=1) if latent
             else lax.reshape(vbuf[buf], (gb * bs, vbuf.shape[-1])))
        acc = acc_ref[...]
        acc_ref[...] = add(mul(acc, wide(fade, acc)), lax.dot_general(
            lax.convert_element_type(p, v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32))
        return 0

    lax.fori_loop(0, groups, group, 0)
    turn[0] = lax.rem(add(buf0, groups), 2)
    # (a padding lane's sum is 0 over an accumulator of 0: it reads 0)
    acc = acc_ref[...]
    o_ref[...] = lax.convert_element_type(lax.div(acc, wide(lax.max(
        l_ref[...], lax.full(l_ref.shape, 1e-30, l_ref.dtype)), acc)), o_ref.dtype)


def paged_decode_attention(q, keys, values, slot, table, pos, real, window, *,
                           dv: int, sm_scale: float, interpret=False):
    """Attention of one query token a lane over the rows its table names in
    the pool AS IT LIES: q [B, Hkv, R, Dh] (the R query heads of a K/V head as
    R rows), lane b at position pos[b]; keys [depth, NB, BS, Hkv * Dh], values
    [depth, NB, BS, Hkv * dv] or None (a latent pool: the key rows' first `dv`
    columns), read at the layer's `slot`; table [B, W] int32; real [B] bool;
    window an int32 scalar (a global layer's is a window no sequence
    reaches). Lane b sees key position p where pos[b] - window < p <= pos[b],
    and only the blocks that hold such keys are fetched
    (`paged_decode_span`); a lane that is not real fetches none and reads 0.

    A lane's program takes whole block rows, every K/V head at once, and
    multiplies them as they lie: its Hkv x R query rows are laid out each in
    its own head's Dh columns of a key row, zeros elsewhere, so that ONE
    product a group of blocks gives every head's scores ([Hkv * R, keys]) and
    one more every head's weighted values, of which a head keeps its own dv
    columns: the products a head at a time would sum, the zeros adding
    nothing, in two matrix products the matrix units share instead of 2 x Hkv
    small ones in a row. bf16 products summed in float32, float32 online
    softmax -> [B, Hkv, R, dv] in the pool's dtype. Dh and dv fill whole lane
    tiles, a block whole sublane tiles."""
    B, width = table.shape
    nb, bs = keys.shape[1:3]
    window = jnp.asarray(window, jnp.int32)
    first, blocks = paged_decode_span(jnp, pos, real, window, bs, width)
    wide = max(width, decode_table_width(B, nb))
    block_bytes = bs * sum(pool.shape[-1] * pool.dtype.itemsize
                           for pool in (keys, values) if pool is not None)
    gb = max(1, min(wide, _DECODE_GROUP_BYTES // block_bytes,      # blocks a group
                    _DECODE_GROUP_KEYS // bs))
    return _paged_decode_call(
        q, keys, values, jnp.asarray(slot, jnp.int32),
        jnp.pad(table.astype(jnp.int32), ((0, 0), (0, wide - width))),
        first.astype(jnp.int32), blocks.astype(jnp.int32), pos.astype(jnp.int32), window,
        gb=gb, dv=dv, sm_scale=sm_scale, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("gb", "dv", "sm_scale", "interpret"))
def _paged_decode_call(q, keys, values, slot, table, first, blocks, pos, window, *,
                       gb: int, dv: int, sm_scale: float, interpret):
    """`paged_decode_attention` behind its scalar operands: the rows laid out,
    the kernel over the lanes, each head's own columns kept. Jitted on its own
    so that the programs of a server (and the layer kinds of a program) that
    bring it the same shapes share its trace."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, heads, R, dh = q.shape
    bs, width = keys.shape[2], table.shape[1]
    pools = [keys] if values is None else [keys, values]
    M = heads * R
    mp = -(-M // 8) * 8
    # row (h, r) holds q[h, r] in columns h * Dh .. of a key row
    own = jnp.eye(heads, dtype=q.dtype)
    rows = (q[:, :, :, None, :] * own[None, :, None, :, None]).reshape(B, M, heads * dh)
    rows = jnp.pad(rows.astype(keys.dtype), ((0, 0), (0, mp - M), (0, 0)))
    buffers = [pltpu.VMEM((2, gb, bs, pool.shape[-1]), pool.dtype) for pool in pools]

    def lane(b, *_):
        return (b, 0, 0)

    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, bs=bs, gb=gb, width=width,
                          dv=heads * dv, sm_scale=sm_scale),
        out_shape=jax.ShapeDtypeStruct((B, mp, heads * dv), keys.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6, grid=(B,),
            in_specs=[pl.BlockSpec((None, mp, heads * dh), lane)]
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
            out_specs=pl.BlockSpec((None, mp, heads * dv), lane),
            scratch_shapes=[*buffers, pltpu.SemaphoreType.DMA((2, len(pools))),
                            pltpu.SMEM((1,), jnp.int32),
                            pltpu.VMEM((mp, 1), jnp.float32),
                            pltpu.VMEM((mp, 1), jnp.float32),
                            pltpu.VMEM((mp, heads * dv), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=64 << 20),
        interpret=interpret, name=PAGED_DECODE_KERNEL,
    )(slot.reshape(1), table.reshape(-1), first, blocks, pos, window.reshape(1),
      rows, *pools)
    # head h's own dv columns of its R rows
    out = out[:, :M].reshape(B, heads, R, heads, dv)
    return jnp.einsum("bhrhd->bhrd", out) if heads > 1 else out[:, :, :, 0]


def decode_table_width(lanes: int, num_blocks: int) -> int:
    """The ONE table width the decode kernel takes at `lanes` lanes over a pool
    of `num_blocks` blocks: the pool's blocks, or what `_DECODE_TABLE_BYTES` of
    int32 hold a lane. A wider table keeps its width. (At the END of the file:
    the compile cache's key carries the line of every `def` above, ROADMAP S7.)"""
    return min(num_blocks, _DECODE_TABLE_BYTES // (4 * lanes))
