"""Attention kernels: Pallas flash attention + ring/Ulysses context parallelism.

The reference has NO sequence-parallel attention (SURVEY.md §2.6 — grep shows
long-context entirely delegated to DeepSpeed/FSDP inside Train workers). Here
it is first-class:

  * `flash_attention` — blockwise online-softmax kernel on the MXU
    (Pallas on TPU; other backends get the XLA reference so CPU tests can
    share model configs — chip_smoke.py and the train cells assert the kernel
    path).
  * `ring_attention`  — sequence shards on the `sp` mesh axis; K/V blocks
    rotate around the ring via `ppermute` with global-position causal
    masking and online-softmax merging. Call under `shard_map`.
  * `ulysses_attention` — all_to_all head<->seq exchange so each device
    runs full-sequence attention on a head subset.

Shapes follow [batch, heads, seq, head_dim] throughout; the flash kernels'
own operands lie as the projections lay them, [batch, seq, heads * head_dim]
(`heads_a_step`), and the wrappers own the way between the two.
"""

from __future__ import annotations

import functools
import math
import re
from typing import Optional

import jax
import jax.numpy as jnp

_NEG_INF = -1e30


# --------------------------------------------------------------- reference
def attention_reference(q, k, v, causal: bool = True, sm_scale: Optional[float] = None):
    """XLA attention (materializes logits). Ground truth for kernels and the
    off-TPU fallback."""
    *_, S, D = q.shape
    Skv = k.shape[-2]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)
    logits = jnp.einsum("bhsd,bhtd->bhst", q, k, preferred_element_type=jnp.float32)
    logits = logits * scale
    if causal:
        qpos = jnp.arange(S)[:, None] + (Skv - S)  # align ends when S != Skv
        kpos = jnp.arange(Skv)[None, :]
        logits = jnp.where(qpos >= kpos, logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhst,bhtd->bhsd", probs.astype(v.dtype), v)


# ------------------------------------------------------------ pallas kernel
#
# All three kernels stream K/V (or Q for dk/dv) block-by-block from HBM via a
# third grid axis instead of holding the whole sequence in VMEM: grid =
# (batch*heads a step, outer blocks, streamed blocks), with the running
# accumulators in VMEM scratch that persists across the innermost
# ("arbitrary") axis.
# VMEM per step is O(block) not O(S), so a single chip runs S=16k+ (the old
# whole-KV layout hit the 16 MiB scoped-vmem wall at 16k — VERDICT r3 §weak 1).
# Causal skipping: the streamed index map clamps past-diagonal steps to the
# last relevant block — Pallas skips the DMA when consecutive steps map to the
# same block — and `pl.when` skips the compute.
#
# INSIDE a grid step the kernels walk sub-tiles (`_sub_tile`), enumerated at
# trace time (`_walks`): a pair of sub-tiles wholly above the diagonal or
# wholly in the padding is not emitted, a pair the diagonal or a ragged tail
# crosses takes its mask, every other pair runs with none, and a sub-tile's
# pairs side by side are ONE product (`_runs`). A grid step costs some
# 0.35 us, more than a 256 x 256 pair's work, so the walk is not a finer grid.


def _causal_last_kv(qi, block_q, block_k, row_offset, nk):
    """Index of the last K/V block the causal mask lets q block `qi` touch."""
    last = jax.lax.div(row_offset + (qi + 1) * block_q - 1, block_k)
    return jnp.clip(last, 0, nk - 1)


def _causal_first_q(ki, block_q, block_k, row_offset, nq):
    """Index of the first q block whose rows reach k block `ki` (causal)."""
    first = jax.lax.div(ki * block_k - row_offset, block_q)
    return jnp.clip(first, 0, nq - 1)


def _sub_tile(kernel: str, head_dim: int) -> int:
    """Rows and columns of a sub-tile of `kernel`'s walk inside a grid step,
    as the chip timed the kernels alone at the train cells' shapes
    (`scripts/flash_time.py`; PERF.md §6, PR 45). The backward kernels are
    bound by their products: the smaller the sub-tile, the closer to the
    triangle (128 gains 3% more and compiles four times as long). The
    forward's Vᵀ·Pᵀ streams only head_dim rows through each tile of Pᵀ it
    loads, and a sub-tile's fixed costs weigh more the narrower the head."""
    if kernel == "flash_fwd" and head_dim < 128:
        return 512
    return 256


def heads_a_step(heads: int, head_dim: int) -> int:
    """Heads a grid step takes when q, k, v, dO, o, dq, dk and dv lie as the
    projections lay them, [B, S, H·Dh], and a step's block is a COLUMN block
    of one lane tile: as many heads as fill 128 lanes (a pair of 64: the body
    runs once a head, on its half of the block, and the results are stored
    128 lanes wide). 0 where no whole heads fill a lane tile (an odd count of
    heads of 64, the tiny heads of the CPU's tests) and where a head fills
    whole tiles by itself (gptj-6b's 256): those operands are laid [B·H, S,
    Dh], a head a ROW, and a transposed caller pays the relayouts. A head of
    256 a column block was built, and read WORSE on the chip than its rows:
    the kernels alone 67.7 us a head for 67.2, more copy bytes in the
    compiled step (XLA lays the projections' results in tiles of (heads, Dh)
    either way), `gptj-6b.train-fsdp4` -1.7% (PERF.md §6, PR 49)."""
    g = 128 // head_dim
    return g if g >= 2 and g * head_dim == 128 and heads % g == 0 else 0


def _to_operand(x):
    """[B, H, S, Dh] -> the kernels' operand, [B, S, H·Dh] or [B·H, S, Dh]. A
    caller that transposed the projections' [B, S, H, Dh] to get here (as
    `models/gpt.py` `_block` does for `attend`) sees XLA cancel the pair."""
    B, H, S, D = x.shape
    if heads_a_step(H, D):
        return x.transpose(0, 2, 1, 3).reshape(B, S, H * D)
    return x.reshape(B * H, S, D)


def _from_operand(x, batch: int, heads: int):
    """`_to_operand`'s inverse: -> [B, H, S, Dh]."""
    if x.shape[0] != batch:     # a head a row
        return x.reshape(batch, heads, *x.shape[1:])
    return x.reshape(batch, x.shape[1], heads, -1).transpose(0, 2, 1, 3)


def _flash_blocks(S: int, Skv: int, D: int, block_q: int, block_k: int):
    """The grid's (block_q, block_k), clamped to the sequence: one longer than
    the kernels' largest sub-tile pads to whole ones."""
    t = max(_sub_tile(kernel, D) for kernel in FLASH_KERNELS)

    def clamp(block, n):
        return min(block, n if n <= t else -(-n // t) * t)

    return clamp(block_q, max(S, 8)), clamp(block_k, Skv)


def _sub_tiles(kernel: str, D: int, block_q: int, block_k: int):
    """(tq, tk) of `kernel`'s walk: a block that whole sub-tiles do not fill
    is its own sub-tile."""
    t = _sub_tile(kernel, D)
    return tuple(t if block % t == 0 else block for block in (block_q, block_k))


def _walks(nq, nk, block_q, block_k, tq, tk, causal, seq_q, seq_kv, guard_rows):
    """The distinct walks of a kernel's grid steps, found at trace time:
    ({key: pairs}, whether every step has one).

    A step (q block i, k block j) is keyed by all its masks depend on,
    (shift, cols, rows): local row r sees local column c iff r - c >= shift
    (None: the whole block is below the diagonal, or there is none), padding
    starts at local column `cols` and, where the kernel guards rows, at local
    row `rows` (None: none in this block). A step wholly above the diagonal
    has no key. `pairs` lists the sub-tile pairs (a, b, mask) the step
    computes; mask = (diag, cols, rows), local to the pair as the key is to
    the block, None for each term the pair does not need."""
    # When S != Skv (decode over a cached prefix) queries are END-aligned
    # with keys, matching attention_reference's (Skv - S) offset.
    row_offset = seq_kv - seq_q
    keys, whole = set(), True
    for i in range(nq):
        for j in range(nk):
            shift = j * block_k - i * block_q - row_offset if causal else None
            if shift is not None and shift >= block_q:
                whole = False
                continue
            if shift is not None and shift <= -(block_k - 1):
                shift = None
            cols = seq_kv - j * block_k if (j + 1) * block_k > seq_kv else None
            rows = (seq_q - i * block_q
                    if guard_rows and (i + 1) * block_q > seq_q else None)
            keys.add((shift, cols, rows))
    walks = {}
    for shift, cols, rows in keys:
        pairs = walks[shift, cols, rows] = []
        for a in range(block_q // tq):
            for b in range(block_k // tk):
                d = None if shift is None else shift - a * tq + b * tk
                c = None if cols is None else cols - b * tk
                r = None if rows is None else rows - a * tq
                if ((d is not None and d >= tq) or (c is not None and c <= 0)
                        or (r is not None and r <= 0)):
                    continue  # nothing of the pair is seen
                pairs.append((a, b, (
                    None if d is None or d <= -(tk - 1) else d,
                    None if c is None or c >= tk else c,
                    None if r is None or r >= tq else r)))
    return walks, whole


def _walk_step(i, j, nq, nk, block_q, block_k, tq, tk, causal, seq_q, seq_kv,
               guard_rows, body):
    """`body(pairs)` of the walk that grid step (i, j) has, if it has one:
    which walk it is is a traced value, so `pl.when` picks among them."""
    from jax.experimental import pallas as pl

    walks, whole = _walks(nq, nk, block_q, block_k, tq, tk, causal, seq_q, seq_kv,
                          guard_rows)
    if whole and len(walks) == 1:
        return body(*walks.values())
    shift = j * block_k - i * block_q - (seq_kv - seq_q)
    ragged_k = nk > 1 and any(c is not None for _, c, _ in walks)
    ragged_q = nq > 1 and any(r is not None for _, _, r in walks)
    for (s, c, r), pairs in walks.items():
        terms = []
        if causal:
            terms.append(shift <= -(block_k - 1) if s is None else shift == s)
        if ragged_k:
            terms.append(j == nk - 1 if c is not None else j != nk - 1)
        if ragged_q:
            terms.append(i == nq - 1 if r is not None else i != nq - 1)
        pl.when(functools.reduce(jnp.logical_and, terms))(
            functools.partial(body, pairs))


_NO_MASK = (None, None, None)


def _runs(pairs, outer, n):
    """For each of the n sub-tiles along axis `outer` (0: q, 1: k), the
    sub-tiles of the other axis it is paired with, as runs [first, end,
    mask]: neighbours that need no mask are ONE run, so one product."""
    runs = [[] for _ in range(n)]
    for pair in pairs:
        o, i, mask = pair[outer], pair[1 - outer], pair[2]
        run = runs[o]
        assert not run or run[-1][1] == i, "a sub-tile's pairs lie side by side"
        if run and mask == run[-1][2] == _NO_MASK:
            run[-1][1] = i + 1
        else:
            run.append([i, i + 1, mask])
    return runs


def _mask_runs(cache, x, runs, t, fill, q_axis, run_axis):
    """Scores x with each run's mask laid on its own part of axis `run_axis`
    (the runs' sub-tiles of t), `fill` where a score is not seen. Query rows
    lie along `q_axis`; `cache` holds the masks one body has built."""
    pieces = []
    for first, end, mask in runs:
        part = slice((first - runs[0][0]) * t, (end - runs[0][0]) * t)
        piece = x[:, part] if run_axis else x[part]
        if mask != _NO_MASK:
            if (piece.shape, mask) not in cache:
                diag, cols, rows = mask
                r = jax.lax.broadcasted_iota(jnp.int32, piece.shape, q_axis)
                c = jax.lax.broadcasted_iota(jnp.int32, piece.shape, 1 - q_axis)
                terms = ([r - c >= diag] if diag is not None else []) + (
                    [c < cols] if cols is not None else []) + (
                    [r < rows] if rows is not None else [])
                cache[piece.shape, mask] = functools.reduce(jnp.logical_and, terms)
            piece = jnp.where(cache[piece.shape, mask], piece, fill)
        pieces.append(piece)
    return pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces, axis=run_axis)


def _nt(x, y):
    """x · yᵀ in float32: operands stay in the input dtype (bf16 runs the MXU
    at full rate; an f32 upcast quarters matmul throughput)."""
    return jax.lax.dot_general(x, y, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _nn(x, y):
    return jax.lax.dot_general(x, y, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _tn(x, y):
    return jax.lax.dot_general(x, y, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _head_lanes(x, r: int, g: int, D: int, cut: bool = False):
    """Head r of x [n, g·D], a block of g heads' columns (`heads_a_step`), as
    an operand of a product: the block with the OTHER heads' lanes zeroed, so
    that a contraction over all g·D lanes against the whole block of the other
    operand is head r's alone and no lane moves (128 lanes half zero cost the
    MXU the passes 64 lanes cost), or, `cut`, its D lanes alone (a static
    lane slice). What the chip said at a pair of 64 (`scripts.flash_time`, us
    a head, PERF.md §6, PR 49): every score product and dq, dk, dv by zeroed
    lanes (cut operands: dq 2.84 -> 3.02, dk/dv 3.67 -> 3.72); the forward's
    V cut, because Vᵀ·Pᵀ over the whole block computes both heads' rows (2.58
    -> 2.11; q and k cut as well 2.12)."""
    if g == 1:
        return x
    if cut:
        return x[:, r * D:(r + 1) * D]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where((lane >= r * D) & (lane < (r + 1) * D), x, jnp.zeros_like(x))


def _join_lanes(parts, D: int):
    """The heads' results [n, g·D], head r's lanes true in parts[r] (a
    product against a whole block, `_head_lanes`) -> [n, g·D], every lane
    true: stored 128 lanes wide."""
    out = parts[-1]
    if len(parts) > 1:
        lane = jax.lax.broadcasted_iota(jnp.int32, out.shape, 1)
        for r in range(len(parts) - 2, -1, -1):
            out = jnp.where(lane < (r + 1) * D, parts[r], out)
    return out


def _flash_fwd_kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    *rest,  # ([lse_ref,] acc_ref, m_ref, l_ref) — lse only on the training path
    head_dim: int,
    nq: int,
    nk: int,
    causal: bool,
    sm_scale: float,
    seq_q: int,
    seq_kv: int,
):
    """One (q block, k block) grid step of the online-softmax forward, for
    the g heads whose columns the block holds (`heads_a_step`).

    Inputs are PADDED to block multiples by the caller (pl.ds on a ragged
    tail clamps the start index, silently misaligning data vs mask — so
    padding + masking against the ORIGINAL lengths is the only safe layout).
    seq_q/seq_kv are the original (unpadded) lengths."""
    from jax.experimental import pallas as pl

    if len(rest) == 4:
        lse_ref, acc_ref, m_ref, l_ref = rest
    else:
        lse_ref = None
        acc_ref, m_ref, l_ref = rest

    qi = pl.program_id(1)
    j = pl.program_id(2)
    block_q, block_k = q_ref.shape[1], k_ref.shape[1]
    D, g = head_dim, q_ref.shape[2] // head_dim
    tq, tk = _sub_tiles("flash_fwd", D, block_q, block_k)

    if nk != 1:
        @pl.when(j == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

    def _flush(rows, start, accs, ms, ls):
        """A head's (acc [D, n], m, l [1, n]) each: o's rows, g·D lanes wide."""
        out = [acc / jnp.maximum(l, 1e-30) for acc, l in zip(accs, ls)]
        out = out[0] if g == 1 else jnp.concatenate(out, axis=0)
        o_ref[0, rows] = out.T.astype(o_ref.dtype)
        if lse_ref is not None:
            # logsumexp per row — the only softmax statistic backward needs.
            # The lse block is the full (g, 1, S_p) rows; each qi writes its
            # slice, covering S_p by the time the bh block flushes.
            for r, (m, l) in enumerate(zip(ms, ls)):
                lse_ref[r, :, pl.ds(qi * block_q + start, m.shape[1])] = (
                    m + jnp.log(jnp.maximum(l, 1e-30)))

    # The scores are computed TRANSPOSED, [keys, queries]: a row's maximum and
    # sum run ACROSS registers, not along the lanes of each (1.5 us of a
    # head's 3.5 on the chip, PERF.md §6, PR 45). A q sub-tile's keys are ONE
    # product and one plain softmax; only pairs an edge crosses pay a mask.
    def _step(pairs):
        masks = {}
        for a, runs in enumerate(_runs(pairs, 0, block_q // tq)):
            rows = slice(a * tq, (a + 1) * tq)
            if not runs:  # rows that see no key of this block (Skv < S)
                if nk == 1:
                    _flush(rows, a * tq, [jnp.zeros((D, tq), jnp.float32)] * g,
                           [jnp.full((1, tq), _NEG_INF)] * g,
                           [jnp.zeros((1, tq), jnp.float32)] * g)
                continue
            cols = slice(runs[0][0] * tk, runs[-1][1] * tk)
            q_blk, k_blk, v_blk = q_ref[0, rows], k_ref[0, cols], v_ref[0, cols]
            state = []
            for r in range(g):
                head = slice(r * D, (r + 1) * D)
                st = _nt(k_blk, _head_lanes(q_blk, r, g, D)) * sm_scale
                st = _mask_runs(masks, st, runs, tk, _NEG_INF, 1, 0)  # [keys, tq] f32
                m = jnp.max(st, axis=0, keepdims=True)  # [1, tq]
                if nk != 1:
                    m_prev = m_ref[r:r + 1, rows]
                    m = jnp.maximum(m_prev, m)
                pt = jnp.exp(st - m)
                l = jnp.sum(pt, axis=0, keepdims=True)
                # Vᵀ·Pᵀ: [D, tq]
                acc = _tn(_head_lanes(v_blk, r, g, D, cut=True), pt.astype(v_blk.dtype))
                if nk == 1:
                    state.append((acc, m, l))
                else:
                    alpha = jnp.exp(m_prev - m)
                    m_ref[r:r + 1, rows] = m
                    l_ref[r:r + 1, rows] = l_ref[r:r + 1, rows] * alpha + l
                    acc_ref[head, rows] = acc_ref[head, rows] * alpha + acc
            if nk == 1:
                # One grid step a q block: its state never leaves values.
                _flush(rows, a * tq, *zip(*state))

    _walk_step(qi, j, nq, nk, block_q, block_k, tq, tk, causal, seq_q, seq_kv, False, _step)

    if nk != 1:
        @pl.when(j == nk - 1)
        def _last():
            _flush(slice(None), 0, *zip(*(
                (acc_ref[r * D:(r + 1) * D], m_ref[r:r + 1], l_ref[r:r + 1])
                for r in range(g))))


def _head_blocks(x, head_dim: int):
    """For an operand [B, S, lanes] (`_to_operand`): (heads a grid step g,
    the grid's first axis B·H/g, `at`: that axis' index -> (batch, column
    block)). A head a row ([B·H, S, Dh]) is one column block a batch."""
    B, _, lanes = x.shape
    H = lanes // head_dim
    g = heads_a_step(H, head_dim) or 1
    assert lanes == H * head_dim and (H == 1 or g > 1), (x.shape, head_dim)
    n = H // g

    def at(bh):
        return (bh, 0) if n == 1 else (bh // n, bh % n)

    return g, B * n, at


def _flash_fwd_call(qr, kr, vr, head_dim: int, causal: bool, sm_scale: float,
                    block_q: int, block_k: int, interpret: bool = False,
                    return_lse: bool = False):
    """The forward over operands as `_to_operand` lays them: -> o in the same
    form [, lse [B·H, 1, S_p], padded to whole q blocks]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, S, lanes = qr.shape
    Skv, D = kr.shape[1], head_dim
    g, steps, at = _head_blocks(qr, D)
    W, BH = g * D, B * lanes // D
    block_q, block_k = _flash_blocks(S, Skv, D, block_q, block_k)
    # Pad to block multiples (see kernel docstring for why).
    S_p = -(-S // block_q) * block_q
    Skv_p = -(-Skv // block_k) * block_k
    if S_p != S:
        qr = jnp.pad(qr, ((0, 0), (0, S_p - S), (0, 0)))
    if Skv_p != Skv:
        kr = jnp.pad(kr, ((0, 0), (0, Skv_p - Skv), (0, 0)))
        vr = jnp.pad(vr, ((0, 0), (0, Skv_p - Skv), (0, 0)))
    nq = S_p // block_q
    nk = Skv_p // block_k
    row_offset = Skv - S
    grid = (steps, nq, nk)  # kv innermost: scratch accumulates across it

    def q_index(bh, i, j):
        b, h = at(bh)
        return (b, i, h)

    if causal:
        # Past-diagonal steps re-map to the last relevant block: same index as
        # the previous step ⇒ Pallas skips the DMA; pl.when skips the compute.
        def kv_index(bh, i, j):
            b, h = at(bh)
            return (b, jnp.minimum(j, _causal_last_kv(i, block_q, block_k, row_offset, nk)), h)
    else:
        def kv_index(bh, i, j):
            b, h = at(bh)
            return (b, j, h)

    out_shape = [jax.ShapeDtypeStruct(qr.shape, qr.dtype)]
    out_specs = [pl.BlockSpec((1, block_q, W), q_index)]
    if return_lse:  # inference forward skips the lse compute+HBM write
        out_shape.append(jax.ShapeDtypeStruct((BH, 1, S_p), jnp.float32))
        out_specs.append(pl.BlockSpec((g, 1, S_p), lambda bh, i, j: (bh, 0, 0)))
    # The training path's lse output is ONE (g,1,S_p) block revisited by
    # every q-block step — its grid dim must stay "arbitrary" or a megacore
    # partition would write back per-core copies of the shared row.
    q_dim_semantics = "arbitrary" if return_lse else "parallel"
    res = pl.pallas_call(
        functools.partial(
            _flash_fwd_kernel,
            head_dim=D,
            nq=nq,
            nk=nk,
            causal=causal,
            sm_scale=sm_scale,
            seq_q=S,
            seq_kv=Skv,
        ),
        out_shape=tuple(out_shape),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, W), q_index),
            pl.BlockSpec((1, block_k, W), kv_index),
            pl.BlockSpec((1, block_k, W), kv_index),
        ],
        out_specs=tuple(out_specs),
        scratch_shapes=[  # a q block's state across its k blocks, transposed
            pltpu.VMEM((W, block_q), jnp.float32),
            pltpu.VMEM((g, block_q), jnp.float32),
            pltpu.VMEM((g, block_q), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", q_dim_semantics, "arbitrary")
        ),
        cost_estimate=pl.CostEstimate(
            flops=4 * BH * S * Skv * D,
            bytes_accessed=2 * (qr.size + kr.size + vr.size) * qr.dtype.itemsize,
            transcendentals=BH * S * Skv,
        ),
        interpret=interpret,
        name="flash_fwd",
    )(qr, kr, vr)
    out = res[0][:, :S]
    if return_lse:
        return out, res[1]  # lse stays padded/flat — backward consumes it as-is
    return out


def _flash_bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc_ref,
    *, head_dim: int, nq: int, nk: int, causal: bool, sm_scale: float, seq_q: int,
    seq_kv: int,
):
    """dQ for one q block: stream k blocks up to the causal diagonal.

    FlashAttention-2 backward: P = exp(S - lse); dS = P∘(dO·Vᵀ − Δ);
    dQ = scale · dS·K, with Δ = rowsum(dO∘O) precomputed by the caller.
    """
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    j = pl.program_id(2)
    block_q, block_k = q_ref.shape[1], k_ref.shape[1]
    D, g = head_dim, q_ref.shape[2] // head_dim
    tq, tk = _sub_tiles("flash_bwd_dq", D, block_q, block_k)

    if nk != 1:
        @pl.when(j == 0)
        def _init():
            dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)

    def _step(pairs):
        masks = {}
        for a, runs in enumerate(_runs(pairs, 0, block_q // tq)):
            rows = slice(a * tq, (a + 1) * tq)
            if not runs:
                if nk == 1:
                    dq_ref[0, rows] = jnp.zeros((tq, g * D), dq_ref.dtype)
                continue
            cols = slice(runs[0][0] * tk, runs[-1][1] * tk)
            # bf16 — MXU operands stay in input dtype
            q_blk, do_blk = q_ref[0, rows], do_ref[0, rows]
            k_blk, v_blk = k_ref[0, cols], v_ref[0, cols]
            dqs = []
            for r in range(g):
                lse = lse_ref[r, 0, rows][:, None]      # [tq, 1]
                delta = delta_ref[r, 0, rows][:, None]  # [tq, 1]
                p = jnp.exp(_nt(_head_lanes(q_blk, r, g, D), k_blk) * sm_scale - lse)
                p = _mask_runs(masks, p, runs, tk, 0.0, 0, 1)  # [tq, keys]
                dp = _nt(_head_lanes(do_blk, r, g, D), v_blk)
                dqs.append(_nn((p * (dp - delta)).astype(k_blk.dtype), k_blk))
            dq = _join_lanes(dqs, D)
            if nk == 1:
                dq_ref[0, rows] = (dq * sm_scale).astype(dq_ref.dtype)
            else:
                dq_acc_ref[rows] = dq_acc_ref[rows] + dq

    _walk_step(qi, j, nq, nk, block_q, block_k, tq, tk, causal, seq_q, seq_kv, False, _step)

    if nk != 1:
        @pl.when(j == nk - 1)
        def _flush():
            dq_ref[0] = (dq_acc_ref[...] * sm_scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_acc_ref, dv_acc_ref,
    *, head_dim: int, nq: int, nk: int, causal: bool, sm_scale: float, seq_q: int,
    seq_kv: int,
):
    """dK/dV for one k block: stream q blocks from the causal diagonal down.

    dV = Pᵀ·dO ; dK = scale · dSᵀ·Q. Padded q rows contribute nothing because
    dO and Δ are zero-padded there (dS = P∘(0 − 0) = 0)."""
    from jax.experimental import pallas as pl

    ki = pl.program_id(1)
    i = pl.program_id(2)
    block_q, block_k = q_ref.shape[1], k_ref.shape[1]
    D, g = head_dim, q_ref.shape[2] // head_dim
    tq, tk = _sub_tiles("flash_bwd_dkv", D, block_q, block_k)

    if nq != 1:
        @pl.when(i == 0)
        def _init():
            dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
            dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    # The scores are computed TRANSPOSED, [keys, queries]: every product is
    # then plain or contracts both operands' last axis (no [tq, tk] transpose
    # through the XLU), and lse and Δ are rows as they lie in memory.
    def _step(pairs):
        masks = {}
        for b, runs in enumerate(_runs(pairs, 1, block_k // tk)):
            cols = slice(b * tk, (b + 1) * tk)
            if not runs:  # padding past the last key: the caller cuts it off
                continue
            rows = slice(runs[0][0] * tq, runs[-1][1] * tq)
            # bf16 — MXU operands stay in input dtype
            q_blk, do_blk = q_ref[0, rows], do_ref[0, rows]
            k_blk, v_blk = k_ref[0, cols], v_ref[0, cols]
            dks, dvs = [], []
            for r in range(g):
                pt = jnp.exp(_nt(_head_lanes(k_blk, r, g, D), q_blk) * sm_scale
                             - lse_ref[r, :, rows])
                # The mask's row term: padded q rows must not reach p (exp
                # against a padded-row lse can overflow to inf, and inf · 0,
                # the zero-padded dO, would make NaNs).
                pt = _mask_runs(masks, pt, runs, tq, 0.0, 1, 1)  # [tk, queries]
                dvs.append(_nn(pt.astype(do_blk.dtype), do_blk))
                dst = pt * (_nt(_head_lanes(v_blk, r, g, D), do_blk) - delta_ref[r, :, rows])
                dks.append(_nn(dst.astype(q_blk.dtype), q_blk))
            dk, dv = _join_lanes(dks, D), _join_lanes(dvs, D)
            if nq == 1:
                dk_ref[0, cols] = (dk * sm_scale).astype(dk_ref.dtype)
                dv_ref[0, cols] = dv.astype(dv_ref.dtype)
            else:
                dk_acc_ref[cols] = dk_acc_ref[cols] + dk
                dv_acc_ref[cols] = dv_acc_ref[cols] + dv

    _walk_step(i, ki, nq, nk, block_q, block_k, tq, tk, causal, seq_q, seq_kv, True, _step)

    if nq != 1:
        @pl.when(i == nq - 1)
        def _flush():
            dk_ref[0] = (dk_acc_ref[...] * sm_scale).astype(dk_ref.dtype)
            dv_ref[0] = dv_acc_ref[...].astype(dv_ref.dtype)


def _flash_bwd_call(qr, kr, vr, o, lse, do, head_dim: int, causal: bool, sm_scale: float,
                    block_q: int, block_k: int, interpret: bool = False):
    """Flash backward over operands as `_to_operand` lays them (o and dO
    too): two Pallas passes (dq over q blocks; dk/dv over k blocks) against
    the saved logsumexp — no S×S materialization. -> dq, dk, dv, same form."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, S, lanes = qr.shape
    Skv, D = kr.shape[1], head_dim
    g, steps, at = _head_blocks(qr, D)
    W, H = g * D, lanes // D
    BH = B * H
    block_q, block_k = _flash_blocks(S, Skv, D, block_q, block_k)
    S_p = -(-S // block_q) * block_q
    Skv_p = -(-Skv // block_k) * block_k

    # Δ = rowsum(dO ∘ O) a head, in float32. Over [B, S, H·Dh] that is a sum
    # over each head's run of lanes, said as a product against the heads' 0/1
    # columns at full precision (float32's parts against exact ones: the
    # float32 sum). As a multiply and a sum over a split lane axis XLA wrote
    # dO ∘ O out in float32, copied it to sequence-minor and summed it there
    # (PERF.md §6, PR 49).
    dr = do.astype(jnp.float32) * o.astype(jnp.float32)
    if H == 1:
        dr = jnp.sum(dr, axis=-1)
    else:
        seg = (jnp.arange(lanes)[:, None] // D == jnp.arange(H)[None, :]).astype(jnp.float32)
        dr = jnp.einsum("bsf,fh->bhs", dr, seg, precision=jax.lax.Precision.HIGHEST)
    dr = dr.reshape(BH, 1, S)
    gr = do
    if S_p != S:
        qr = jnp.pad(qr, ((0, 0), (0, S_p - S), (0, 0)))
        gr = jnp.pad(gr, ((0, 0), (0, S_p - S), (0, 0)))
        dr = jnp.pad(dr, ((0, 0), (0, 0), (0, S_p - S)))
    if Skv_p != Skv:
        kr = jnp.pad(kr, ((0, 0), (0, Skv_p - Skv), (0, 0)))
        vr = jnp.pad(vr, ((0, 0), (0, Skv_p - Skv), (0, 0)))
    # lse arrives padded to (BH, 1, S_p) from the forward (same block_q).
    lr = lse

    nq = S_p // block_q
    nk = Skv_p // block_k
    row_offset = Skv - S
    kwargs = dict(head_dim=D, nq=nq, nk=nk, causal=causal, sm_scale=sm_scale, seq_q=S,
                  seq_kv=Skv)

    def outer(bh, i, j):  # the block a kernel's outer axis names
        b, h = at(bh)
        return (b, i, h)

    if causal:
        def kv_index(bh, i, j):
            b, h = at(bh)
            return (b, jnp.minimum(j, _causal_last_kv(i, block_q, block_k, row_offset, nk)), h)

        def q_block(ki, i):
            return jnp.maximum(i, _causal_first_q(ki, block_q, block_k, row_offset, nq))
    else:
        def kv_index(bh, i, j):
            b, h = at(bh)
            return (b, j, h)

        def q_block(ki, i):
            return i

    def q_index(bh, ki, i):
        b, h = at(bh)
        return (b, q_block(ki, i), h)

    def q_row_index(bh, ki, i):
        return (bh, 0, q_block(ki, i))

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, **kwargs),
        out_shape=jax.ShapeDtypeStruct(qr.shape, qr.dtype),
        grid=(steps, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, W), outer),
            pl.BlockSpec((1, block_k, W), kv_index),
            pl.BlockSpec((1, block_k, W), kv_index),
            pl.BlockSpec((1, block_q, W), outer),
            pl.BlockSpec((g, 1, block_q), lambda bh, i, j: (bh, 0, i)),
            pl.BlockSpec((g, 1, block_q), lambda bh, i, j: (bh, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_q, W), outer),
        scratch_shapes=[pltpu.VMEM((block_q, W), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        cost_estimate=pl.CostEstimate(
            flops=6 * BH * S * Skv * D,
            bytes_accessed=3 * (qr.size + kr.size + vr.size) * qr.dtype.itemsize,
            transcendentals=BH * S * Skv,
        ),
        interpret=interpret,
        name="flash_bwd_dq",
    )(qr, kr, vr, gr, lr, dr)

    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, **kwargs),
        out_shape=(
            jax.ShapeDtypeStruct(kr.shape, kr.dtype),
            jax.ShapeDtypeStruct(vr.shape, vr.dtype),
        ),
        grid=(steps, nk, nq),
        in_specs=[
            pl.BlockSpec((1, block_q, W), q_index),
            pl.BlockSpec((1, block_k, W), outer),
            pl.BlockSpec((1, block_k, W), outer),
            pl.BlockSpec((1, block_q, W), q_index),
            pl.BlockSpec((g, 1, block_q), q_row_index),
            pl.BlockSpec((g, 1, block_q), q_row_index),
        ],
        out_specs=(
            pl.BlockSpec((1, block_k, W), outer),
            pl.BlockSpec((1, block_k, W), outer),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_k, W), jnp.float32),
            pltpu.VMEM((block_k, W), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        cost_estimate=pl.CostEstimate(
            flops=8 * BH * S * Skv * D,  # 4 matmuls: s, dv, dp, dk
            bytes_accessed=3 * (qr.size + kr.size + vr.size) * qr.dtype.itemsize,
            transcendentals=BH * S * Skv,
        ),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qr, kr, vr, gr, lr, dr)
    return dq[:, :S], dk[:, :Skv], dv[:, :Skv]


def _flash_bwd_pallas(q, k, v, o, lse, g, causal: bool, sm_scale: float,
                      block_q: int, block_k: int, interpret: bool = False):
    """`_flash_bwd_call` from and to [B, H, S, Dh]."""
    B, H, _, D = q.shape
    grads = _flash_bwd_call(*(_to_operand(x) for x in (q, k, v, o)), lse, _to_operand(g),
                            D, causal, sm_scale, block_q, block_k, interpret)
    return tuple(_from_operand(x, B, H) for x in grads)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


FLASH_KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def flash_kernels_in(hlo_text: str) -> dict:
    """Occurrences of each flash kernel's Mosaic custom call in the text of
    a compiled (or lowered-for-TPU) program — how a caller proves a step runs
    the kernels and not the XLA reference `_on_tpu()` would otherwise pick.
    Lowered StableHLO carries `kernel_name = "<name>"`; compiled HLO carries
    the name in the call's `op_name` metadata."""
    counts = dict.fromkeys(FLASH_KERNELS, 0)
    for line in hlo_text.splitlines():
        if "tpu_custom_call" in line:
            for name in FLASH_KERNELS:
                if re.search(rf'(kernel_name = "|op_name="[^"]*\b){name}\b', line):
                    counts[name] += 1
    return counts


# The custom_vjp boundary is drawn around the operands' form: residuals and
# cotangents cross it as the kernels read and write them.
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(qr, kr, vr, head_dim, causal, sm_scale, block_q, block_k):
    return _flash_fwd_call(qr, kr, vr, head_dim, causal, sm_scale, block_q, block_k)


def _flash_fwd(qr, kr, vr, head_dim, causal, sm_scale, block_q, block_k):
    out, lse = _flash_fwd_call(
        qr, kr, vr, head_dim, causal, sm_scale, block_q, block_k, return_lse=True
    )
    return out, (qr, kr, vr, out, lse)


def _flash_bwd(head_dim, causal, sm_scale, block_q, block_k, res, g):
    return _flash_bwd_call(*res, g, head_dim, causal, sm_scale, block_q, block_k)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_stats(qr, kr, vr, head_dim, causal, sm_scale, block_q, block_k):
    return _flash_fwd_call(
        qr, kr, vr, head_dim, causal, sm_scale, block_q, block_k, return_lse=True
    )


def _flash_stats_fwd(qr, kr, vr, head_dim, causal, sm_scale, block_q, block_k):
    from jax.ad_checkpoint import checkpoint_name

    out, lse = _flash_fwd_call(
        qr, kr, vr, head_dim, causal, sm_scale, block_q, block_k, return_lse=True
    )
    # Name the values HERE so the residual vars themselves carry the names:
    # under jax.checkpoint with save_only_these_names("attn_out","attn_lse")
    # the saved copies satisfy both the downstream primal use and the
    # backward's residual needs, and the rematerialized forward's pallas
    # call DCEs away — attention forward runs exactly once per step.
    out = checkpoint_name(out, "attn_out")
    lse = checkpoint_name(lse, "attn_lse")
    return (out, lse), (qr, kr, vr, out, lse)


def _flash_stats_bwd(head_dim, causal, sm_scale, block_q, block_k, res, g):
    g_o, _ = g  # lse cotangent is structurally zero (stats are not a loss path)
    return _flash_bwd_call(*res, g_o, head_dim, causal, sm_scale, block_q, block_k)


_flash_stats.defvjp(_flash_stats_fwd, _flash_stats_bwd)


def _through_operands(kernel, q, k, v, *static):
    """`kernel` (a custom_vjp over operands) from and to [B, H, S, Dh]."""
    B, H, _, D = q.shape
    out = kernel(_to_operand(q), _to_operand(k), _to_operand(v), D, *static)
    if isinstance(out, tuple):
        return _from_operand(out[0], B, H), *out[1:]
    return _from_operand(out, B, H)


def _flash_fwd_pallas(q, k, v, causal: bool, sm_scale: float, block_q: int, block_k: int,
                      interpret: bool = False, return_lse: bool = False):
    """`_flash_fwd_call` from and to [B, H, S, Dh]."""
    return _through_operands(_flash_fwd_call, q, k, v, causal, sm_scale, block_q, block_k,
                             interpret, return_lse)


def flash_attention_with_stats(
    q,
    k,
    v,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
):
    """`flash_attention` that also returns the per-row logsumexp.

    Exists for remat integration: the VPU-bound forward kernel is the most
    expensive recompute in a rematerialized transformer block, and saving
    (out, lse) — named inside the vjp forward rule — lets a
    `save_only_these_names` policy skip exactly that rerun
    (models/gpt.py `remat_policy="attn"`).

    The returned lse is STOP-GRADIENTED on every backend: the flash
    backward implements only d(out); declaring lse non-differentiable here
    keeps TPU and the off-TPU reference path consistent instead of silently
    dropping a cotangent on one of them. Use it for logging/remat, not as
    a loss term."""
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if not _on_tpu():
        out = attention_reference(q, k, v, causal, scale)
        *_, S, D = q.shape
        Skv = k.shape[-2]
        logits = jnp.einsum(
            "bhsd,bhtd->bhst", q, k, preferred_element_type=jnp.float32
        ) * scale
        if causal:
            qpos = jnp.arange(S)[:, None] + (Skv - S)
            logits = jnp.where(qpos >= jnp.arange(Skv)[None, :], logits, _NEG_INF)
        B, H = q.shape[0], q.shape[1]
        lse = jax.nn.logsumexp(logits, axis=-1).reshape(B * H, 1, S)
        return out, jax.lax.stop_gradient(lse)
    if block_q is None:
        block_q = 1024
    if block_k is None:
        block_k = 1024
    out, lse = _through_operands(_flash_stats, q, k, v, causal, scale, block_q, block_k)
    return out, jax.lax.stop_gradient(lse)


def flash_attention(
    q,
    k,
    v,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
):
    """Blockwise attention. Pallas on TPU; XLA reference elsewhere.

    Default blocks (1024, 1024): blocks ≥2048 failed to compile on an
    earlier installation, and a grid step costs more than a small block's
    work, so the causal triangle is walked INSIDE a block (`_sub_tile`).
    What the kernels reach today is read per cell (`flash_*_roofline`,
    PERF.md). Note the D=64 head dim caps attention matmuls at ~50% MXU
    utilization (the contraction or output dim is half the 128-wide
    systolic array)."""
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if not _on_tpu():
        return attention_reference(q, k, v, causal, scale)
    if block_q is None:
        block_q = 1024
    if block_k is None:
        block_k = 1024
    return _through_operands(_flash, q, k, v, causal, scale, block_q, block_k)


# ------------------------------------------------------------ ring attention
def _chunk_attn(q, k, v, mask, scale):
    """One K/V chunk's contribution with softmax stats (all fp32)."""
    s = jnp.einsum("bhsd,bhtd->bhst", q, k, preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask, s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)  # [B,H,S,1]
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhst,bhtd->bhsd", p, v.astype(jnp.float32))
    return o, m, l


def ring_attention(
    q,
    k,
    v,
    axis: str = "sp",
    causal: bool = True,
    sm_scale: Optional[float] = None,
):
    """Blockwise ring attention over sequence shards (call under shard_map).

    Per device: q,k,v are the LOCAL sequence shard [B, H, S_local, D]. Each of
    the `axis_size` steps attends q against the K/V block currently resident,
    then rotates K/V one hop around the ring (`ppermute` compiles to
    neighbor ICI transfers, overlapped by XLA with the matmuls). Causal
    masking uses global positions, so fully-masked steps contribute nothing.
    """
    n = jax.lax.axis_size(axis)  # static int: scan length + ppermute table
    my = jax.lax.axis_index(axis)
    B, H, S_local, D = q.shape
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)
    qf = q.astype(jnp.float32)

    q_start = my * S_local
    rows = q_start + jnp.arange(S_local)[:, None]  # global q positions

    def step(carry, i):
        acc, m_prev, l_prev, k_cur, v_cur = carry
        src = (my - i) % n  # whose K/V block we hold at step i
        kv_start = src * S_local
        cols = kv_start + jnp.arange(S_local)[None, :]
        mask = (rows >= cols) if causal else jnp.ones((S_local, S_local), bool)
        o_c, m_c, l_c = _chunk_attn(qf, k_cur, v_cur, mask, scale)
        m_new = jnp.maximum(m_prev, m_c)
        alpha = jnp.exp(m_prev - m_new)
        beta = jnp.exp(m_c - m_new)
        acc = acc * alpha + o_c * beta
        l_new = l_prev * alpha + l_c * beta
        perm = [(d, (d + 1) % n) for d in range(n)]
        k_nxt = jax.lax.ppermute(k_cur, axis, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis, perm)
        return (acc, m_new, l_new, k_nxt, v_nxt), None

    init = (
        jnp.zeros((B, H, S_local, D), jnp.float32),
        jnp.full((B, H, S_local, 1), _NEG_INF, jnp.float32),
        jnp.zeros((B, H, S_local, 1), jnp.float32),
        k,
        v,
    )
    (acc, m, l, _, _), _ = jax.lax.scan(step, init, jnp.arange(n))
    return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)


def ulysses_attention(
    q,
    k,
    v,
    axis: str = "sp",
    causal: bool = True,
    sm_scale: Optional[float] = None,
):
    """DeepSpeed-Ulysses-style context parallelism (call under shard_map).

    Inputs are sequence-sharded [B, H, S_local, D]; `all_to_all` swaps the
    shard axis from sequence to heads, each device runs FULL-sequence
    attention over H/n heads, then swaps back. Requires H % axis_size == 0.
    """
    # [B, H, S/n, D] -> [B, H/n, S, D]
    q2 = jax.lax.all_to_all(q, axis, split_axis=1, concat_axis=2, tiled=True)
    k2 = jax.lax.all_to_all(k, axis, split_axis=1, concat_axis=2, tiled=True)
    v2 = jax.lax.all_to_all(v, axis, split_axis=1, concat_axis=2, tiled=True)
    o2 = flash_attention(q2, k2, v2, causal=causal, sm_scale=sm_scale)
    # [B, H/n, S, D] -> [B, H, S/n, D]
    return jax.lax.all_to_all(o2, axis, split_axis=2, concat_axis=1, tiled=True)
