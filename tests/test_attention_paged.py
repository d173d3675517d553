"""Attention over the paged pool (`ops/paged_attention.py`). A chunk's
attention over a wide table as ONE kernel (`paged_chunk_attention`): in
interpret mode against the plain key loop it stands in for, through the paged
programs themselves, and the rule on shapes that gives a program its form
(`paged_attn_form`), as the program reads it and as the engine's host counts
with it. Below it the DECODE step's kernel (`paged_decode_attention`: each
lane's own blocks through its table) the same way against the gather it
stands in for."""

import contextlib
import functools

import numpy as np
import pytest

BS = 8          # tokens a block
NB = 96         # blocks of the pool
TILE = 32       # keys a tile of the key loop here: a table of 16 blocks is 4 tiles
W = 16
TOL = 3e-5      # float32 programs, logits near 1

_COMMON = dict(vocab_size=64, n_layers=2, d_model=64, d_mlp=96, max_seq=256,
               attn_impl="ref", remat=False, norm="rmsnorm", activation="swiglu",
               pos="rotary", tie_embeddings=False)
MODELS = {
    # ONE K/V head, the values inside the key row: 128 + 8 features in a row
    # of 256, the last 120 columns padding
    "latent": dict(n_heads=4, d_head=16, q_lora_rank=24, kv_lora_rank=128,
                   rotary_dim=8, init="unit_stream",
                   init_gains=(("embed", 1.5), ("dq", 1.0), ("q", 1.5), ("dkv", 1.0),
                               ("k", 1.5), ("v", 1.0), ("o", 0.9), ("mlp_in", 1.0),
                               ("mlp_out", 0.5), ("head", 1.0))),
    "grouped": dict(n_heads=4, n_kv_heads=2, d_head=128, rotary_dim=32),
    # layer 0 global without positions, layer 1 rotary under a window of 40
    # keys: two KV groups, two tables, the window a traced scalar a layer
    "grouped-window": dict(n_heads=4, n_kv_heads=2, d_head=128, rotary_dim=32,
                           rope_layout=(0, 1), sliding_window_layout=(0, 1),
                           sliding_window=40),
    "multi-head": dict(n_heads=2, d_head=128, rotary_dim=32),
}
# A table is a map, not a range: scattered, unordered physical blocks.
TABLES = np.asarray([
    [7, 3, 21, 12, 5, 30, 9, 18, 40, 2, 33, 27, 14, 36, 1, 25],
    [44, 8, 19, 31, 6, 38, 11, 29, 47, 16, 4, 35, 23, 42, 10, 20],
    [50, 61, 52, 77, 54, 69, 56, 83, 58, 71, 60, 51, 62, 79, 64, 53],
    [90, 66, 81, 68, 73, 70, 85, 72, 55, 74, 87, 76, 57, 78, 89, 80],
], np.int32)


def _cfg(model):
    import jax.numpy as jnp

    from ray_tpu.models.gpt import GPTConfig

    return GPTConfig(**_COMMON, **MODELS[model], dtype=jnp.float32)


def _form(cfg, tokens, width, block):
    """The rule's answer for a program of `cfg`, as `_paged_layers` and the
    engine ask it."""
    from ray_tpu.models.gpt import kv_head_rows
    from ray_tpu.ops.paged_attention import paged_attn_form

    return paged_attn_form(tokens, width, block, *kv_head_rows(cfg)[1:], cfg.dtype)


@contextlib.contextmanager
def _programs(by_kernel: bool):
    """The three paged programs jitted anew, their key loop in tiles of
    `TILE` keys; with `by_kernel` as the chip traces them (`_on_tpu`), the
    chunk kernel in interpret mode and the norms' kernels left to their plain
    forms (they are not what is under test)."""
    import jax

    from jax.experimental.pallas import tpu as pltpu

    from ray_tpu.models import gpt
    from ray_tpu.ops import attention, norms, paged_attention
    from ray_tpu.serve.engine import engine as engine_module

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(paged_attention, "_ATTN_TILE_KEYS", TILE)
        mp.setattr(engine_module, "_JITS", None)    # the engine's traces too
        if by_kernel:
            mp.setattr(attention, "_on_tpu", lambda: True)
            mp.setattr(paged_attention, "paged_chunk_attention", functools.partial(
                paged_attention.paged_chunk_attention, interpret=True))
            # a decode step traced so takes ITS kernel (uninitialised rows NaN),
            # a few blocks a DMA group so that a lane here has several
            mp.setattr(paged_attention, "_DECODE_GROUP_BYTES", 128 << 10)
            mp.setattr(paged_attention, "paged_decode_attention", functools.partial(
                paged_attention.paged_decode_attention, interpret=pltpu.InterpretParams()))
            mp.setattr(norms, "_rmsnorm_pallas", norms._rmsnorm_ref)
        yield (jax.jit(lambda *a: gpt.prefill_paged(*a), static_argnums=(6,)),
               jax.jit(lambda *a: gpt.verify_step_paged(*a), static_argnums=(6,)))


def _tables(cfg, lanes):
    """[lanes, W] or [lanes, G, W]: every group's table its own blocks."""
    from ray_tpu.models.gpt import kv_layout

    G = len(kv_layout(cfg).windows)
    t = np.stack([TABLES[2 * b:2 * b + G] for b in range(lanes)])
    return t if G > 1 else t[:, 0]


def _prefill(prefill, params, cfg, tokens, table, kv, chunks):
    """The prompt `tokens` in chunks of the given lengths, each padded to a
    bucket of 32: (the last chunk's logits, kv)."""
    import jax.numpy as jnp

    start = 0
    for n in chunks:
        padded = np.zeros((1, 32), np.int32)
        padded[0, :n] = tokens[start:start + n]
        logits, kv = prefill(params, jnp.asarray(padded), jnp.int32(n),
                             jnp.int32(start), jnp.asarray(table), kv, cfg)
        start += n
    return logits, kv


def _poison(kv, blocks):
    """NaN in every row of `blocks` in every layer: a tile fetched and
    multiplied, even under a mask that is false everywhere, would show
    (0 x NaN)."""
    import jax.numpy as jnp

    blocks = jnp.asarray(np.asarray(blocks).reshape(-1))
    return {name: rows.at[:, blocks].set(jnp.nan) for name, rows in kv.items()}


def _chunks(programs, cfg, params, tokens):
    """B = 1: a 75-token prompt in chunks of 30, 20 and 25 over a table of 4
    tiles; the second chunk's queries (positions 30..49) straddle the edge of
    tile 0, the third's (50..74) that of tile 1, and the sequence never
    reaches tile 3, whose blocks hold NaN: `trips` < `NT`."""
    from ray_tpu.models.gpt import init_paged_cache

    prefill, _ = programs
    table = _tables(cfg, 1)[0]
    kv = _poison(init_paged_cache(cfg, NB, BS), table[..., 12:])
    logits, kv = _prefill(prefill, params, cfg, tokens, table, kv, (30, 20, 25))
    return logits, kv, table[..., :12]


def _lanes(programs, cfg, params, tokens):
    """B = 2 and a padding lane between them: a verify step of 3 tokens a
    lane (rows padded to a sublane tile), lane 0 at position 100 (its window
    layer starts at tile 1: `first` > 0, and tile 0's blocks hold NaN by
    then), lane 2 at position 37 with 2 real tokens (it needs tiles 0 and 1
    of the 4 the step's trips cover)."""
    import jax.numpy as jnp

    from ray_tpu.models.gpt import init_paged_cache, kv_layout

    prefill, verify = programs
    tables = _tables(cfg, 2)
    kv = init_paged_cache(cfg, NB, BS)
    _, kv = _prefill(prefill, params, cfg, tokens, tables[0], kv, (32, 32, 32, 4))
    _, kv = _prefill(prefill, params, cfg, tokens[50:], tables[1], kv, (32, 5))
    windows = kv_layout(cfg).windows
    if any(windows):    # the window group's first tile: no query reaches back
        kv = _poison(kv, tables[0][list(windows).index(max(windows))][:4])
    tables = np.stack([tables[0], np.zeros_like(tables[0]), tables[1]])
    logits, kv = verify(
        params, jnp.asarray(np.stack([tokens[100:103], [0, 0, 0], tokens[87:90]])),
        jnp.asarray([100, 0, 37], jnp.int32), jnp.asarray([3, 0, 2], jnp.int32),
        jnp.asarray(tables), kv, cfg)
    rows = np.concatenate([tables[0][..., 4:13].reshape(-1), tables[2][..., :5].reshape(-1)])
    return (logits[0], logits[2, :2]), kv, rows


STEPS = {"chunks-B1": _chunks, "lanes-B2-and-padding": _lanes}


@pytest.mark.parametrize("step", list(STEPS))
@pytest.mark.parametrize("model", list(MODELS))
def test_the_chunk_kernel_is_the_key_loop(model, step):
    """The same programs on the same inputs by the plain loop and by the
    kernel: the logits of every real token and the pool's rows of every
    block the step may read or write agree, NaN in tiles outside the bounds
    reaches neither, and the kernel is in the traced program."""
    import jax

    from ray_tpu.models import gpt
    from ray_tpu.ops.paged_attention import CHUNK_KERNEL, KEY_LOOP

    cfg = _cfg(model)
    params = gpt.init_params(jax.random.PRNGKey(5), cfg)
    tokens = np.random.default_rng(11).integers(1, cfg.vocab_size, 128).astype(np.int32)
    got = {}
    for by_kernel in (False, True):
        with _programs(by_kernel) as programs:
            assert _form(cfg, 32, W, BS) == (CHUNK_KERNEL if by_kernel else KEY_LOOP)
            logits, kv, blocks = STEPS[step](programs, cfg, params, tokens)
            got[by_kernel] = jax.tree_util.tree_map(
                np.asarray, (logits, {k: v[:, blocks.reshape(-1)] for k, v in kv.items()}))
    for plain, kernel in zip(*map(jax.tree_util.tree_leaves, (got[False], got[True]))):
        assert np.isfinite(plain).all() and np.isfinite(kernel).all()
        assert np.abs(plain - kernel).max() < TOL


def test_the_kernel_is_named_in_the_program_and_the_loop_is_gone():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt
    from ray_tpu.ops import paged_attention

    cfg = _cfg("latent")
    params = jax.eval_shape(lambda k: gpt.init_params(k, cfg), jax.random.PRNGKey(0))
    kv = jax.eval_shape(lambda: gpt.init_paged_cache(cfg, NB, BS))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    calls = {}
    for by_kernel in (False, True):
        with _programs(by_kernel) as (prefill, _):
            jaxpr = str(jax.make_jaxpr(
                lambda *a: gpt.prefill_paged(*a, cfg)
            )(params, i32(1, 32), i32(), i32(), i32(W), kv))
        calls[by_kernel] = (jaxpr.count(paged_attention.PAGED_CHUNK_KERNEL), "while" in jaxpr)
    # once, in the layer scan's body; the plain form's one loop a layer is gone
    assert calls == {False: (0, True), True: (1, False)}


# (tokens a lane, table width in blocks, block size) at the module's own tile
RULE = [
    ("ax-k1", {}, 512, 256, 64, True),
    ("ax-k1", {}, 512, 16, 64, False),          # 1,024 keys: one tile
    ("ax-k1", {}, 1, 256, 64, False),           # the decode step
    ("smallthinker-21b-a3b", {}, 512, 128, 64, True),
    ("smallthinker-21b-a3b", {}, 256, 8, 64, False),
    ("ouro-2.6b", {}, 256, 128, 16, True),
    ("ouro-2.6b", {}, 256, 64, 16, False),
    ("jamba2-3b", {}, 256, 16, 128, True),
    ("jamba2-3b", {}, 256, 8, 128, False),
    ("gpt2-large", {}, 64, 64, 16, False),      # 1,024 positions: never two tiles
    ("gpt2-large", {}, 64, 128, 16, False),     # and heads of 64 fill no lane tile
    ("gptj-6b", {}, 64, 128, 16, True),
]


@pytest.mark.parametrize("model,overrides,tokens,width,block,want", RULE,
                         ids=[f"{r[0]}-{r[2]}x{r[3]}x{r[4]}" for r in RULE])
def test_the_rule_is_a_function_of_shapes(model, overrides, tokens, width, block, want,
                                          monkeypatch):
    """On the chip the rule says what the shapes say; off it, never."""
    from ray_tpu.models import gpt
    from ray_tpu.ops import attention
    from ray_tpu.ops.paged_attention import CHUNK_KERNEL, KEY_LOOP, ONE_SHOT

    cfg = gpt.CONFIGS[model](**overrides)
    assert _form(cfg, tokens, width, block) in (KEY_LOOP, ONE_SHOT)
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    assert (_form(cfg, tokens, width, block) == CHUNK_KERNEL) is want


def test_the_engine_counts_chunks_with_the_programs_rule():
    """`attn_chunks` counts every prefill chunk program dispatched and
    `attn_chunks_kernel` those whose shapes the rule sends to the kernel: a
    100-token prompt's table is 16 blocks = 4 tiles wide (every one of its
    chunks), a 20-token prompt's 4 blocks = one tile (none). The kernel runs
    (interpret mode) and the tokens are the plain loop's."""
    import jax

    from ray_tpu.models import gpt
    from ray_tpu.serve.engine import EngineOptions, InferenceEngine

    cfg = _cfg("multi-head")
    params = jax.tree_util.tree_map(
        lambda a: a * 3.0, gpt.init_params(jax.random.PRNGKey(3), cfg))
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, 64, 100).tolist(), rng.integers(1, 64, 20).tolist()]
    seen = {}
    for by_kernel in (False, True):
        with _programs(by_kernel):
            engine = InferenceEngine(cfg, params=params, options=EngineOptions(
                num_blocks=NB, block_size=BS, max_num_seqs=4, prefill_chunk_tokens=32,
                max_step_tokens=64, enable_prefix_caching=False))
            ids = [engine.submit(prompt, max_new_tokens=4) for prompt in prompts]
            for _ in range(200):
                if not engine.scheduler.has_work():
                    break
                engine.step()
            stats = engine.stats()
            seen[by_kernel] = ([list(engine.stream(rid)) for rid in ids],
                               stats["attn_chunks"], stats["attn_chunks_kernel"])
    assert seen[False][1:] == (5, 0)        # 100 tokens in 4 chunks of 32, 20 in one
    assert seen[True][1:] == (5, 4)
    assert seen[True][0] == seen[False][0] and all(len(t) == 4 for t in seen[True][0])


@pytest.mark.parametrize("width", [8, 128])
def test_gpt2_larges_programs_never_hold_the_kernel(width, monkeypatch):
    """The control: 1,024 positions are one tile and heads of 64 fill no
    lane tile, so even traced as the chip traces them, over a table wider
    than the model can fill, `gpt2-large`'s three programs hold no chunk
    kernel (off the chip their text is the parent's to the byte:
    `tests/test_axk1.py` pins it)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt
    from ray_tpu.ops import attention, paged_attention

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    cfg = gpt.CONFIGS["gpt2-large"](remat=False, remat_policy=None)
    params = jax.eval_shape(lambda k: gpt.init_params(k, cfg), jax.random.PRNGKey(0))
    kv = jax.eval_shape(lambda: gpt.init_paged_cache(cfg, 256, 16))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    programs = {
        "prefill": jax.make_jaxpr(lambda *a: gpt.prefill_paged(*a, cfg))(
            params, i32(1, 64), i32(), i32(), i32(width), kv),
        "decode": jax.make_jaxpr(lambda *a: gpt.decode_step_paged(*a, cfg))(
            params, i32(4), i32(4), i32(4, width), kv),
        "verify": jax.make_jaxpr(lambda *a: gpt.verify_step_paged(*a, cfg))(
            params, i32(4, 3), i32(4), i32(4), i32(4, width), kv),
    }
    for name, jaxpr in programs.items():
        assert paged_attention.PAGED_CHUNK_KERNEL not in str(jaxpr), name


def test_the_rehearsal_finds_the_copies_inside_a_layers_loop():
    """`scripts.paged_rehearse.loop_copy_bytes` on a program's text: the
    bytes `copy-start` / `copy-done` pairs move, by how many `while` bodies
    enclose them (what found the key loop's 64 MiB carry, and says that it
    is gone)."""
    from scripts.paged_rehearse import loop_copy_bytes

    text = """HloModule jit_prefill

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %n = f32[8]{0} negate(%p)
}

%key_loop_body (c: (f32[32768,512])) -> (f32[32768,512]) {
  %c = (f32[32768,512]{1,0}) parameter(0)
  %acc = f32[32768,512]{1,0} get-tuple-element(%c), index=0
  %copy-start.1 = (f32[32768,512]{1,0:S(1)}, f32[32768,512]{1,0}, u32[]) copy-start(%acc)
  %copy-done.1 = f32[32768,512]{1,0:S(1)} copy-done(%copy-start.1)
  ROOT %t = (f32[32768,512]{1,0}) tuple(%copy-done.1)
}

%key_loop_cond (c: (f32[32768,512])) -> pred[] {
  %c = (f32[32768,512]{1,0}) parameter(0)
  ROOT %lt = pred[] constant(true)
}

%layer_body (c: (f32[32768,512])) -> (f32[32768,512]) {
  %c = (f32[32768,512]{1,0}) parameter(0)
  %w = bf16[1024,640]{1,0} constant(0)
  %copy-start.2 = (bf16[1024,640]{1,0:S(1)}, bf16[1024,640]{1,0}, u32[]) copy-start(%w)
  %copy-done.2 = bf16[1024,640]{1,0:S(1)} copy-done(%copy-start.2)
  ROOT %while.2 = (f32[32768,512]{1,0}) while(%c), condition=%key_loop_cond, body=%key_loop_body
}

%layer_cond (c: (f32[32768,512])) -> pred[] {
  %c = (f32[32768,512]{1,0}) parameter(0)
  ROOT %lt = pred[] constant(true)
}

ENTRY %main (a: f32[32768,512]) -> (f32[32768,512]) {
  %a = f32[32768,512]{1,0} parameter(0)
  %t = (f32[32768,512]{1,0}) tuple(%a)
  ROOT %while.1 = (f32[32768,512]{1,0}) while(%t), condition=%layer_cond, body=%layer_body
}
"""
    assert loop_copy_bytes(text) == {1: 1024 * 640 * 2, 2: 32768 * 512 * 4}
    assert loop_copy_bytes(text.replace("copy-start", "copy-begin")) == {}


# ------------------------------------------------------- the decode kernel
#
# One decode step over a pool of random rows (the history a prefill would
# have left), by the gather at the table's width and by the kernel, through
# `decode_step_paged` itself. `None` is a padding lane: a null table.

_WINDOWED = dict(rope_layout=(0, 1), sliding_window_layout=(0, 1), sliding_window=150)
DECODE = {
    # model, block size, table width in blocks, the lanes' positions
    # R = 1 over two heads; a lane of ONE key, lanes at a block's edge
    "R1-blocks-of-16": (dict(n_heads=2, d_head=128, rotary_dim=32), 16, 8,
                        (100, 0, None, 15, 16, 37)),
    # a table of two 1,024-key tiles (the gather's form is the key loop):
    # lanes at the tile's edge either side, a short lane beside them
    "R1-tile-edge": (dict(n_heads=2, d_head=128, rotary_dim=32), 16, 128,
                     (1023, 1024, None, 300, 2047)),
    "R6-blocks-of-64": (dict(n_heads=6, n_kv_heads=1, d_head=128, rotary_dim=32), 64, 8,
                        (400, 63, 64, None)),
    # layer 0 global, layer 1 under a window of 150 keys shorter than the lanes:
    # two KV groups, two tables, the window group's entries below it RELEASED
    "R7-window-released": (dict(n_heads=14, n_kv_heads=2, d_head=128, rotary_dim=32,
                                **_WINDOWED), 64, 16, (900, None, 149, 150, 700)),
    "R8-blocks-of-128": (dict(n_heads=8, n_kv_heads=1, d_head=128, rotary_dim=32), 128, 4,
                         (500, 127, 128)),
    "R20-blocks-of-128": (dict(n_heads=20, n_kv_heads=1, d_head=128, rotary_dim=32), 128, 4,
                          (300, None, 5)),
    # a looped model: pass t of a layer reads the pool rows of ITS (pass, layer)
    "looped-pool": (dict(n_heads=2, d_head=128, rotary_dim=32, ut_steps=3), 16, 8,
                    (100, 3, None, 64)),
    # ONE K/V head whose values lie inside the key row, R = 4
    "latent": (MODELS["latent"], 8, 16, (100, 0, None, 8, 127)),
}


def _decode_case(name):
    """(cfg, params, pool of random rows, args of `decode_step_paged`, the
    blocks each real lane's position reaches a group)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt

    model, bs, width, lanes = DECODE[name]
    cfg = gpt.GPTConfig(**{**_COMMON, "max_seq": 2048}, **model, dtype=jnp.float32)
    params = gpt.init_params(jax.random.PRNGKey(5), cfg)
    windows = gpt.kv_layout(cfg).windows
    G, B = len(windows), len(lanes)
    nb = 1 + B * G * width
    rng = np.random.default_rng(3)
    kv = {k: jnp.asarray(rng.normal(size=rows.shape), jnp.float32)
          for k, rows in gpt.init_paged_cache(cfg, nb, bs).items()}
    tables = (1 + rng.permutation(nb - 1)).astype(np.int32).reshape(B, G, width)
    for b, pos in enumerate(lanes):
        for g, w in enumerate(windows):
            if pos is None:
                tables[b, g] = 0
            else:       # not yet allocated above the lane, released below its window
                tables[b, g, pos // bs + 1:] = 0
                tables[b, g, :max(pos - w + 1, 0) // bs if w else 0] = 0
    positions = np.asarray([pos or 0 for pos in lanes], np.int32)
    tokens = rng.integers(1, cfg.vocab_size, B).astype(np.int32)
    args = (jnp.asarray(tokens), jnp.asarray(positions),
            jnp.asarray(tables if G > 1 else tables[:, 0]))
    return cfg, params, kv, args, tables


@contextlib.contextmanager
def _decode_program(by_kernel: bool):
    """`decode_step_paged` jitted anew at the module's own tile of keys; with
    `by_kernel` as the chip traces it, the kernel in interpret mode."""
    import jax

    from ray_tpu.models import gpt
    from ray_tpu.ops import paged_attention

    with _programs(by_kernel), pytest.MonkeyPatch.context() as mp:
        mp.setattr(paged_attention, "_ATTN_TILE_KEYS", 1024)
        yield jax.jit(lambda *a: gpt.decode_step_paged(*a), static_argnums=(5,))


@pytest.mark.parametrize("case", list(DECODE))
def test_the_decode_kernel_is_the_gather(case):
    """The same decode step on the same pool by the gather at the table's
    width and by the kernel: the logits of every real lane and the pool's
    rows of every block a real lane holds agree, and the kernel is in the
    traced program."""
    import jax

    from ray_tpu.models import gpt
    from ray_tpu.ops import paged_attention

    cfg, params, kv, args, tables = _decode_case(case)
    real = np.asarray([pos is not None for pos in DECODE[case][3]])
    blocks = np.setdiff1d(tables[real], [0])    # block 0 takes the padding lanes' rows
    got = {}
    for by_kernel in (False, True):
        with _decode_program(by_kernel) as decode:
            assert (_form(cfg, 1, *DECODE[case][2:0:-1])
                    == paged_attention.DECODE_KERNEL) is by_kernel
            text = str(jax.make_jaxpr(lambda *a: gpt.decode_step_paged(*a, cfg))(
                params, *args, kv))
            assert (paged_attention.PAGED_DECODE_KERNEL in text) is by_kernel
            out, pool = decode(params, *args, kv, cfg)
            logits = out[0] if isinstance(out, tuple) else out
            got[by_kernel] = jax.tree_util.tree_map(
                np.asarray, (logits[real], {k: v[:, blocks] for k, v in pool.items()}))
    for plain, kernel in zip(*map(jax.tree_util.tree_leaves, (got[False], got[True]))):
        assert np.isfinite(plain).all() and np.isfinite(kernel).all()
        assert np.abs(plain - kernel).max() < TOL


@pytest.mark.parametrize("window", [None, 100, 16], ids=["global", "window-100", "window-16"])
def test_the_decode_kernel_fetches_a_lanes_own_blocks_and_no_other(window, monkeypatch):
    """NaN in every row of every block outside a lane's span (the null block,
    the blocks above its position, those a window has left behind, a padding
    lane's whole table): a block fetched and multiplied, even under a weight
    of 0, would show. Against the dense softmax over the clean pool."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    from ray_tpu.ops import paged_attention
    from ray_tpu.ops.paged_attention import NO_WINDOW

    monkeypatch.setattr(paged_attention, "_DECODE_GROUP_BYTES", 128 << 10)  # 4 blocks a group
    B, heads, R, dh, bs, width, depth, slot = 5, 2, 3, 128, 16, 24, 3, 2
    positions = np.asarray([383, 0, 200, 16, 15], np.int32)
    real = np.asarray([True, True, False, True, True])
    reach = NO_WINDOW if window is None else window
    rng = np.random.default_rng(7)
    nb = 1 + B * width
    clean = rng.normal(size=(2, depth, nb, bs, heads * dh)).astype(np.float32)
    table = (1 + rng.permutation(nb - 1)).astype(np.int32).reshape(B, width)
    first, blocks = paged_attention.paged_decode_span(np, positions, real, reach, bs, width)
    assert blocks[2] == 0 and (blocks[real] >= 1).all()
    poisoned = clean.copy()
    held = np.concatenate([table[b, first[b]:first[b] + blocks[b]] for b in range(B)])
    poisoned[:, :, np.setdiff1d(np.arange(nb), held)] = np.nan
    poisoned[:, np.arange(depth) != slot] = np.nan          # and every other layer's rows
    q = jnp.asarray(rng.normal(size=(B, heads, R, dh)), jnp.float32)
    out = paged_attention.paged_decode_attention(
        q, jnp.asarray(poisoned[0]), jnp.asarray(poisoned[1]), slot, jnp.asarray(table),
        jnp.asarray(positions), jnp.asarray(real), reach, dv=dh, sm_scale=0.1,
        interpret=pltpu.InterpretParams())
    k, v = (clean[i, slot][table].reshape(B, width * bs, heads, dh) for i in (0, 1))
    scores = np.einsum("bhrd,bthd->bhrt", np.asarray(q), k) * 0.1
    kp, qp = np.arange(width * bs)[None, None, None], positions[:, None, None, None]
    scores = np.where((kp <= qp) & (kp > qp - reach), scores, -1e30)
    want = np.einsum("bhrt,bthd->bhrd", jax.nn.softmax(scores, axis=-1), v)
    out = np.asarray(out)
    assert np.isfinite(out).all() and (out[2] == 0).all()       # a padding lane reads 0
    assert np.abs(out[real] - want[real]).max() < TOL


@pytest.mark.parametrize("lanes,widths,pool_blocks,traces", [
    (4, (2, 8, 32), 64, 1),         # every width padded to the pool's 64 blocks
    (4, (8, 128), 72, 2),           # a table wider than the pool has blocks stays as it is
    (64, (4, 16), 4096, 1),         # 64 lanes: to the 128 entries 32 KiB hold
    (64, (64, 256), 4104, 2),       # past them each width is its own
], ids=["to-the-pool", "past-the-pool", "to-the-bytes", "past-the-bytes"])
def test_decode_programs_of_one_lane_count_share_one_trace_of_the_kernel(
        lanes, widths, pool_blocks, traces, monkeypatch):
    """Tables reach the kernel padded to one width a lane count, so the decode
    programs a server warms for one lane bucket (a program a table width)
    trace the kernel body ONCE between them; another lane count is another
    trace."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import paged_attention
    from ray_tpu.ops.paged_attention import NO_WINDOW

    heads, R, dh, bs, depth = 2, 1, 128, 16, 2

    def program(B, width):
        arr = lambda dt, *shape: jax.ShapeDtypeStruct(shape, dt)  # noqa: E731
        pool = arr(jnp.bfloat16, depth, pool_blocks, bs, heads * dh)
        return jax.make_jaxpr(lambda q, k, v, slot, table, pos, real: (
            paged_attention.paged_decode_attention(q, k, v, slot, table, pos, real, NO_WINDOW,
                                                   dv=dh, sm_scale=0.1)))(
            arr(jnp.bfloat16, B, heads, R, dh), pool, pool, arr(jnp.int32),
            arr(jnp.int32, B, width), arr(jnp.int32, B), arr(jnp.bool_, B))

    traced, body = [], paged_attention._paged_decode_kernel
    monkeypatch.setattr(paged_attention, "_paged_decode_kernel",
                        lambda *refs, **sizes: traced.append(sizes) or body(*refs, **sizes))
    texts = [str(program(lanes, width)) for width in widths]
    assert all(paged_attention.PAGED_DECODE_KERNEL in text for text in texts)
    assert len(traced) == traces
    program(lanes // 2, widths[0])
    assert len(traced) == traces + 1


# (tokens a lane, block size) -> the decode kernel; on the chip
DECODE_RULE = [
    ("ouro-2.6b", 1, 16, True),                 # R = 1, 16 heads of 128
    ("smallthinker-21b-a3b", 1, 64, True),      # R = 7, window and global groups
    ("laguna-xs2", 1, 64, True),                # R = 6 and 8 by layer kind
    ("jamba2-3b", 1, 128, True),                # R = 20 over one head
    ("ax-k1", 1, 64, True),                     # the latent pool: values inside the row
    ("gptj-6b", 1, 16, True),
    ("gpt2-large", 1, 16, False),               # heads of 64 fill no lane tile
    ("ouro-2.6b", 2, 16, False),                # a verify step, a chunk: not one token
    ("ouro-2.6b", 256, 16, False),
    ("ouro-2.6b", 1, 8, False),                 # bfloat16 blocks of half a sublane tile
]


@pytest.mark.parametrize("model,tokens,block,want", DECODE_RULE,
                         ids=[f"{r[0]}-{r[1]}x{r[2]}" for r in DECODE_RULE])
def test_the_decode_rule_is_a_function_of_shapes(model, tokens, block, want, monkeypatch):
    """On the chip the rule says what the shapes say, whatever the table's
    width; off it, never (the CPU keeps the gather)."""
    from ray_tpu.models import gpt
    from ray_tpu.ops import attention
    from ray_tpu.ops.paged_attention import DECODE_KERNEL, KEY_LOOP, ONE_SHOT

    cfg = gpt.CONFIGS[model]()
    for width in (8, 128):
        assert _form(cfg, tokens, width, block) in (KEY_LOOP, ONE_SHOT)
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    for width in (8, 128):
        assert (_form(cfg, tokens, width, block) == DECODE_KERNEL) is want


def test_the_decode_kernel_is_in_the_decode_program_and_in_no_other():
    """Traced as the chip traces them: once a layer kind in the decode
    program (the layer scan's body), in no prefill and no verify program;
    `gpt2-large`'s decode program holds none (heads of 64)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt
    from ray_tpu.ops.paged_attention import PAGED_DECODE_KERNEL

    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    counts = {}
    with _programs(True):
        for name in ("grouped", "grouped-window"):
            cfg = _cfg(name)
            G = len(gpt.kv_layout(cfg).windows)
            table = (W,) if G == 1 else (G, W)
            params = jax.eval_shape(lambda k: gpt.init_params(k, cfg), jax.random.PRNGKey(0))
            kv = jax.eval_shape(lambda: gpt.init_paged_cache(cfg, NB, BS))
            counts[name] = tuple(
                str(jaxpr).count(PAGED_DECODE_KERNEL) for jaxpr in (
                    jax.make_jaxpr(lambda *a: gpt.decode_step_paged(*a, cfg))(
                        params, i32(4), i32(4), i32(4, *table), kv),
                    jax.make_jaxpr(lambda *a: gpt.prefill_paged(*a, cfg))(
                        params, i32(1, 32), i32(), i32(), i32(*table), kv),
                    jax.make_jaxpr(lambda *a: gpt.verify_step_paged(*a, cfg))(
                        params, i32(4, 3), i32(4), i32(4), i32(4, *table), kv)))
        cfg = gpt.CONFIGS["gpt2-large"](remat=False, remat_policy=None)
        params = jax.eval_shape(lambda k: gpt.init_params(k, cfg), jax.random.PRNGKey(0))
        kv = jax.eval_shape(lambda: gpt.init_paged_cache(cfg, 256, 16))
        counts["gpt2-large"] = str(jax.make_jaxpr(
            lambda *a: gpt.decode_step_paged(*a, cfg))(
                params, i32(4), i32(4), i32(4, 8), kv)).count(PAGED_DECODE_KERNEL)
    assert counts == {"grouped": (1, 0, 0), "grouped-window": (1, 0, 0), "gpt2-large": 0}


def test_the_host_counts_a_decode_programs_keys_by_lane():
    """`paged_attn_cover` under the decode kernel's form: each real lane's own
    blocks under the layer's window, from the function the kernel takes its
    bounds from; under the key loop's, the gather's lanes x trips x tile."""
    from ray_tpu.ops.paged_attention import (
        DECODE_KERNEL, KEY_LOOP, paged_attn_cover, paged_decode_span)

    pos = np.asarray([2047, 300, 0, 0])
    real = np.asarray([True, True, False, False])
    heads = ((0, 12), (512, 36))    # a global kind and a window kind
    assert paged_attn_cover(KEY_LOOP, (), 128, 16, pos, pos, real)[:2] == (4 * 2048, 4 * 2048)
    run = 2048 + 304                # 128 blocks and 19: the padding lanes none
    assert paged_attn_cover(DECODE_KERNEL, heads, 128, 16, pos, pos, real) == (
        run, 4 * 2048, 36 * (32 * 16 + 304), 12 * run + 36 * (32 * 16 + 304))
    first, blocks = paged_decode_span(np, pos, real, 512, 16, 128)
    assert first.tolist() == [96, 0, 0, 0] and blocks.tolist() == [32, 19, 0, 0]


# (program, table) -> the form on the chip, of every served configuration, at
# the block size, chunk length and served positions of its file under
# `benchmarks/configs/`: the decode program and a full prefill chunk, each
# over the narrowest table it can meet (one block; the chunk's own blocks)
# and the widest (the served positions, or the whole pool where that is less)
_KERNELS = {("decode", "narrow"): "decode_kernel", ("decode", "wide"): "decode_kernel",
            ("chunk", "narrow"): "one_shot", ("chunk", "wide"): "chunk_kernel"}
SERVED = {
    "gpt2-large": dict.fromkeys(_KERNELS, "one_shot"),  # heads of 64, 1,024 positions
    "smallthinker-21b-a3b": _KERNELS,
    "ouro-2.6b": _KERNELS,
    "ax-k1": _KERNELS,
    "jamba2-3b": _KERNELS,
    "laguna-xs2": _KERNELS,
}


@pytest.mark.parametrize("table", ["narrow", "wide"])
@pytest.mark.parametrize("program", ["decode", "chunk"])
@pytest.mark.parametrize("config", list(SERVED))
def test_no_served_program_takes_the_key_loop_on_the_chip(config, program, table, monkeypatch):
    """The four forms against the six served configurations: on the chip
    every program's form is the table's and the key loop is none's; off it the
    same shapes take the key loop exactly where the table is wider than a
    tile (the one form a CPU has for it)."""
    from benchmarks import harness
    from ray_tpu.models.gpt import CONFIGS
    from ray_tpu.ops import attention
    from ray_tpu.ops.paged_attention import KEY_LOOP, ONE_SHOT, paged_attn_tiling
    from ray_tpu.serve.engine import EngineOptions

    served = harness.load_json(harness.ROOT, f"benchmarks/configs/{config}.json")
    arch = harness.arch(served["arch"])
    model, overrides = arch.program(served, arch.dims(served, False))
    cfg = CONFIGS[model](**overrides)
    opts = EngineOptions(**served["runners"]["requests"]["engine_options"])
    tokens = 1 if program == "decode" else opts.prefill_chunk_tokens
    width = (-(-tokens // opts.block_size) if table == "narrow"
             else min(cfg.max_seq // opts.block_size, opts.num_blocks))
    tiled = paged_attn_tiling(width, opts.block_size)[1] > 1
    assert tiled is (table == "wide" and config != "gpt2-large")
    assert _form(cfg, tokens, width, opts.block_size) == (KEY_LOOP if tiled else ONE_SHOT)
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    assert _form(cfg, tokens, width, opts.block_size) == SERVED[config][program, table]
    assert SERVED[config][program, table] != KEY_LOOP
