"""Laguna's layer stack (window layers of their own head count beside full
layers over the same K/V heads, a rotary table a kind, a gate a head, a
leading dense layer inside a KV group, sigmoid-routed experts beside a shared
one) through the program's normal paths, on the CPU at a small size with
seeded random weights, each against the plain reference of
`benchmarks/arch/laguna.py`: `forward`; chunked paged prefill then paged decode
through the block manager's five tables (logits, not tokens), lanes of unequal
length in one decode bucket, every lane several windows deep, tables of one
tile and of many; a window group's blocks given back and taken by another
lane; every control of the benchmark's token check failing the same
comparison; the grouped experts against the dense sum where a step touches
more experts than it has lanes; the engine end to end with its new counters;
what refuses the model."""

import dataclasses

import numpy as np
import pytest

from benchmarks.arch import laguna as arch
from benchmarks.arch import laguna_reference as reference

BS, WINDOW = 8, 16
KINDS = ["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"]
PUBLISHED = {
    "num_hidden_layers": 5, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16,
    "num_attention_heads_per_layer": [4, 6, 6, 6, 4], "layer_types": KINDS,
    "mlp_layer_types": ["dense"] + ["sparse"] * 4, "intermediate_size": 96,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "num_experts": 12, "num_experts_per_tok": 3, "moe_routed_scaling_factor": 2.5,
    "moe_apply_router_weight_on_input": False, "sliding_window": WINDOW,
    "attention_bias": False, "tie_word_embeddings": False, "gating": True,
    "rms_norm_eps": 1e-6, "vocab_size": 300, "max_position_embeddings": 4096,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 8,
            "original_max_position_embeddings": 32, "beta_slow": 1, "beta_fast": 4,
            "attention_factor": 1.2079441541679836, "partial_rotary_factor": 0.5},
        "sliding_attention": {
            "rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1}},
    "deployment": {"served_positions": 256}, "program_model": "laguna-xs2",
}
# float32 program against the float32 reference: the largest difference of two
# logits over the largest logit in size. Both sum the same terms in float32 in
# another order; the checked positions read under 1e-6.
TOL = 2e-5
FORMS = {"one-shot": 1 << 20, "tiled": 16}      # keys a trip of the key loop
WRONG = {
    "window_one_block_wide": {"window": WINDOW + BS},
    "rotary_tables_swapped": {"rope_swapped": True},
    "no_gate": {"gate": False},
    "no_shared_expert": {"shared_expert": False},
    "top_k_minus_one": {"top_k": 2},
}


@pytest.fixture(scope="module")
def case():
    """(cfg, params, dims, tokens [2, 130], reference logits [2, 130, V])."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt

    m = arch.dims(PUBLISHED, False)
    name, overrides = arch.program(PUBLISHED, m)
    cfg = gpt.CONFIGS[name](**overrides, dtype=jnp.float32,
                            param_dtype=jnp.float32, remat=False)
    params = gpt.init_params(jax.random.PRNGKey(3), cfg)
    tokens = np.random.default_rng(0).integers(1, m["vocab_size"], (2, 130))
    logits = arch.make_logits(m)
    want = np.stack([logits(params, t) for t in tokens])
    assert np.abs(want).max() > 1.0
    return cfg, params, m, tokens, want


def _err(got, want):
    return float(np.abs(np.asarray(got) - want).max() / np.abs(want).max())


# ------------------------------------------------------------ what is declared
def test_two_attention_stacks_by_kind_and_five_groups_of_one_layer(case):
    from ray_tpu.models import gpt

    cfg, params, m, _t, _w = case
    assert (cfg.n_heads, cfg.n_heads_window, cfg.kv_heads) == (4, 6, 2)
    assert cfg.layer_heads == (4, 6, 6, 6, 4)
    # each kind at its own shape: no padding of the full layers to six heads
    assert params["w_q"].shape == (1, 64, 4, 16) and params["win_w_q"].shape == (3, 64, 6, 16)
    assert params["w_o"].shape == (1, 4, 16, 64) and params["win_w_o"].shape == (3, 6, 16, 64)
    assert params["w_head_gate"].shape == (1, 64, 4)
    assert params["win_w_head_gate"].shape == (3, 64, 6)
    assert params["w_kv"].shape[1:] == params["win_w_kv"].shape[1:] == (64, 2, 2, 16)
    assert params["lead_w_q"].shape == (1, 64, 4, 16)       # the dense layer is a full one
    assert params["lead_w_in"].shape == (1, 64, 96) and params["moe_w_in"].shape == (4, 12, 64, 32)
    assert not [k for k in params if k.endswith(("_b", "b_o", "b_in", "b_out", "b_qkv"))]
    n = sum(int(np.prod(a.shape)) for a in params.values())
    assert n == cfg.n_params == arch.tree_params(m)
    lay = gpt.kv_layout(cfg)
    # 2 full + 3 window layers: gcd 1, so five groups of one layer; the
    # leading dense layer (layer 0) is dealt into a global group like layer 4
    assert lay.per_group == lay.depth == 1 and lay.windows == (0, 0, WINDOW, WINDOW, WINDOW)
    assert lay.group_of == (0, 2, 3, 4, 1) and lay.slot_of == (0,) * 5
    pool = gpt.init_paged_cache(cfg, 10, BS)
    assert pool["k"].shape == pool["v"].shape == (1, 10, BS, 2 * 16)
    assert lay.block_bytes(BS, 4) == arch.kv_block_bytes(m, BS) * 2     # float32 here


def test_the_published_shapes_at_full_size():
    import jax

    from ray_tpu.models import gpt

    full = gpt.CONFIGS["laguna-xs2"]()
    assert (full.n_layers, full.dense_layers, full.moe_experts, full.moe_top_k) == (40, 1, 256, 8)
    assert full.layer_heads[:5] == (48, 64, 64, 64, 48) and sum(full.sliding_window_layout) == 30
    assert 33.3e9 < full.n_params < 33.5e9          # the published 33.4 B
    cut = gpt.CONFIGS["laguna-xs2"](n_layers=5)
    tree = jax.eval_shape(lambda k: gpt.init_params(k, cut), jax.random.PRNGKey(0))
    assert tree["w_q"].shape == (1, 2048, 48, 128) and tree["win_w_q"].shape == (3, 2048, 64, 128)
    assert tree["moe_w_in"].shape == (4, 256, 2048, 512)
    assert sum(int(np.prod(a.shape)) for a in tree.values()) == cut.n_params == 3_869_857_792
    lay = gpt.kv_layout(cut)
    assert len(lay.windows) == 5 and lay.block_bytes(64, 2) == 256 * 1024


@pytest.mark.parametrize("kind", ["full_attention", "sliding_attention"])
def test_each_kind_rotates_by_its_own_table(case, kind):
    """The program's tables (YaRN on the full layers: half the head, scaled
    frequencies, cos and sin times the attention factor) are the reference's."""
    from ray_tpu.models import gpt

    cfg, _p, m, _t, _w = case
    own = cfg if kind == "full_attention" else gpt._window_cfg(cfg)
    cos, sin = gpt._rope_tables(own)
    want_cos, want_sin = reference.rotary_table(m["rope"][kind], m["d_head"], cfg.max_seq)
    assert cos.shape == want_cos.shape == (256, 4 if kind == "full_attention" else 8)
    assert float(np.abs(cos - want_cos).max()) < 2e-5 and float(np.abs(sin - want_sin).max()) < 2e-5
    amp = m["rope"][kind].get("attention_factor", 1.0)
    assert abs(float(cos[0, 0]) - amp) < 1e-6


# ------------------------------------------------------------------- forward
def test_forward_matches_the_reference(case):
    import jax.numpy as jnp

    from ray_tpu.models.gpt import forward

    cfg, params, _m, tokens, want = case
    assert _err(forward(params, jnp.asarray(tokens), cfg), want) < TOL


@pytest.mark.parametrize("wrong", list(WRONG))
def test_a_wrong_reference_fails_the_tolerance_a_hundredfold(case, wrong):
    _cfg, params, m, tokens, want = case
    got = arch.make_logits({**m, **WRONG[wrong]})(params, tokens[0])
    assert _err(got[40:], want[0, 40:]) > 100 * TOL


# ------------------------------------------------- the paged programs, logits
class _Paged:
    """The two paged programs over the five tables a `KVBlockManager` gives,
    sliding as the scheduler does, the pool donated from call to call. Before
    every call the null block's rows, which every released entry of a window
    group's table points at, are set to a large value: the masks must keep
    them from every output."""

    CHUNK, WIDTH = 32, 32

    def __init__(self, cfg, params, jits, blocks=80):
        from ray_tpu.models import gpt
        from ray_tpu.serve.engine import KVBlockManager

        self.cfg, self.params = cfg, params
        self.prefill, self.decode, _ = jits
        self.windows = gpt.kv_layout(cfg).windows
        self.mgr = KVBlockManager(blocks, BS, group_windows=self.windows)
        self.kv = gpt.init_paged_cache(cfg, blocks, BS)

    def _poisoned(self):
        return {n: a.at[:, 0].set(1e4) for n, a in self.kv.items()}

    def tables(self, sid):
        t = np.zeros((len(self.windows), self.WIDTH), np.int32)
        for g, tab in enumerate(self.mgr.block_tables(sid)):
            t[g, : len(tab)] = tab
        return t

    def prompt(self, sid, prompt, chunk=24):
        """Chunked prefill: [(last position, logits)] a chunk."""
        import jax.numpy as jnp

        prompt = [int(t) for t in prompt]
        self.mgr.allocate_cached(sid, prompt, len(prompt) + 1)
        out, start = [], 0
        while start < len(prompt):
            n = min(chunk, len(prompt) - start)
            self.mgr.slide(sid, start, start + n)
            self.mgr.check_invariants()
            padded = np.zeros((1, self.CHUNK), np.int32)
            padded[0, :n] = prompt[start:start + n]
            logits, self.kv = self.prefill(
                self.params, jnp.asarray(padded), jnp.int32(n), jnp.int32(start),
                jnp.asarray(self.tables(sid)), self._poisoned(), self.cfg)
            start += n
            self.mgr.register_computed(sid, prompt, start)
            out.append((start - 1, np.asarray(logits)))
        return out

    def step(self, lanes, bucket):
        """One decode step of `lanes` [(sid, token, position)] in a program of
        `bucket` lanes: the rest are padding. -> (logits [lanes, V], load)."""
        import jax.numpy as jnp

        tok, pos = np.zeros((bucket,), np.int32), np.zeros((bucket,), np.int32)
        tabs = np.zeros((bucket, len(self.windows), self.WIDTH), np.int32)
        for i, (sid, t, p) in enumerate(lanes):
            self.mgr.grow(sid, p + 1, first_query=p)
            tok[i], pos[i], tabs[i] = t, p, self.tables(sid)
        self.mgr.check_invariants()
        (logits, load), self.kv = self.decode(
            self.params, jnp.asarray(tok), jnp.asarray(pos), jnp.asarray(tabs),
            self._poisoned(), self.cfg)
        return np.asarray(logits)[: len(lanes)], np.asarray(load)


@pytest.mark.parametrize("form", list(FORMS))
def test_chunked_prefill_then_decode_of_unequal_lanes_matches_the_reference(
        case, form, tile_keys):
    """Two prompts of 100 and 61 tokens (6 and 3 windows deep), prefilled in
    chunks of 24 with a padded last chunk, then decoding side by side in ONE
    program of four lanes (two of them padding), each against the reference's
    full forward pass at its own position."""
    cfg, params, _m, tokens, want = case
    with tile_keys(FORMS[form]) as jits:
        run = _Paged(cfg, params, jits)
        lens = {"a": 100, "b": 61}
        for i, sid in enumerate("ab"):
            for pos, logits in run.prompt(sid, tokens[i, :lens[sid]]):
                assert _err(logits, want[i, pos]) < TOL
        for k in range(24):
            lanes = [(sid, tokens[i, lens[sid] + k], lens[sid] + k)
                     for i, sid in enumerate("ab")]
            logits, load = run.step(lanes, bucket=4)
            for i, (_sid, _t, pos) in enumerate(lanes):
                assert _err(logits[i], want[i, pos]) < TOL
            # two lanes x top-3 of 12 experts, mean over the four expert layers
            assert 3 <= load[0] <= 6 and 1 / 6 - 1e-6 <= load[1] <= 1 / 3 + 1e-6
    # a window group holds the window and at most one block more, a global
    # group every block, the leading dense layer's group among them
    held = run.mgr.held_blocks("a")
    assert held[:2] == [-(-124 // BS)] * 2 and max(held[2:]) <= WINDOW // BS + 1
    assert run.mgr.window_released > 0


def test_a_window_group_gives_blocks_back_that_another_lane_takes(case, tile_keys):
    """A pool too small for both sequences' whole contexts in every group:
    lane b is admitted into blocks that lane a's window groups released while
    a lived, and a's logits do not move."""
    cfg, params, _m, tokens, want = case
    with tile_keys(FORMS["one-shot"]) as jits:
        # a at 120 tokens: 2 x 15 global blocks + 3 x 3 window blocks = 39, b
        # at 44: 2 x 6 + 3 x 3 = 21, of 65; with every layer keeping everything
        # a alone would be 75
        run = _Paged(cfg, params, jits, blocks=66)
        for pos, logits in run.prompt("a", tokens[0, :96]):
            assert _err(logits, want[0, pos]) < TOL
        given_back = run.mgr.window_released
        assert given_back >= 3 * (96 // BS - WINDOW // BS - 1)
        # what a's window groups gave back rests on the cached list (its rows
        # are still that prefix's) until someone needs a block
        released = set(run.mgr._cached)
        assert len(released) > len(run.mgr._free)
        for pos, logits in run.prompt("b", tokens[1, :20]):
            assert _err(logits, want[1, pos]) < TOL
        taken = {b for t in run.mgr.block_tables("b") for b in t if b} & released
        assert taken and run.mgr.window_blocks_held <= 2 * 3 * (WINDOW // BS + 1)
        for k in range(24):
            lanes = [("a", tokens[0, 96 + k], 96 + k), ("b", tokens[1, 20 + k], 20 + k)]
            logits, _ = run.step(lanes, bucket=2)
            assert _err(logits[0], want[0, 96 + k]) < TOL
            assert _err(logits[1], want[1, 20 + k]) < TOL
        run.mgr.free("a")
        run.mgr.free("b")
        run.mgr.check_invariants()
        assert run.mgr.blocks_held == run.mgr.window_blocks_held == 0


@pytest.mark.parametrize("form", list(FORMS))
def test_verify_step_equals_sequential_decode(case, form, tile_keys):
    """Four tokens a lane in one forward over five tables, beside a padding
    lane: both kinds' attention with several queries a lane."""
    import jax.numpy as jnp

    from ray_tpu.models import gpt

    cfg, params, _m, tokens, want = case
    G, n0, k1 = len(gpt.kv_layout(cfg).windows), 60, 4
    table = np.zeros((G, 16), np.int32)
    for g in range(G):      # every group keeps everything: the masks decide
        table[g, :10] = 1 + 10 * g + np.arange(10)
    kv = gpt.init_paged_cache(cfg, 51, BS)
    padded = np.zeros((1, 64), np.int32)
    padded[0, :n0] = tokens[0, :n0]
    toks = np.zeros((2, k1), np.int32)
    toks[0] = tokens[0, n0:n0 + k1]
    with tile_keys(FORMS[form]) as (prefill, _decode, verify):
        _, kv = prefill(params, jnp.asarray(padded), jnp.int32(n0),
                        jnp.int32(0), jnp.asarray(table), kv, cfg)
        logits, _ = verify(
            params, jnp.asarray(toks), jnp.asarray([n0, 0]), jnp.asarray([k1, 0]),
            jnp.asarray(np.stack([table, np.zeros_like(table)])), kv, cfg)
    assert _err(logits[0], want[0, n0:n0 + k1]) < TOL


# ------------------------------------------------------------------- experts
def test_a_step_that_touches_more_experts_than_it_has_lanes_grouped_against_dense(case):
    """Two lanes choose 3 of 12 experts each: up to six experts, each a tile
    of its own for one row. The grouped form (what every served step takes)
    against the dense sum over all experts, on one layer's weights."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import moe

    _cfg, params, m, _t, _w = case
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64), jnp.float32)
    logits = x @ params["moe_router"][1]
    idx, w = moe.dropless_route(logits, 3, "sigmoid", 2.5)
    assert len(set(np.asarray(idx).ravel().tolist())) > 2       # more experts than lanes
    assert np.allclose(np.asarray(w).sum(-1), 2.5, atol=1e-5)
    combine = moe.dropless_combine(idx, w, 12)
    stacks = tuple(params[k] for k in ("moe_w_gate", "moe_w_in", "moe_w_out"))
    dense = moe.dropless_experts(x, combine, *(a[1] for a in stacks), "swiglu")
    grouped = moe.dropless_experts(x, combine, *stacks, "swiglu", layer=1, grouped_k=3)
    assert float(jnp.abs(grouped - dense).max()) < 1e-5 * float(jnp.abs(dense).max() + 1)
    rows, tile_expert, _first, tiles = moe.dropless_groups(combine, 3, moe.GROUP_ROWS)
    touched = int((np.asarray(combine) > 0).any(0).sum())
    assert int(tiles) == touched and int((np.asarray(rows) >= 0).sum()) == 6


# -------------------------------------------------------------------- engine
def _engine(case, **opts):
    from ray_tpu.serve.engine import EngineOptions, InferenceEngine

    options = EngineOptions(**{**dict(num_blocks=120, block_size=BS, max_num_seqs=4,
                                      max_step_tokens=64, prefill_chunk_tokens=24,
                                      host_kv_bytes=0), **opts})
    return InferenceEngine(case[0], params=case[1], options=options)


@pytest.mark.parametrize("form", list(FORMS))
def test_engine_serves_both_kinds_and_keeps_its_books(case, form, tile_keys, monkeypatch):
    from ray_tpu.serve.engine import engine as engine_module

    cfg, params, m, tokens, _want = case
    with tile_keys(FORMS[form]):
        monkeypatch.setattr(engine_module, "_JITS", None)
        eng = _engine(case)
        prompt = [int(t) for t in tokens[0, :100]]
        rid_long = eng.submit(prompt, 12)
        rid_short = eng.submit([int(t) for t in tokens[1, 5:25]], 6)
        while eng.scheduler.has_work():
            eng.step()
            eng.block_manager.check_invariants()
        got = list(eng.stream(rid_long))
        assert len(got) == 12 and len(list(eng.stream(rid_short))) == 6
    # each token is the reference's own choice at its position (float32)
    want = arch.make_logits(m)(params, np.asarray(prompt + got[:-1]))[99:]
    assert (want.argmax(-1) == np.asarray(got)).all()
    s = eng.stats()
    assert s["window_blocks_released"] > 0 and s["total_preemptions"] == 0
    # the pool over time: the window groups' share is under the layers' 3 of 5
    assert 0 < s["kv_window_block_ns"] < 0.6 * s["kv_block_held_ns"]
    # heads x keys: window layers have 18 of the 26 heads and see fewer keys
    # where the tables are tiled; over one-shot tables every layer covers all
    share = s["attn_head_keys_window"] / s["attn_head_keys"]
    assert share == pytest.approx(18 / 26) if form == "one-shot" else 0 < share < 18 / 26
    assert eng.block_manager.stats().used_blocks == 0


def test_engine_under_kv_pressure_preempts_resumes_and_stays_exact(case):
    cfg, params, m, tokens, _want = case
    prompts = [[int(t) for t in tokens[i, :70]] for i in range(2)]
    # both fit at admission (2 x 9 global + 3 x 3 window blocks each) and
    # outgrow 55 blocks while they decode (2 x 13 + 3 x 3 each at 100 tokens)
    outs = []
    for eng in (_engine(case), _engine(case, num_blocks=56)):
        rids = [eng.submit(p, 30) for p in prompts]
        for _ in range(400):
            if not eng.scheduler.has_work():
                break
            eng.step()
            eng.block_manager.check_invariants()
        outs.append([list(eng.stream(r)) for r in rids])
    assert outs[0] == outs[1] and len(outs[0][0]) == 30
    assert eng.stats()["total_preemptions"] > 0
    assert eng.block_manager.stats().used_blocks == 0


# ------------------------------------------------------------------ counters
def test_head_keys_are_counted_by_layer_kind_under_each_window(case, monkeypatch):
    """A chunk of 8 queries at positions 120-127 over a table of 16 blocks:
    with tiles of 16 keys a window layer (window 16) covers tiles 6-7, a full
    layer tiles 0-7."""
    from ray_tpu.models import gpt
    from ray_tpu.ops import paged_attention

    cfg = case[0]
    heads = gpt.attn_heads_by_window(cfg)
    monkeypatch.setattr(paged_attention, "_ATTN_TILE_KEYS", 16)
    _, _, window, every = paged_attention.paged_attn_cover(
        paged_attention.KEY_LOOP, heads, 16, BS, np.asarray([120]), np.asarray([127]), True)
    assert window == 3 * 6 * 2 * 16 and every == window + 2 * 4 * 8 * 16
    # one tile: every layer covers the table whole
    monkeypatch.setattr(paged_attention, "_ATTN_TILE_KEYS", 1 << 20)
    real = np.asarray([True, True])
    _, _, window, every = paged_attention.paged_attn_cover(
        paged_attention.ONE_SHOT, heads, 16, BS, np.asarray([5, 9]), np.asarray([5, 9]), real)
    assert (window, every) == (18 * 2 * 128, 26 * 2 * 128)


# ------------------------------------------------------------------ refusals
def _refusals():
    import jax
    import optax

    from ray_tpu.models import gpt

    return {
        "dense_prefill": lambda c, p: gpt.prefill(p, np.zeros((1, 4), np.int32), c, None),
        "dense_decode": lambda c, p: gpt.decode_step(p, np.zeros((1,), np.int32), {"len": 0}, c),
        "stage_forward": lambda c, p: gpt.stage_forward(p, np.zeros((1, 4), np.int32), c,
                                                        first=True, last=True),
        "make_train_step": lambda c, p: gpt.make_train_step(c, optax.sgd(0.1)),
        "loss_fn": lambda c, p: gpt.loss_fn(p, {"tokens": np.zeros((1, 5), np.int32)}, c),
        "param_shardings": lambda c, p: gpt.param_logical_dims(c),
        "init_gpt2": lambda c, p: gpt.init_params(
            jax.random.PRNGKey(0), dataclasses.replace(c, init="gpt2")),
    }


@pytest.mark.parametrize("what", ["dense_prefill", "dense_decode", "stage_forward",
                                  "make_train_step", "loss_fn", "param_shardings",
                                  "init_gpt2"])
def test_programs_that_cannot_take_stacks_by_kind_refuse_them_by_name(case, what):
    with pytest.raises(NotImplementedError, match="n_heads_window|by kind"):
        _refusals()[what](case[0], case[1])


@pytest.mark.parametrize("change, match", [
    ({"n_heads_window": 5}, "n_heads_window"),                  # not a multiple of the K/V heads
    ({"sliding_window_layout": (0, 0, 0, 0, 0)}, "n_heads_window"),     # no window layer
    ({"sliding_window_layout": (1, 1, 1, 1, 0)}, "n_heads_window"),     # a window dense layer
    ({"n_heads_window": 0}, "dense_layers|rotary table"),
    ({"rope_layout": (1, 1, 1, 1, 1)}, "n_heads_window"),
])
def test_config_refuses_what_the_two_stacks_are_not_written_for(case, change, match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(case[0], **change)


@pytest.mark.parametrize("change, match", [
    ({"program_model": "laguna-of-tomorrow"}, "no model"),
    ({"gating": "per-element"}, "laguna: written for"),
    ({"num_attention_heads_per_layer": [4, 6, 6, 4, 4]}, "laguna: written for"),
    ({"mlp_layer_types": ["sparse"] * 5}, "laguna: written for"),
    ({"shared_expert_intermediate_size": 48}, "whole number"),
])
def test_architecture_module_refuses_what_is_not_the_model(change, match):
    published = {**PUBLISHED, **change}
    with pytest.raises(SystemExit, match=match):
        arch.program(published, arch.dims(published, False))
