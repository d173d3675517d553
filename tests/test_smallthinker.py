"""SmallThinker through the program's normal paths, on the CPU at small
sizes with seeded random weights, each against the plain reference of
`benchmarks/arch/smallthinker.py`: `forward`; chunked paged prefill then
paged decode through the block manager's tables for a sequence that crosses
the window by several blocks (logits, not tokens), with tables of one tile
and of sixteen (`_paged_layers`' key loop); verify against sequential decode; four wrong references that must fail; routing that drops
nothing; grouped-query heads against multi-head; the engine end to end."""

import copy

import numpy as np
import pytest

from benchmarks.arch import smallthinker as arch

BS, WINDOW = 8, 32
PUBLISHED = {
    "num_hidden_layers": 8, "hidden_size": 64, "num_attention_heads": 8,
    "num_key_value_heads": 2, "head_dim": 16, "moe_ffn_hidden_size": 32,
    "moe_num_primary_experts": 8, "moe_num_active_primary_experts": 3,
    "max_position_embeddings": 256, "vocab_size": 300, "rope_theta": 1500000,
    "rms_norm_eps": 1e-6, "rope_layout": [0, 1, 1, 1] * 13,
    "sliding_window_layout": [0, 1, 1, 1] * 13, "sliding_window_size": WINDOW,
    "program_model": "smallthinker-21b-a3b",
}
TOL = 2e-5      # float32 program against float32 reference, logits near 1
# Keys a trip of `_paged_layers`' key loop covers: the tables below (32
# blocks of 8) are one tile (one shot, no loop) or sixteen, and the window
# of 32 then starts inside a tile at most positions.
FORMS = {"one-shot": 1 << 20, "tiled": 16}


@pytest.fixture(scope="module")
def case():
    """(cfg, params, dims, tokens [130], reference logits [130, V])."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt

    m = arch.dims(PUBLISHED, False)
    name, overrides = arch.program(PUBLISHED, m)
    cfg = gpt.CONFIGS[name](**overrides, dtype=jnp.float32,
                            param_dtype=jnp.float32, remat=False)
    params = gpt.init_params(jax.random.PRNGKey(3), cfg)
    tokens = np.random.default_rng(0).integers(1, m["vocab_size"], 130)
    want = arch.make_logits(m)(params, tokens)
    assert np.abs(want).max() > 0.5
    return cfg, params, m, tokens, want


def _err(got, want):
    return float(np.abs(np.asarray(got) - want).max())


def test_layout_deals_layers_into_groups_of_one_kind(case):
    from ray_tpu.models.gpt import CONFIGS, init_paged_cache, kv_layout

    lay = kv_layout(case[0])
    assert lay.per_group == 2 and lay.windows == (0, WINDOW, WINDOW, WINDOW)
    assert sorted(zip(lay.group_of, lay.slot_of)) == [
        (g, s) for g in range(4) for s in range(2)]
    assert all((lay.windows[g] > 0) == (l % 4 != 0) for l, g in enumerate(lay.group_of))
    assert init_paged_cache(case[0], 10, BS)["k"].shape == (2, 10, BS, 2 * 16)
    full = kv_layout(CONFIGS["smallthinker-21b-a3b"]())
    assert full.per_group == 13 and full.windows == (0, 4096, 4096, 4096)
    one = kv_layout(CONFIGS["gpt2-large"]())
    assert one.per_group == 36 and one.windows == (0,)


def test_forward_matches_the_reference(case):
    import jax.numpy as jnp

    from ray_tpu.models.gpt import forward

    cfg, params, _m, tokens, want = case
    got = forward(params, jnp.asarray(tokens)[None], cfg)[0]
    assert _err(got, want) < TOL


@pytest.fixture(scope="module", params=list(FORMS))
def through(case, request, tile_keys):
    with tile_keys(FORMS[request.param]) as jits:
        return _through_the_manager(case, jits)


def _through_the_manager(case, jits, chunk=24, n_prompt=100):
    """Chunked `prefill_paged` then `decode_step_paged` over the tables the
    block manager gives, sliding as the scheduler does; yields (position,
    logits) of every prefill chunk's last position and every decode step.
    Before every call the null block's rows, which every released entry of
    a window group's table points at, are set to a large value: the mask
    must keep them from every output."""
    import jax.numpy as jnp

    from ray_tpu.models import gpt
    from ray_tpu.serve.engine import KVBlockManager

    prefill, decode, _verify = jits
    cfg, params, _m, tokens, _want = case
    lay = gpt.kv_layout(cfg)
    mgr = KVBlockManager(40, BS, group_windows=lay.windows)
    kv = gpt.init_paged_cache(cfg, 40, BS)
    prompt = [int(t) for t in tokens[:n_prompt]]
    mgr.allocate_cached("s", prompt, n_prompt + 1)

    def tables(width=32):
        t = np.zeros((len(lay.windows), width), np.int32)
        for g, tab in enumerate(mgr.block_tables("s")):
            t[g, : len(tab)] = tab
        return jnp.asarray(t)

    def poisoned(kv):
        return {n: a.at[:, 0].set(1e4) for n, a in kv.items()}

    out, start = [], 0
    while start < n_prompt:
        n = min(chunk, n_prompt - start)
        mgr.slide("s", start, start + n)
        mgr.check_invariants()
        padded = np.zeros((1, 32), np.int32)
        padded[0, :n] = prompt[start:start + n]
        logits, kv = prefill(
            params, jnp.asarray(padded), jnp.int32(n), jnp.int32(start),
            tables(), poisoned(kv), cfg)
        start += n
        mgr.register_computed("s", prompt, start)
        out.append((start - 1, np.asarray(logits)))
    for pos in range(n_prompt, len(tokens)):
        seen = [int(t) for t in tokens[:pos]]
        mgr.grow("s", pos + 1, token_ids=seen, num_computed=pos, first_query=pos)
        mgr.check_invariants()
        (logits, _load), kv = decode(
            params, jnp.asarray(tokens[pos:pos + 1]), jnp.asarray([pos]),
            tables()[None], poisoned(kv), cfg)
        out.append((pos, np.asarray(logits)[0]))
    return mgr, out


def test_paged_prefill_and_decode_across_the_window_match_the_reference(case, through):
    mgr, out = through
    want = case[4]
    assert len(out) == 5 + 30 and out[-1][0] == 129 > 4 * WINDOW
    assert max(_err(lg, want[pos]) for pos, lg in out) < TOL
    # the window groups really gave blocks back while the sequence lived, and
    # hold the window and at most one block more; the global group holds all
    held = mgr.held_blocks("s")
    assert held[0] == -(-130 // BS) and max(held[1:]) <= WINDOW // BS + 1
    assert mgr.window_released == 3 * (held[0] - held[1])


@pytest.mark.parametrize("form", list(FORMS))
def test_verify_step_equals_sequential_decode(case, form, tile_keys):
    """Four tokens a lane in one forward, beside a padding lane. In the
    tiled form the V rows of the window groups' blocks that lie wholly
    below the tile of the window's first key are NaN: a window layer's
    loop starts at that tile (0 x NaN would reach the output of one that
    started at tile 0), and the padding lane at position 0 does not pull
    the start down."""
    import jax.numpy as jnp

    from ray_tpu.models import gpt

    cfg, params, _m, tokens, want = case
    G, n0, k1 = len(gpt.kv_layout(cfg).windows), 60, 4
    table = np.zeros((G, 16), np.int32)
    for g in range(G):      # every group keeps everything: the masks decide
        table[g, :10] = 1 + 10 * g + np.arange(10)
    kv = gpt.init_paged_cache(cfg, 41, BS)
    padded = np.zeros((1, 64), np.int32)
    padded[0, :n0] = tokens[:n0]
    toks = np.zeros((2, k1), np.int32)
    toks[0] = tokens[n0:n0 + k1]
    with tile_keys(FORMS[form]) as (prefill, _decode, verify):
        _, kv = prefill(params, jnp.asarray(padded), jnp.int32(n0),
                        jnp.int32(0), jnp.asarray(table), kv, cfg)
        if form == "tiled":
            # first key a query at 60 sees on a window layer: 29, in tile 1
            assert (n0 - WINDOW + 1) // FORMS[form] == 1
            kv["v"] = kv["v"].at[:, jnp.asarray(table[1:, :2].ravel())].set(jnp.nan)
        logits, _ = verify(
            params, jnp.asarray(toks), jnp.asarray([n0, 0]), jnp.asarray([k1, 0]),
            jnp.asarray(np.stack([table, np.zeros_like(table)])), kv, cfg)
    assert _err(logits[0], want[n0:n0 + k1]) < TOL


@pytest.mark.parametrize("wrong", ["top_k_minus_one", "window_off",
                                   "window_off_by_one_block", "rope_on_nope_layers"])
def test_a_wrong_reference_fails_the_tolerance_threefold(case, through, wrong):
    m = copy.deepcopy(case[2])
    if wrong == "top_k_minus_one":
        m["top_k"] -= 1
    elif wrong == "window_off":
        m["window_layout"] = [0] * m["n_layers"]
    elif wrong == "window_off_by_one_block":
        m["window"] += BS
    else:
        m["rope_layout"] = [1] * m["n_layers"]
    off = arch.make_logits(m)(case[1], case[3])
    assert _err(off, case[4]) > 3 * TOL
    # and what the program computes is on the right side of it
    assert _err(off[-1], case[4][-1]) > 3 * _err(through[1][-1][1], case[4][-1])


def test_routing_drops_no_token_when_all_choose_the_same_experts():
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import moe

    N, D, F, X, k = 24, 16, 8, 8, 3
    keys = jax.random.split(jax.random.PRNGKey(1), 5)
    row = jax.random.normal(keys[0], (1, D))
    x = jnp.tile(row, (N, 1))                          # every token alike
    router = jax.random.normal(keys[1], (D, X))
    w_gate, w_in = (jax.random.normal(kk, (X, D, F)) for kk in keys[2:4])
    w_out = jax.random.normal(keys[4], (X, F, D))
    idx, w = moe.dropless_route(x @ router, k)
    assert (np.asarray(idx) == np.asarray(idx)[0]).all()    # one choice for all
    combine = moe.dropless_combine(idx, w, X)
    assert np.allclose(np.asarray(combine).sum(-1), 1.0, atol=1e-6)
    assert (np.asarray(combine > 0).sum(-1) == k).all()
    want = sum(float(w[0, j]) * (jax.nn.relu(row @ w_gate[e]) * (row @ w_in[e])) @ w_out[e]
               for j, e in enumerate(np.asarray(idx)[0]))
    for grouped_k in (0, k):                           # dense, and the served form
        y = moe.dropless_experts(x, combine, w_gate, w_in, w_out, "reglu",
                                 grouped_k=grouped_k)
        assert np.abs(np.asarray(y) - np.asarray(want)).max() < 1e-4, grouped_k
    touched, share = moe.dropless_load(combine)
    assert float(touched) == k and abs(float(share) - 1 / k) < 1e-6
    # the capacity path of training would have dropped most of these tokens
    cap = moe.MoEConfig(num_experts=X, top_k=2, d_model=D, d_ff=F).capacity(N)
    assert cap < N


def test_grouped_query_heads_repeated_give_the_multi_head_logits():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt

    base = dict(vocab_size=128, n_layers=2, d_model=48, n_heads=6, d_head=8,
                d_mlp=64, max_seq=64, attn_impl="ref", remat=False, pos="rotary",
                rotary_dim=8, norm="rmsnorm", activation="swiglu",
                dtype=jnp.float32)
    gq, mh = gpt.GPTConfig(**base, n_kv_heads=2), gpt.GPTConfig(**base)
    p = gpt.init_params(jax.random.PRNGKey(0), gq)
    p = {k: v * 6.0 if k in ("w_q", "w_kv") else v for k, v in p.items()}
    kv = jnp.repeat(p["w_kv"], 3, axis=3)              # [L, E, 2, 6, Dh]
    q = {k: v for k, v in p.items() if k not in ("w_q", "w_kv")}
    q["w_qkv"] = jnp.concatenate([p["w_q"][:, :, None], kv], axis=2)
    q["b_qkv"] = jnp.zeros((2, 3, 6, 8))
    tokens = jnp.asarray(np.random.default_rng(2).integers(0, 128, (2, 40)))
    assert _err(gpt.forward(p, tokens, gq), np.asarray(gpt.forward(q, tokens, mh))) < 1e-5
    # and through the paged programs: the pool row is the K/V heads' alone
    table = jnp.asarray([[1, 2, 3, 0], [4, 5, 6, 0]])
    outs = []
    for cfg, params in ((gq, p), (mh, q)):
        pool = gpt.init_paged_cache(cfg, 8, 16)
        assert pool["k"].shape[-1] == cfg.kv_heads * 8
        for b in range(2):
            _, pool = gpt.prefill_paged(params, tokens[b:b + 1, :39].at[:, 39:].set(0),
                                        jnp.int32(39), jnp.int32(0), table[b], pool, cfg)
        logits, _ = gpt.decode_step_paged(params, tokens[:, 39], jnp.asarray([39, 39]),
                                          table, pool, cfg)
        outs.append(np.asarray(logits))
    assert _err(outs[0], outs[1]) < 1e-5


def test_paths_that_were_not_generalised_refuse_loudly(case):
    import jax.numpy as jnp

    from ray_tpu.models import gpt

    cfg, params = case[0], case[1]
    with pytest.raises(NotImplementedError, match="grouped-query"):
        gpt.prefill(params, jnp.zeros((1, 4), jnp.int32), cfg, None)
    with pytest.raises(NotImplementedError, match="per-layer kinds"):
        gpt.decode_step(params, jnp.zeros((1,), jnp.int32), None, cfg)
    with pytest.raises(NotImplementedError):
        gpt.stage_forward(params, jnp.zeros((1, 4), jnp.int32), cfg, first=True, last=True)
    with pytest.raises(ValueError, match="entries"):
        gpt.GPTConfig(n_layers=4, pos="rotary", rope_layout=[0, 1])
    assert gpt.CONFIGS["smallthinker-21b-a3b"](
        n_layers=4, rope_layout=[0, 1, 1, 1]).rope_layout == (0, 1, 1, 1)


# ------------------------------------------------------------------ engine
def _engine(case, **opts):
    from ray_tpu.serve.engine import EngineOptions, InferenceEngine

    options = EngineOptions(**{**dict(num_blocks=80, block_size=BS, max_num_seqs=4,
                                      max_step_tokens=64, prefill_chunk_tokens=24,
                                      host_kv_bytes=0), **opts})
    return InferenceEngine(case[0], params=case[1], options=options)


@pytest.mark.parametrize("form", list(FORMS))
def test_engine_serves_across_the_window_and_counts_what_it_did(case, form, tile_keys, monkeypatch):
    from ray_tpu.serve.engine import engine as engine_module

    cfg, params, m, tokens, _want = case
    with tile_keys(FORMS[form]):
        # the engine's own programs, made and traced anew under this tile
        monkeypatch.setattr(engine_module, "_JITS", None)
        _serves_across_the_window(case, form)


def _serves_across_the_window(case, form):
    cfg, params, m, tokens, _want = case
    eng = _engine(case)
    prompt = [int(t) for t in tokens[:100]]
    rid_long = eng.submit(prompt, 12)
    rid_short = eng.submit([int(t) for t in tokens[5:25]], 6)
    records = []
    while eng.scheduler.has_work():
        eng.step()
        eng.block_manager.check_invariants()
        if eng._step_moe is not None:
            records.append(eng._step_moe)
    got = list(eng.stream(rid_long))
    assert len(got) == 12 and len(list(eng.stream(rid_short))) == 6
    # each token is the reference's own choice at its position (float32)
    want = arch.make_logits(m)(params, np.asarray(prompt + got[:-1]))[99:]
    assert (want.argmax(-1) == np.asarray(got)).all()
    stats = eng.stats()
    assert stats["window_blocks_released"] > 0
    # tables of one tile are computed over whole; of several, as far as the
    # longest lane of each step reaches
    run, padded = stats["attn_keys_run"], stats["attn_keys_padded"]
    assert 0 < run == padded if form == "one-shot" else 0 < run < padded, (run, padded)
    touched, share = np.asarray(records).T
    assert (touched >= 3).all() and (touched <= 6).all()
    assert (share >= 1 / 6 - 1e-6).all() and (share <= 1 / 3 + 1e-6).all()
    assert eng.block_manager.stats().used_blocks == 0


def test_engine_under_kv_pressure_preempts_resumes_and_stays_exact(case):
    cfg, params, m, tokens, _want = case
    free_run = _engine(case)
    # two lanes fit at admission (3 + 3 x 3 blocks each) and outgrow the
    # pool while they decode (8 + 3 x 5 each at 60 tokens)
    tight = _engine(case, num_blocks=26)
    prompts = [[int(t) for t in tokens[:20]], [int(t) for t in tokens[30:50]]]
    outs = []
    for eng in (free_run, tight):
        rids = [eng.submit(p, 40) for p in prompts]
        while eng.scheduler.has_work():
            eng.step()
            eng.block_manager.check_invariants()
        outs.append([list(eng.stream(r)) for r in rids])
    assert outs[0] == outs[1]
    assert tight.stats()["total_preemptions"] > 0
    assert tight.block_manager.stats().used_blocks == 0


def test_engine_refuses_what_it_cannot_do_for_layers_of_two_kinds(case):
    with pytest.raises(ValueError, match="host"):
        _engine(case, host_kv_bytes=1 << 20)
    for role in ("prefill", "decode"):
        with pytest.raises(ValueError, match="mixed"):
            _engine(case, role=role)
    eng = _engine(case)
    with pytest.raises(NotImplementedError, match="export"):
        eng.export_prompt_kv(list(range(1, 40)))
    with pytest.raises(NotImplementedError, match="import"):
        eng.import_blocks({"sig": "x"})
    with pytest.raises(NotImplementedError):
        eng.block_manager.fork("a", "b")
