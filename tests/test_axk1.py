"""A.X-K1 (latent attention over one cached row a token, YaRN rotary, a leading
dense layer, sigmoid-routed experts of which a range is held here beside a
shared expert) through the program's normal paths, on the CPU at a small size
with seeded random weights, each against the plain reference of
`benchmarks/arch/axk1.py`: `forward`; chunked paged prefill then paged decode
through the block manager's tables (logits, not tokens), with tables of one
tile and of many (the key loop); bfloat16 under its own tolerance; absorbed
against expanded attention; the held ranges and the shared expert adding up
to the uncut layer; the router and the YaRN tables against hand-written
numbers; what the pool declares and what refuses it; five wrong references
that must fail; the engine's counts; the programs that refuse the new
fields; and the paged programs of the models the repo had, text for text."""

import copy
import dataclasses
import hashlib
import math

import numpy as np
import pytest

from benchmarks.arch import axk1 as arch
from benchmarks.arch import axk1_reference as reference

BS = 8
PUBLISHED = {
    "num_hidden_layers": 3, "first_k_dense_replace": 1, "hidden_size": 64,
    "num_attention_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "intermediate_size": 96, "moe_intermediate_size": 32, "n_routed_experts": 4,
    "n_shared_experts": 1, "num_experts_per_tok": 3, "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid", "norm_topk_prob": True, "topk_method": "none",
    "max_position_embeddings": 4096, "vocab_size": 300, "rope_theta": 10000,
    "rms_norm_eps": 1e-6,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 32, "mscale": 1,
                     "mscale_all_dim": 1, "original_max_position_embeddings": 64,
                     "type": "yarn"},
    "deployment": {"router_experts": 16, "held_experts_start": 4,
                   "served_positions": 256},
    "program_model": "ax-k1",
}
TOL = 2e-5      # float32 program against float32 reference, logits near 1
# bfloat16 weights and activations against the float32 reference on the SAME
# (bfloat16-rounded) weights, the MEDIAN over the checked positions: rounding
# at 2^-8 through three layers reads 0.027-0.035 on logits of size 4. Not the
# largest: where bfloat16 decides a near-tie of the router's top-k otherwise
# than float32, a position's logits move further, as they would in the
# published model. The weakest wrong reference's median is 0.21.
TOL_BF16 = 0.1
FORMS = {"one-shot": 1 << 20, "tiled": 16}      # keys a trip of the key loop
WRONG = {
    "softmax_for_sigmoid": {"scoring": "softmax"},
    "no_mscale_in_the_scale": {"attn_mscale": False},
    "no_shared_expert": {"shared_expert": False},
    "held_range_shifted_by_one": {"held_start": 5},
    "rotary_on_the_wrong_columns": {"rope_cols": "nope"},
}


def _cfg(published=PUBLISHED, dtype="float32"):
    import jax.numpy as jnp

    from ray_tpu.models import gpt

    m = arch.dims(published, False)
    name, overrides = arch.program(published, m)
    dt = getattr(jnp, dtype)
    return gpt.CONFIGS[name](**overrides, dtype=dt, param_dtype=dt, remat=False), m


@pytest.fixture(scope="module")
def case():
    """(cfg, params, dims, tokens [130], reference logits [130, V])."""
    import jax

    from ray_tpu.models import gpt

    cfg, m = _cfg()
    params = gpt.init_params(jax.random.PRNGKey(3), cfg)
    tokens = np.random.default_rng(0).integers(1, m["vocab_size"], 130)
    want = arch.make_logits(m)(params, tokens)
    assert np.abs(want).max() > 0.5
    return cfg, params, m, tokens, want


def _err(got, want):
    return float(np.abs(np.asarray(got) - want).max())


def test_forward_matches_the_reference(case):
    import jax.numpy as jnp

    from ray_tpu.models.gpt import forward

    cfg, params, _m, tokens, want = case
    assert _err(forward(params, jnp.asarray(tokens)[None], cfg)[0], want) < TOL


def _through(cfg, params, tokens, jits, n_prompt=100, chunk=24):
    """Chunked `prefill_paged` then `decode_step_paged` over the tables the
    block manager gives: [(position, logits)] of every prefill chunk's last
    position and every decode step. Before every call the null block's rows
    are set to a large value: the mask must keep them from every output."""
    import jax.numpy as jnp

    from ray_tpu.models import gpt
    from ray_tpu.serve.engine import KVBlockManager

    prefill, decode, _verify = jits
    mgr = KVBlockManager(40, BS)
    kv = gpt.init_paged_cache(cfg, 40, BS)
    prompt = [int(t) for t in tokens[:n_prompt]]
    mgr.allocate_cached("s", prompt, n_prompt + 1)

    def table(width=32):
        t = np.zeros((width,), np.int32)
        tab = mgr.block_table("s")
        t[: len(tab)] = tab
        return jnp.asarray(t)

    def poisoned(kv):
        return {n: a.at[:, 0].set(1e4) for n, a in kv.items()}

    out, start, loads = [], 0, []
    while start < n_prompt:
        n = min(chunk, n_prompt - start)
        padded = np.zeros((1, 32), np.int32)
        padded[0, :n] = prompt[start:start + n]
        logits, kv = prefill(
            params, jnp.asarray(padded), jnp.int32(n), jnp.int32(start),
            table(), poisoned(kv), cfg)
        start += n
        out.append((start - 1, np.asarray(logits)))
    for pos in range(n_prompt, len(tokens)):
        mgr.grow("s", pos + 1)
        (logits, load), kv = decode(
            params, jnp.asarray(tokens[pos:pos + 1]), jnp.asarray([pos]),
            table()[None], poisoned(kv), cfg)
        out.append((pos, np.asarray(logits)[0]))
        loads.append(np.asarray(load))
    assert set(kv) == {"k"}
    return out, loads


@pytest.fixture(scope="module", params=list(FORMS))
def through(case, request, tile_keys):
    with tile_keys(FORMS[request.param]) as jits:
        return _through(case[0], case[1], case[3], jits)


def test_paged_prefill_and_decode_through_the_latent_pool_match_the_reference(
        case, through):
    """The tiled form's tables are 16 tiles of 16 keys: a prompt and a decode
    context longer than one key tile run the key loop."""
    out, loads = through
    want = case[4]
    assert len(out) == 5 + 30 and out[-1][0] == 129 > 16 * BS
    assert max(_err(lg, want[pos]) for pos, lg in out) < TOL
    # one lane, top-3 of 16 with 4 held, two expert layers: what comes back
    # beside the logits is (touched, busiest share, held, all, 1 if none was
    # held: the layer's routing is empty) a layer
    load = np.stack(loads)
    assert load.shape == (30, 5) and (load[:, 3] == 3).all()
    assert (load[:, 2] <= 3).all() and (load[:, 0] == load[:, 2]).all()
    assert 0 < load[:, 2].mean() < 3
    assert set(load[:, 4].tolist()) <= {0.0, 0.5, 1.0} and 0 < load[:, 4].mean() < 1
    assert ((load[:, 4] == 1.0) == (load[:, 2] == 0)).all()
    assert (load[:, 4] <= 1.0 - load[:, 2] / 6).all()  # a layer holds 3 at most


def test_bfloat16_program_stays_near_the_reference_on_the_same_weights(tile_keys):
    import jax

    from ray_tpu.models import gpt

    cfg, m = _cfg(dtype="bfloat16")
    params = gpt.init_params(jax.random.PRNGKey(3), cfg)
    tokens = np.random.default_rng(0).integers(1, m["vocab_size"], 130)
    want = arch.make_logits(m)(params, tokens)
    with tile_keys(FORMS["tiled"]) as jits:
        out, _ = _through(cfg, params, tokens, jits)
    errs = [_err(lg, want[pos]) for pos, lg in out]
    assert 1e-3 < np.median(errs) < TOL_BF16
    for wrong in WRONG.values():
        off = arch.make_logits({**m, **wrong})(params, tokens)
        assert np.median(np.abs(off - want).max(-1)) > 5 * np.median(errs)


def test_absorbed_attention_equals_the_expanded_form(case):
    """One layer's attention as `_block` computes it (the key up-projection
    moved onto the query, every head over the one row [c | rot(k_r)], the
    value up-projection afterwards) against the reference's expanded form
    (per-head keys [k_nope | rot(k_r)] and values), on the same input."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt

    cfg, params, m, _tokens, _want = case
    p = {k: v[1] for k, v in gpt._layer_stack(params).items()}
    x = jax.random.normal(jax.random.PRNGKey(9), (1, 40, cfg.d_model), jnp.float32)
    pos = jnp.arange(40)
    h = gpt._norm(x, p["ln1_w"], p["ln1_b"], cfg.norm)
    q, k, expand = gpt._project_latent(cfg, p, h, gpt._rope_tables(cfg), pos)
    assert q.shape == (1, 4, 40, 40) and k.shape == (1, 1, 40, 40)
    attn = expand(gpt._attention_plain(cfg, q, k, None, pos))
    got = jnp.einsum("bhsd,hde->bse", attn, p["w_o"]) + p["b_o"]
    with jax.default_matmul_precision("highest"):
        want = reference._latent_attention(x[0], p, m)
    assert np.abs(np.asarray(want)).max() > 0.1 and _err(got[0], want) < TOL


@pytest.mark.parametrize("side", ["program", "reference"])
def test_the_held_ranges_and_the_shared_expert_once_add_up_to_the_uncut_layer(side):
    """Four chips of four experts: the parts of an expert layer's result
    that the four held ranges give, with the shared expert (which every chip
    computes alike) counted once, are what the layer gives with all sixteen
    experts here."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt

    whole_pub = {**PUBLISHED, "n_routed_experts": 16,
                 "deployment": {**PUBLISHED["deployment"], "held_experts_start": 0}}
    cfg, m = _cfg(whole_pub)
    params = gpt.init_params(jax.random.PRNGKey(4), cfg)
    p = {k: v[0] for k, v in gpt._layer_stack(params).items()}
    g = jax.random.normal(jax.random.PRNGKey(5), (1, 50, cfg.d_model), jnp.float32)

    def layer(cfg, m, p):
        if side == "program":
            return np.asarray(gpt._mlp(cfg, p, p["moe_router"], None, g)[0][0])
        with jax.default_matmul_precision("highest"):
            return np.asarray(reference._expert_mlp(g[0], p, m))

    def shared_only(cfg, m, p):
        none = {**p, **{k: jnp.zeros_like(p[k])
                        for k in ("moe_w_gate", "moe_w_in", "moe_w_out")}}
        return layer(cfg, m, none)

    whole = layer(cfg, m, p)
    shared = shared_only(cfg, m, p)
    parts = []
    for first in (0, 4, 8, 12):
        cut = {**p, **{k: p[k][first:first + 4]
                       for k in ("moe_w_gate", "moe_w_in", "moe_w_out")}}
        cfg_cut = dataclasses.replace(cfg, moe_held=(first, 4))
        m_cut = {**m, "held_start": first, "held_count": 4}
        parts.append(layer(cfg_cut, m_cut, cut) - shared)
    assert np.abs(whole - shared).max() > 0.1       # the routed part is no rounding
    assert np.abs(sum(parts) + shared - whole).max() < TOL
    assert all(np.abs(part).max() > 0.01 for part in parts)


def test_router_is_sigmoid_top_k_normalised_and_scaled():
    import jax.numpy as jnp

    from ray_tpu.ops import moe

    logits = jnp.asarray([[0.0, 2.0, -1.0, 1.0, 3.0],
                          [1.0, 1.5, 0.5, -2.0, -3.0]])
    idx, w = moe.dropless_route(logits, 3, "sigmoid", 2.5)
    sig = lambda v: 1.0 / (1.0 + math.exp(-v))
    assert idx.tolist() == [[4, 1, 3], [1, 0, 2]]
    for row, kept in zip(np.asarray(w), ([3.0, 2.0, 1.0], [1.5, 1.0, 0.5])):
        s = [sig(v) for v in kept]
        assert np.allclose(row, [2.5 * v / sum(s) for v in s], atol=1e-6)
    assert np.allclose(np.asarray(w).sum(-1), 2.5)
    # 0.9526 / (0.9526 + 0.8808 + 0.7311) x 2.5, by hand
    assert abs(float(w[0, 0]) - 0.92863) < 1e-4
    # the softmax the other expert model keeps is untouched by the new data
    idx2, w2 = moe.dropless_route(logits, 3)
    assert idx2.tolist() == idx.tolist() and np.allclose(np.asarray(w2).sum(-1), 1.0)
    held = moe.dropless_combine(idx, w, 5)[:, 1:3]
    load = moe.dropless_load(held, None, 3)
    # experts 1 and 2 held: token 0 chose expert 1, token 1 both
    assert np.allclose([float(v) for v in load], [2.0, 2 / 3, 3.0, 6.0, 0.0])
    # token 0 alone, with expert 2 alone held: nothing of its routing fell here
    none = moe.dropless_load(held[:1, 1:], None, 3)
    assert [float(v) for v in none] == [0.0, 0.0, 0.0, 3.0, 1.0]
    with pytest.raises(ValueError, match="scoring"):
        moe.dropless_route(logits, 3, "tanh")


def test_yarn_tables_follow_the_formula(case):
    from ray_tpu.models import gpt

    cfg, _params, m, _tokens, _want = case
    cos, sin = (np.asarray(t) for t in gpt._rope_tables(cfg))
    assert cos.shape == sin.shape == (256, 4)
    freq = reference.yarn_frequencies(m)
    # by hand, d = 8, theta 1e4, 64 original positions: n(32) = 8 ln(64 / (2 pi
    # 32)) / (2 ln 1e4) = -0.497 -> 0, n(1) = 1.008 -> 2: dimension 0 is left,
    # dimension 1 half interpolated, dimensions 2 and 3 divided by 32
    assert np.allclose(freq, [1.0, 0.1 * (0.5 + 0.5 / 32), 0.01 / 32, 0.001 / 32])
    pos = np.arange(256)[:, None]
    assert np.abs(cos - np.cos(pos * freq)).max() < 1e-4
    assert np.abs(sin - np.sin(pos * freq)).max() < 1e-4
    # mscale = 0.1 ln 32 + 1 on both sides of the tables' ratio, squared in the scale
    assert abs(gpt._latent_scale(cfg) - 24 ** -0.5 * (0.1 * math.log(32) + 1) ** 2) < 1e-9
    published = gpt.CONFIGS["ax-k1"]()
    assert abs(gpt._latent_scale(published) - 192 ** -0.5 * 1.3465736 ** 2) < 1e-6


def test_the_pool_declares_one_latent_row_and_the_engine_reckons_bytes_from_it(case):
    import jax

    from ray_tpu.models import gpt

    cfg = case[0]
    lay = gpt.kv_layout(cfg)
    assert (lay.depth, lay.per_group, lay.passes, lay.windows) == (3, 3, 1, (0,))
    assert (lay.key_row, lay.value_row) == (128, 0)      # 32 + 8 in whole tiles
    pool = gpt.init_paged_cache(cfg, 10, BS)
    assert set(pool) == {"k"} and pool["k"].shape == (3, 10, BS, 128)
    assert lay.block_bytes(BS, 4) == pool["k"].nbytes // 10
    eng = _engine(case)
    assert eng.kv_block_bytes == 3 * BS * 128 * 4 and set(eng.kv) == {"k"}
    full = gpt.CONFIGS["ax-k1"](n_layers=7, moe_held=(0, 12), vocab_size=20480)
    lay = gpt.kv_layout(full)
    assert (lay.depth, lay.key_row, lay.value_row) == (7, 640, 0)    # 512 + 64 -> 640
    assert lay.block_bytes(64, 2) == 7 * 64 * 1280
    assert arch.kv_block_bytes(arch.dims(_published_cell(), False), 64) == 7 * 64 * 1280
    pool = jax.eval_shape(lambda: gpt.init_paged_cache(full, 4096, 64))
    assert pool["k"].shape == (7, 4096, 64, 640)
    tree = jax.eval_shape(lambda k: gpt.init_params(k, full), jax.random.PRNGKey(0))
    assert tree["moe_w_in"].shape == (6, 12, 7168, 2048)
    assert tree["moe_router"].shape == (6, 7168, 192)
    assert tree["lead_w_in"].shape == (1, 7168, 18432)
    assert tree["w_dkv"].shape == (6, 7168, 576) and tree["lm_head"].shape == (7168, 20480)
    # a K/V model's layout says what it always held
    old = gpt.kv_layout(gpt.CONFIGS["smallthinker-21b-a3b"](n_layers=12))
    assert (old.key_row, old.value_row) == (512, 512)


def _published_cell():
    from benchmarks import harness

    return harness.load_json(harness.ROOT, "benchmarks/configs/ax-k1.json")


@pytest.mark.parametrize("wrong", list(WRONG))
def test_a_wrong_reference_fails_the_tolerance_threefold(case, through, wrong):
    m = {**copy.deepcopy(case[2]), **WRONG[wrong]}
    off = arch.make_logits(m)(case[1], case[3])
    assert _err(off, case[4]) > 3 * TOL
    # and what the program computes is on the right side of it
    assert _err(off[-1], case[4][-1]) > 3 * _err(through[0][-1][1], case[4][-1])


@pytest.mark.parametrize("form", list(FORMS))
def test_verify_step_equals_sequential_decode(case, form, tile_keys):
    import jax.numpy as jnp

    from ray_tpu.models import gpt

    cfg, params, _m, tokens, want = case
    n0, k1 = 40, 4
    table = np.zeros((2, 16), np.int32)
    table[0, :9] = 1 + np.arange(9)                 # lane 1: padding
    kv = gpt.init_paged_cache(cfg, 12, BS)
    padded = np.zeros((1, 64), np.int32)
    padded[0, :n0] = tokens[:n0]
    with tile_keys(FORMS[form]) as (prefill, _decode, verify):
        _, kv = prefill(params, jnp.asarray(padded), jnp.int32(n0), jnp.int32(0),
                        jnp.asarray(table[0]), kv, cfg)
        toks = np.zeros((2, k1), np.int32)
        toks[0] = tokens[n0:n0 + k1]
        logits, kv = verify(params, jnp.asarray(toks), jnp.asarray([n0, 0]),
                            jnp.asarray([k1, 0]), jnp.asarray(table), kv, cfg)
    assert _err(logits[0], want[n0:n0 + k1]) < TOL


# ------------------------------------------------------------------ engine
def _engine(case, **opts):
    from ray_tpu.serve.engine import EngineOptions, InferenceEngine

    options = EngineOptions(**{**dict(num_blocks=80, block_size=BS, max_num_seqs=4,
                                      max_step_tokens=64, prefill_chunk_tokens=24,
                                      host_kv_bytes=0), **opts})
    return InferenceEngine(case[0], params=case[1], options=options)


def _drain(eng):
    while eng.scheduler.has_work():
        eng.step()
        eng.block_manager.check_invariants()


def test_engine_serves_exactly_and_counts_the_assignments_that_fell_here(
        case, monkeypatch):
    from ray_tpu.util import flight

    records = []
    monkeypatch.setattr(flight, "enabled", lambda: True)
    monkeypatch.setattr(
        flight, "record",
        lambda name, *a, attrs=None, **kw: records.append((name, attrs)))
    cfg, params, m, tokens, _want = case
    eng = _engine(case)
    facts, read = [], eng._decode_facts

    def keeping(lanes, more):   # what each decode program handed back beside its ids
        facts.append(np.asarray(more[0]))
        return read(lanes, more)

    monkeypatch.setattr(eng, "_decode_facts", keeping)
    prompts = [[int(t) for t in tokens[:30]], [int(t) for t in tokens[40:75]]]
    rids = [eng.submit(p, 20) for p in prompts]
    _drain(eng)
    outs = [list(eng.stream(r)) for r in rids]
    for prompt, out in zip(prompts, outs):
        want = arch.make_logits(m)(params, np.asarray(prompt + out[:-1]))[len(prompt) - 1:]
        assert (want.argmax(-1) == np.asarray(out)).all()
    steps = [a for n, a in records if n == "engine.step"]
    decodes = [a for a in steps if a["decodes"]]
    assert decodes and all(
        a["assign_total"] == 2 * 3 * a["decodes"] and 0 <= a["assign_held"] <= a["assign_total"]
        and a["experts_touched"] <= 4 and 0.0 <= a["expert_load_max"] <= 1.0
        for a in decodes)
    assert all("assign_held" not in a for a in steps if not a["decodes"])
    stats = eng.stats()
    assert stats["moe_assign_total"] == sum(a["assign_total"] for a in decodes) \
        == 2 * 3 * 2 * 19
    assert 0 < stats["moe_assign_held"] == sum(a["assign_held"] for a in decodes) \
        < stats["moe_assign_total"]
    # the expert layers (two) whose routing left no assignment here, of the
    # decode programs' real lanes: the scalar that rode back, summed; a layer
    # that is not empty holds an assignment, and a step with none has both empty
    assert len(facts) == len(decodes) and all(f.shape == (5,) for f in facts)
    assert stats["moe_layers_routed"] == 2 * len(decodes)
    empty = [int(round(2 * float(f[4]))) for f in facts]
    assert stats["moe_layers_empty"] == sum(empty)
    for n, a in zip(empty, decodes):
        assert 2 - a["assign_held"] <= n <= 2 - (a["assign_held"] > 0)
    assert 0 < stats["moe_layers_empty"] < stats["moe_layers_routed"]
    # every program's tokens as it is shaped x the two expert layers: the
    # prompts' chunks (24 + 6 and 24 + 11 tokens in programs of 32, 8, 32 and
    # 16) and the decode steps (at most 4 lanes) take the one served form
    assert sum(a["prefills"] for a in steps) == 4
    assert stats["moe_tokens_grouped"] == stats["moe_tokens_expert"]
    lanes = stats["moe_tokens_expert"] - 2 * (32 + 8 + 32 + 16)
    assert 2 * len(decodes) <= lanes <= 2 * 4 * len(decodes)
    # a latent layer's keys are counted as a global layer's
    assert 0 < stats["attn_keys_run"] <= stats["attn_keys_padded"]


def test_a_latent_and_a_kv_engine_refuse_each_others_blocks(case):
    import jax

    from ray_tpu.models import gpt
    from ray_tpu.serve.engine import EngineOptions, InferenceEngine

    latent = _engine(case)
    plain_cfg = gpt.gpt2_small(n_layers=3, d_model=64, n_heads=4, d_head=16, d_mlp=96,
                               vocab_size=300, max_seq=256, remat=False)
    plain = InferenceEngine(
        plain_cfg, params=gpt.init_params(jax.random.PRNGKey(0), plain_cfg),
        options=EngineOptions(num_blocks=20, block_size=BS, host_kv_bytes=0))
    assert latent._kv_sig().endswith(":latent128") and plain._kv_sig().endswith(":rows")
    assert latent._kv_sig() != plain._kv_sig()
    desc = {"sig": latent._kv_sig(), "digests": ["00"]}
    assert plain.import_blocks(desc) == 0
    with pytest.raises(NotImplementedError, match="latent row"):
        latent.import_blocks({"sig": plain._kv_sig(), "digests": ["00"]})
    with pytest.raises(NotImplementedError, match="latent row"):
        latent.export_prompt_kv([1, 2, 3])


@pytest.mark.parametrize("opts", [{"host_kv_bytes": 1 << 20}, {"role": "prefill"},
                                  {"role": "decode"}])
def test_engine_refuses_at_construction_what_moves_k_and_v_blocks(case, opts):
    with pytest.raises(ValueError, match="latent row"):
        _engine(case, **opts)


# ---------------------------------------------------------------- refusals
def _refusals():
    import jax.numpy as jnp
    import optax

    from ray_tpu.models import gpt

    toks = jnp.zeros((1, 4), jnp.int32)
    return {
        "dense-cache prefill": lambda c, p: gpt.prefill(p, toks, c, None),
        "dense-cache decode_step": lambda c, p: gpt.decode_step(p, toks[0], None, c),
        "pipeline stage": lambda c, p: gpt.stage_forward(p, toks, c, first=True, last=True),
        "MPMD stage split": lambda c, p: gpt.make_mpmd_stage_fns(c, 0, 3),
        "GPipe pipeline": lambda c, p: gpt.pipeline_loss_fn(p, {"tokens": toks}, c, None, 1),
        "make_train_step": lambda c, p: gpt.make_train_step(c, optax.sgd(0.1)),
        "loss_fn": lambda c, p: gpt.loss_fn(p, {"tokens": toks}, c),
        "param_shardings": lambda c, p: gpt.param_logical_dims(c),
    }


@pytest.mark.parametrize("what", ["dense-cache prefill", "dense-cache decode_step",
                                  "pipeline stage", "MPMD stage split", "GPipe pipeline",
                                  "make_train_step", "loss_fn", "param_shardings"])
def test_programs_that_cannot_take_the_new_fields_refuse_them_by_name(case, what):
    # (the MPMD split refuses any expert model before it looks further)
    with pytest.raises(NotImplementedError, match="kv_lora_rank|MoE aux loss"):
        _refusals()[what](case[0], case[1])


def test_config_and_architecture_module_refuse_what_is_not_the_model():
    from ray_tpu.models import gpt

    with pytest.raises(ValueError, match="moe_held"):
        gpt.GPTConfig(moe_experts=8, moe_held=(6, 4))
    with pytest.raises(ValueError, match="moe_scoring"):
        gpt.GPTConfig(moe_scoring="tanh")
    with pytest.raises(ValueError, match="q_lora_rank"):
        gpt.GPTConfig(kv_lora_rank=32, pos="rotary")
    with pytest.raises(ValueError, match="dense_layers"):
        gpt.GPTConfig(dense_layers=1, d_dense_mlp=8, ut_steps=2)
    with pytest.raises(NotImplementedError, match="unit_stream"):
        gpt.init_params(None, gpt.GPTConfig(moe_shared=1))
    with pytest.raises(SystemExit, match="no model"):
        arch.program({**PUBLISHED, "program_model": "ax-of-tomorrow"},
                     arch.dims(PUBLISHED, False))
    with pytest.raises(SystemExit, match="topk_method"):
        arch.dims({**PUBLISHED, "topk_method": "noaux_tc"}, False)
    full = gpt.CONFIGS["ax-k1"]()
    assert (full.n_layers, full.dense_layers, full.moe_experts, full.moe_top_k) == (61, 1, 192, 8)
    assert 518e9 < full.n_params < 520e9            # the published 519 B
    # PR 40: ssm_*; PR 43: window kind, gate; PR 51: block_pattern, Mamba-2's four, the bias, norm_eps;
    # PR 56: layer_pattern; PR 58: gdn_interval
    assert len(dataclasses.fields(gpt.GPTConfig)) == 34 + 10 + 5 + 4 + 7 + 1 + 1


# ------------------------------------------------- the models the repo had
# sha256[:16] and length of the lowered text of the engine's three paged
# programs (`serve/engine/engine.py` `_paged_jits`: each ends in the sampler;
# 4 lanes, a pool of 64 blocks of 16, a chunk of 32, 2 drafts) at the parent
# commit 4caabe5, under the jax they were taken with, with tables of 8 blocks
# (one tile) and of 128 (the key loop). PR 39 re-took the six
# `smallthinker-21b-a3b` entries (`decode`: 4 lanes took the loop over chosen
# experts and take the grouped form; `prefill` and `verify`, grouped since
# PR 35: their padding rows are now routed nowhere). PR 46 re-took the
# `decode` entry of `ouro-2.6b/8` and of `ouro-2.6b/128`: the two programs that
# held `lone` (one query a K/V head over heads of 128 as two products over the
# rows as the pool lays them), which went, and which take the per-head form
# every other shape takes off the chip. The other 16 are still the parent's:
# no other program of a model without experts moved. Since PR 50 these are
# the programs over the PUBLIC tree, which the paged entry points still take
# (all 24 held); over the tree as the engine holds it (`gpt.hold_served`: the
# fused q/k/v stack as [L, 3, H, Dh, E], one product) the 18 programs of the
# three models with that stack are `_HELD`'s, taken at PR 50, and
# `smallthinker-21b-a3b`'s six are the same text either way.
_PARENT = {
    "gpt2-small/8": {"decode": ["47e12eb441a8db6f", 56599], "prefill": ["3d712626f6741d9d", 56211], "verify": ["cabdde9d7cb27d1c", 46062]},
    "gpt2-small/128": {"decode": ["a469eadf508f0a50", 69719], "prefill": ["3eaeb73f7472490f", 69106], "verify": ["cdc8b418bab759b6", 59099]},
    "gpt2-large/8": {"decode": ["4a3c99ad312b90d7", 56890], "prefill": ["586bdd407d571263", 56494], "verify": ["9c9ab8dce652d623", 46341]},
    "gpt2-large/128": {"decode": ["3e8b3a276dffa813", 70014], "prefill": ["d8ff5e567f857d44", 69393], "verify": ["325d1493576d59f0", 59382]},
    "smallthinker-21b-a3b/8": {"decode": ["4e37645107422994", 85872], "prefill": ["683caebcddddb256", 82697], "verify": ["2e56174898997513", 74321]},
    "smallthinker-21b-a3b/128": {"decode": ["1758aba14558b3c2", 97901], "prefill": ["fb52c1ec3674f57b", 94689], "verify": ["3184a5406968d88f", 86472]},
    "ouro-2.6b/8": {"decode": ["a5b6302e6c2b7ba7", 71869], "prefill": ["6f59bfc7e81f8afa", 65849], "verify": ["441542f5c0143299", 57230]},
    "ouro-2.6b/128": {"decode": ["66e128546bb82d6a", 84626], "prefill": ["11867b981e748ff7", 79107], "verify": ["90cb98c74573bd2c", 70644]},
}
_HELD = {
    "gpt2-small/8": {"decode": ["b56c47b408be8554", 56599], "prefill": ["850d27659933581f", 56211], "verify": ["b001ec589d1bccd2", 46062]},
    "gpt2-small/128": {"decode": ["39b54ab0971106d7", 69719], "prefill": ["eabda753f8feb0fb", 69106], "verify": ["47500e17d45556f1", 59099]},
    "gpt2-large/8": {"decode": ["ffe96530c5772f2d", 56890], "prefill": ["d2aec4a49574648d", 56494], "verify": ["23454bc73f059c5a", 46341]},
    "gpt2-large/128": {"decode": ["e02139bfc5cf8c5d", 70014], "prefill": ["6c47cf156642ce48", 69393], "verify": ["43136697fc61cbd2", 59382]},
    "smallthinker-21b-a3b/8": _PARENT["smallthinker-21b-a3b/8"],
    "smallthinker-21b-a3b/128": _PARENT["smallthinker-21b-a3b/128"],
    "ouro-2.6b/8": {"decode": ["69f4ae76ede4bc49", 71869], "prefill": ["fac939052cd0d511", 65849], "verify": ["6605a66c22934b80", 57230]},
    "ouro-2.6b/128": {"decode": ["95852691bdb3f97e", 84626], "prefill": ["967ac20f5de5cb33", 79107], "verify": ["f8188bab94be5471", 70644]},
}


def _lowered(model, width, held=False):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt
    from ray_tpu.serve.engine.engine import _paged_jits, init_sampler

    overrides = {"n_layers": 12} if model.startswith("smallthinker") else {}
    cfg = gpt.CONFIGS[model](**overrides, remat=False, remat_policy=None)
    def tree(key):
        params = gpt.init_params(key, cfg)
        return gpt.hold_served(params)[0] if held else params

    params = jax.eval_shape(tree, jax.random.PRNGKey(0))
    kv = jax.eval_shape(lambda: gpt.init_paged_cache(cfg, 64, 16))
    last, sampling = jax.eval_shape(lambda: init_sampler(4, 0, 0.0))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    groups = len(gpt.kv_layout(cfg).windows)
    table = (width,) if groups == 1 else (groups, width)
    prefill, decode, verify = _paged_jits()
    return {
        "decode": decode.lower(params, i32(4, 4), i32(4, *table), kv, last, sampling, cfg),
        "prefill": prefill.lower(params, i32(1, 32), i32(3), i32(*table), kv, last,
                                 sampling, cfg),
        "verify": verify.lower(params, i32(4, 3), i32(4), i32(4), i32(4, *table), kv, cfg),
    }


@pytest.mark.parametrize("form", ["public", "held"])
@pytest.mark.parametrize("pinned", list(_PARENT))
def test_the_paged_programs_of_the_models_the_repo_had_are_the_parents(pinned, form):
    import jax

    model, width = pinned.rsplit("/", 1)
    texts = {k: v.as_text()
             for k, v in _lowered(model, int(width), held=form == "held").items()}
    assert all("stablehlo.while" in t for t in texts.values())
    if jax.__version__ != "0.9.0":
        pytest.skip(f"the parent's digests were taken under jax 0.9.0, not {jax.__version__}")
    got = {k: [hashlib.sha256(t.encode()).hexdigest()[:16], len(t)] for k, t in texts.items()}
    assert got == (_HELD if form == "held" else _PARENT)[pinned]
