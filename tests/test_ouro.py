"""Ouro (layers run several times over shared weights, sandwich norms, an exit
gate) through the program's normal paths, on the CPU at a small size with
seeded random weights, each against the plain reference of
`benchmarks/arch/ouro.py`: `forward`; chunked paged prefill then paged decode
through the block manager's tables across several blocks (logits, not
tokens), with tables of one tile and of sixteen; verify against sequential
decode; what the exit gate read; five wrong references that must fail; the
engine under KV pressure; the programs that refuse the loop; and a one-pass
model without post-norms left as the parent commit had it."""

import copy
import hashlib

import numpy as np
import pytest

from benchmarks.arch import ouro as arch
from benchmarks.arch import ouro_reference as reference

BS = 8
PUBLISHED = {
    "num_hidden_layers": 3, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 16, "intermediate_size": 96,
    "max_position_embeddings": 256, "vocab_size": 300, "rope_theta": 1000000,
    "rms_norm_eps": 1e-6, "total_ut_steps": 4, "early_exit_threshold": 1,
    "program_model": "ouro-2.6b",
}
TOL = 2e-5      # float32 program against float32 reference, logits near 1
FORMS = {"one-shot": 1 << 20, "tiled": 16}      # keys a trip of the key loop
WRONG = {
    "three_passes_for_four": {"ut_steps": 3},
    "every_pass_on_the_first_pass_cache": {"cache_of_pass_one": True},
    "no_norm_between_passes": {"norm_between_passes": False},
    "no_post_norms": {"post_norms": False},
    "second_block_unseen": {"keys_unseen": (BS, 2 * BS)},
}


@pytest.fixture(scope="module")
def case():
    """(cfg, params, dims, tokens [130], reference logits [130, V], the
    reference's exit gates [4, 130])."""
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
    _x, lams = reference.make_hidden(m)(params, jnp.asarray(tokens))
    assert np.abs(want).max() > 0.5
    return cfg, params, m, tokens, want, np.asarray(lams)


def _err(got, want):
    return float(np.abs(np.asarray(got) - want).max())


def test_pool_is_as_deep_as_passes_times_layers(case):
    from ray_tpu.models.gpt import CONFIGS, init_paged_cache, kv_layout

    lay = kv_layout(case[0])
    assert (lay.per_group, lay.passes, lay.depth) == (3, 4, 12)
    assert lay.windows == (0,) and lay.slot_of == (0, 1, 2)
    assert init_paged_cache(case[0], 10, BS)["k"].shape == (12, 10, BS, 4 * 16)
    full = CONFIGS["ouro-2.6b"]()
    assert kv_layout(full).depth == 192 and len(kv_layout(full).windows) == 1
    assert (full.ut_steps, full.sandwich_norm, full.n_layers) == (4, True, 48)


def test_n_params_counts_post_norms_and_gate(case):
    from ray_tpu.models.gpt import CONFIGS

    cfg, params = case[0], case[1]
    # as `n_params` always counted: no bias but the gate's, no final norm
    counted = sum(v.size for k, v in params.items()
                  if not k.startswith("b_") and not k.endswith("_b") and k != "ln_f_w")
    assert cfg.n_params == counted + 1
    # the issue's count at the published sizes: 2.668 B in all
    full = CONFIGS["ouro-2.6b"]()
    assert full.n_params == 48 * (51_380_224 + 4 * 2048) + 2 * 49152 * 2048 + 2049


def test_forward_matches_the_reference(case):
    import jax.numpy as jnp

    from ray_tpu.models.gpt import forward

    cfg, params, _m, tokens, want, _lams = case
    got = forward(params, jnp.asarray(tokens)[None], cfg)[0]
    assert _err(got, want) < TOL


@pytest.fixture(scope="module", params=list(FORMS))
def through(case, request, tile_keys):
    """Chunked `prefill_paged` then `decode_step_paged` over the tables the
    block manager gives: [(position, logits, None or the decode step's exit
    reading)] of every prefill chunk's last position and every decode step.
    Before every call the null block's rows are set to a large value: the
    mask must keep them from every output."""
    import jax.numpy as jnp

    from ray_tpu.models import gpt
    from ray_tpu.serve.engine import KVBlockManager

    cfg, params, _m, tokens, _want, _lams = case
    n_prompt, chunk = 100, 24
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

    out, start = [], 0
    with tile_keys(FORMS[request.param]) as (prefill, decode, _verify):
        while start < n_prompt:
            n = min(chunk, n_prompt - start)
            padded = np.zeros((1, 32), np.int32)
            padded[0, :n] = prompt[start:start + n]
            logits, kv = prefill(
                params, jnp.asarray(padded), jnp.int32(n), jnp.int32(start),
                table(), poisoned(kv), cfg)
            start += n
            out.append((start - 1, np.asarray(logits), None))
        for pos in range(n_prompt, len(tokens)):
            mgr.grow("s", pos + 1)
            (logits, exits), kv = decode(
                params, jnp.asarray(tokens[pos:pos + 1]), jnp.asarray([pos]),
                table()[None], poisoned(kv), cfg)
            out.append((pos, np.asarray(logits)[0], np.asarray(exits)))
    return out


def test_paged_prefill_and_decode_across_blocks_match_the_reference(case, through):
    want = case[4]
    assert len(through) == 5 + 30 and through[-1][0] == 129 > 16 * BS
    assert max(_err(lg, want[pos]) for pos, lg, _ in through) < TOL


def test_decode_reports_what_the_exit_gate_read(case, through):
    """What comes back with a one-lane decode step's logits is the
    reference's own exit distribution at that position, one entry a pass the
    program ran; at the published threshold of 1.0 the rule lets no token go
    before pass 4."""
    lams = case[5]
    pdf = reference.exit_pdf(lams)                  # [4, 130]
    assert np.allclose(pdf.sum(0), 1.0) and (pdf > 0).all()
    for pos, _lg, exits in through[5:]:
        assert exits.shape == (4,) and np.abs(exits - pdf[:, pos]).max() < 1e-5
    assert (reference.exit_steps(lams, 1.0) == 4).all()
    early = reference.exit_steps(lams, 0.5)
    assert early.min() == 1 and early.max() <= 4 and (early < 4).any()


@pytest.mark.parametrize("form", list(FORMS))
def test_verify_step_equals_sequential_decode(case, form, tile_keys):
    """Four tokens a lane in one forward, beside a padding lane."""
    import jax.numpy as jnp

    from ray_tpu.models import gpt

    cfg, params, _m, tokens, want, _lams = case
    n0, k1 = 60, 4
    table = np.zeros((16,), np.int32)
    table[:10] = 1 + np.arange(10)
    kv = gpt.init_paged_cache(cfg, 12, BS)
    padded = np.zeros((1, 64), np.int32)
    padded[0, :n0] = tokens[:n0]
    toks = np.zeros((2, k1), np.int32)
    toks[0] = tokens[n0:n0 + k1]
    with tile_keys(FORMS[form]) as (prefill, _decode, verify):
        _, kv = prefill(params, jnp.asarray(padded), jnp.int32(n0),
                        jnp.int32(0), jnp.asarray(table), kv, cfg)
        logits, _ = verify(
            params, jnp.asarray(toks), jnp.asarray([n0, 0]), jnp.asarray([k1, 0]),
            jnp.asarray(np.stack([table, np.zeros_like(table)])), kv, cfg)
    assert _err(logits[0], want[n0:n0 + k1]) < TOL


@pytest.mark.parametrize("form", list(FORMS))
def test_decode_over_heads_of_a_whole_lane_tile_matches_the_reference(form, tile_keys):
    """Heads of 128 features, one query a head: the decode step, the shape
    the decode kernel takes on the chip, in the per-head form it takes off
    it (one shot and key loop), beside a padding lane, against the
    reference."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt

    pub = {**PUBLISHED, "num_attention_heads": 2, "num_key_value_heads": 2,
           "head_dim": 128, "num_hidden_layers": 2}
    m = arch.dims(pub, False)
    name, overrides = arch.program(pub, m)
    cfg = gpt.CONFIGS[name](**overrides, dtype=jnp.float32,
                            param_dtype=jnp.float32, remat=False)
    params = gpt.init_params(jax.random.PRNGKey(5), cfg)
    tokens = np.random.default_rng(1).integers(1, m["vocab_size"], 70)
    want = arch.make_logits(m)(params, tokens)
    n0 = 40
    table = np.zeros((2, 16), np.int32)
    table[0, :9] = 1 + np.arange(9)                 # lane 1: padding
    kv = gpt.init_paged_cache(cfg, 12, BS)
    padded = np.zeros((1, 64), np.int32)
    padded[0, :n0] = tokens[:n0]
    errs = []
    with tile_keys(FORMS[form]) as (prefill, decode, _verify):
        _, kv = prefill(params, jnp.asarray(padded), jnp.int32(n0), jnp.int32(0),
                        jnp.asarray(table[0]), kv, cfg)
        for pos in range(n0, len(tokens)):
            (logits, _exits), kv = decode(
                params, jnp.asarray([tokens[pos], 0]), jnp.asarray([pos, 0]),
                jnp.asarray(table), kv, cfg)
            errs.append(_err(logits[0], want[pos]))
    assert len(errs) == 30 and max(errs) < TOL


@pytest.mark.parametrize("wrong", list(WRONG))
def test_a_wrong_reference_fails_the_tolerance_threefold(case, through, wrong):
    m = {**copy.deepcopy(case[2]), **WRONG[wrong]}
    off = arch.make_logits(m)(case[1], case[3])
    assert _err(off, case[4]) > 3 * TOL
    # and what the program computes is on the right side of it
    assert _err(off[-1], case[4][-1]) > 3 * _err(through[-1][1], case[4][-1])


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


def test_engine_under_kv_pressure_preempts_resumes_stays_exact_and_counts_passes(case):
    cfg, params, m, tokens, _want, _lams = case
    free_run = _engine(case)
    # two lanes fit at admission (3 blocks each) and outgrow the pool of 12
    # while they decode (8 each at 60 tokens)
    tight = _engine(case, num_blocks=13)
    prompts = [[int(t) for t in tokens[:20]], [int(t) for t in tokens[30:50]]]
    outs = []
    for eng in (free_run, tight):
        rids = [eng.submit(p, 40) for p in prompts]
        _drain(eng)
        outs.append([list(eng.stream(r)) for r in rids])
    assert outs[0] == outs[1]
    # each token is the reference's own choice at its position (float32)
    want = arch.make_logits(m)(params, np.asarray(prompts[0] + outs[0][0][:-1]))[19:]
    assert (want.argmax(-1) == np.asarray(outs[0][0])).all()
    assert tight.stats()["total_preemptions"] > 0
    assert tight.block_manager.stats().used_blocks == 0
    # every decoded token ran every pass: 2 x 39 tokens through the decode
    # program without pressure (the first of the 40 is the prefill's); a
    # preempted lane's tokens come back as a prompt, so no more with it
    free, held = free_run.stats(), tight.stats()
    assert free["ut_passes_run"] == free["ut_passes_full"] == 4 * 2 * 39
    assert 0 < held["ut_passes_run"] == held["ut_passes_full"] <= 4 * 2 * 39


@pytest.mark.parametrize("ran", [4, 3])
def test_step_record_carries_the_passes_the_program_ran_and_the_gate(
        case, monkeypatch, ran):
    """The count of passes is the length of what the decode program hands
    back: an engine whose program runs three passes for the model's four
    says so, on the record and in `ut_passes_run` / `ut_passes_full`."""
    import dataclasses

    from ray_tpu.util import flight

    records = []
    monkeypatch.setattr(flight, "enabled", lambda: True)
    monkeypatch.setattr(
        flight, "record",
        lambda name, *a, attrs=None, **kw: records.append((name, attrs)))
    eng = _engine(case)
    decode, short = eng._decode, dataclasses.replace(eng.cfg, ut_steps=ran)
    monkeypatch.setattr(eng, "_decode", lambda *a: decode(*a[:-1], short))
    rid = eng.submit([int(t) for t in case[3][:30]], 5)
    _drain(eng)
    assert len(list(eng.stream(rid))) == 5
    steps = [a for n, a in records if n == "engine.step"]
    decodes = [a for a in steps if a["decodes"]]
    assert decodes and all(a["ut_passes"] == ran and 1.0 < a["exit_step_mean"] < ran
                           and 0.0 < a["exit_cdf_early"] < 1.0 for a in decodes)
    assert all("exit_step_mean" not in a and "ut_passes" not in a
               for a in steps if not a["decodes"])
    stats = eng.stats()
    assert (stats["ut_passes_run"], stats["ut_passes_full"]) == (ran * 4, 4 * 4)


def test_a_one_pass_and_a_four_pass_engine_do_not_adopt_each_others_blocks(case):
    import dataclasses

    looped = _engine(case)
    once = _engine((dataclasses.replace(case[0], ut_steps=1),
                    {k: v for k, v in case[1].items() if "exit_gate" not in k}))
    assert looped._kv_sig() != once._kv_sig()
    assert looped._kv_sig().startswith("12/4:") and once._kv_sig().startswith("3/1:")
    assert looped.import_blocks({"sig": once._kv_sig(), "digests": ["00"]}) == 0
    blob = looped._block_blobs([1])[0]
    assert blob.shape == (2, 12, BS, 64)


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
    }


@pytest.mark.parametrize("what", ["dense-cache prefill", "dense-cache decode_step",
                                  "pipeline stage", "MPMD stage split", "GPipe pipeline",
                                  "make_train_step", "loss_fn"])
def test_programs_that_cannot_take_the_loop_refuse_it_by_name(case, what):
    with pytest.raises(NotImplementedError, match="ut_steps"):
        _refusals()[what](case[0], case[1])


def test_config_and_architecture_module_refuse_what_is_not_the_model():
    from ray_tpu.models import gpt

    with pytest.raises(ValueError, match="ut_steps"):
        gpt.GPTConfig(ut_steps=0)
    with pytest.raises(ValueError, match="sandwich_norm"):
        gpt.GPTConfig(sandwich_norm=True, parallel_block=True)
    m = arch.dims({**PUBLISHED, "early_exit_threshold": 0.9}, False)
    with pytest.raises(SystemExit, match="early_exit_threshold"):
        arch.program(PUBLISHED, m)
    with pytest.raises(SystemExit, match="no model"):
        arch.program({**PUBLISHED, "program_model": "ouro-of-tomorrow"}, m)
    with pytest.raises(NotImplementedError, match="objective"):
        arch.make_loss(m)


# ------------------------------------------------- the models the repo had
# sha256[:16] of the lowered text of gpt2-small's paged decode program (4
# lanes, tables of 8 blocks of 16, a pool of 64 blocks) at the parent commit
# ef3feb3, under the jax it was taken with: over the public tree, and
# (taken at PR 50) over the tree as an engine holds it (`gpt.hold_served`).
_PARENT_DECODE = {"public": ("bc9840091a56e28e", 46271), "held": ("bb4c1630c27def5b", 46271)}


@pytest.mark.parametrize("form", list(_PARENT_DECODE))
def test_one_pass_without_post_norms_is_the_parents_program(form):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt

    cfg = gpt.CONFIGS["gpt2-small"](remat=False, remat_policy=None)
    assert (cfg.ut_steps, cfg.sandwich_norm) == (1, False)
    lay = gpt.kv_layout(cfg)
    assert (lay.per_group, lay.depth, lay.passes, lay.windows) == (12, 12, 1, (0,))
    assert lay.slot_of == tuple(range(12)) and lay.group_of == (0,) * 12
    shapes = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
    kv = shapes(jax.eval_shape(lambda: gpt.init_paged_cache(cfg, 64, 16)))
    assert kv["k"].shape == kv["v"].shape == (12, 64, 16, 768)
    tree = jax.eval_shape(lambda k: gpt.init_params(k, cfg), jax.random.PRNGKey(0))
    assert not {"ln1_post_w", "ln2_post_w", "exit_gate_w"} & set(tree)
    if form == "held":
        tree = jax.eval_shape(lambda t: gpt.hold_served(t)[0], tree)
    smallthinker = gpt.CONFIGS["smallthinker-21b-a3b"](n_layers=12)
    assert gpt.kv_layout(smallthinker).depth == gpt.kv_layout(smallthinker).per_group == 3
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    # the logits-returning program itself: the engine's puts a sampler behind it
    text = jax.jit(gpt.decode_step_paged, static_argnums=(5,), donate_argnums=(4,)).lower(
        shapes(tree), i32(4), i32(4), i32(4, 8), kv, cfg).as_text()
    assert "while" in text and text.count("stablehlo.while") == 1   # one layer scan
    if jax.__version__ != "0.9.0":
        pytest.skip(f"the parent's digest was taken under jax 0.9.0, not {jax.__version__}")
    assert (hashlib.sha256(text.encode()).hexdigest()[:16], len(text)) == _PARENT_DECODE[form]
