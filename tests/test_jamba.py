"""AI21-Jamba2-3B's layer stack (state-space layers whose state is one fixed
slot a sequence, an attention layer between them, a tied head, no bias)
through the program's normal paths, on the CPU at a small size (two periods:
4 layers, attention where i % 2 == 1, so both mixers and both stacks run)
with seeded random weights, each against the plain reference of
`benchmarks/arch/jamba.py`: `forward`; chunked paged prefill then paged decode
through the block manager's tables AND state slots (logits, not tokens) with a
padded last chunk, padding lanes, two sequences interleaved chunk by chunk, a
sequence given up and recomputed, and a slot reused; the engine itself with a
preemption, slots turning over and two prompts that share three full blocks
under the prefix cache's default; the kernel against the plain scan at the
published inner width in interpret mode; a masked token's state bit for bit;
what the layout declares and the manager counts; what refuses the model; four
wrong references that must fail; the published initialisation."""

import dataclasses

import numpy as np
import pytest

from benchmarks.arch import jamba as arch

BS = 8
PUBLISHED = {
    "num_hidden_layers": 4, "attn_layer_period": 2, "attn_layer_offset": 1,
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 1,
    "intermediate_size": 96, "mamba_d_state": 16, "mamba_d_conv": 4,
    "mamba_expand": 2, "mamba_dt_rank": 8, "mamba_conv_bias": True,
    "mamba_proj_bias": False, "num_experts": 1, "tie_word_embeddings": True,
    "hidden_act": "silu", "sliding_window": None, "max_position_embeddings": 256,
    "vocab_size": 300, "rms_norm_eps": 1e-6, "program_model": "jamba2-3b",
}
# float32 program against the float32 reference: the largest difference of
# two logits over the largest logit in size (a tied head over a unit-size
# stream gives logits of some 50). Both sum the same terms in float32 in
# another order; 28 checked positions read under 1e-6.
TOL = 2e-5
WRONG = {
    "state_zeroed_at_chunk_edges": {"state_reset_every": 16},
    "tail_zeroed_at_chunk_edges": {"tail_reset_every": 16},
    "state_in_bfloat16": {"state_bf16": True},
    "no_inner_norms": {"inner_norms": False},
}


def _cfg(dtype="float32"):
    import jax.numpy as jnp

    from ray_tpu.models import gpt

    m = arch.dims(PUBLISHED, False)
    name, overrides = arch.program(PUBLISHED, m)
    dt = getattr(jnp, dtype)
    return gpt.CONFIGS[name](**overrides, dtype=dt, param_dtype=dt, remat=False), m


@pytest.fixture(scope="module")
def case():
    """(cfg, params, dims, tokens [2, 90], reference logits [2, 90, V])."""
    import jax

    from ray_tpu.models import gpt

    cfg, m = _cfg()
    params = gpt.init_params(jax.random.PRNGKey(3), cfg)
    tokens = np.random.default_rng(0).integers(1, m["vocab_size"], (2, 90))
    logits = arch.make_logits(m)
    want = np.stack([logits(params, t) for t in tokens])
    assert np.abs(want).max() > 5.0
    return cfg, params, m, tokens, want


def _err(got, want):
    return float(np.abs(np.asarray(got) - want).max() / np.abs(want).max())


def test_forward_matches_the_reference(case):
    import jax.numpy as jnp

    from ray_tpu.models.gpt import forward

    cfg, params, _m, tokens, want = case
    assert _err(forward(params, jnp.asarray(tokens), cfg), want) < TOL


# ------------------------------------------------- the paged programs, logits
class _Paged:
    """The two paged programs over the tables and state slots a
    `KVBlockManager` gives, the pool donated from call to call as the engine
    donates it. Before every call the null block's rows and the null slot's
    state are set to a large value: no real lane may read either."""

    CHUNK, WIDTH = 16, 16

    def __init__(self, cfg, params, slots=3):
        import jax

        from ray_tpu.models import gpt
        from ray_tpu.serve.engine import KVBlockManager

        self.cfg, self.params = cfg, params
        self.mgr = KVBlockManager(40, BS, state_slots=slots)
        self.kv = gpt.init_paged_cache(cfg, 40, BS, slots)
        self.prefill = jax.jit(gpt.prefill_paged, static_argnums=6, donate_argnums=5)
        self.decode = jax.jit(gpt.decode_step_paged, static_argnums=5, donate_argnums=4)

    def _poisoned(self):
        kv = dict(self.kv)
        for name in ("k", "v"):
            kv[name] = kv[name].at[:, 0].set(1e4)
        kv["state"] = {n: a.at[:, 0].set(1e4) for n, a in kv["state"].items()}
        return kv

    def table(self, sid):
        t = np.zeros((self.WIDTH,), np.int32)
        tab = self.mgr.block_table(sid)
        t[: len(tab)] = tab
        return t

    def admit(self, sid, prompt):
        _, cached = self.mgr.allocate_cached(sid, [int(t) for t in prompt], len(prompt) + 1)
        assert cached == 0
        return self.mgr.state_slot(sid)

    def chunk(self, sid, prompt, start):
        """One prefill chunk of `prompt` from `start`: (last position, logits)."""
        import jax.numpy as jnp

        n = min(self.CHUNK, len(prompt) - start)
        padded = np.zeros((1, self.CHUNK), np.int32)
        padded[0, :n] = prompt[start:start + n]
        logits, self.kv = self.prefill(
            self.params, jnp.asarray(padded), jnp.int32(n), jnp.int32(start),
            jnp.asarray(self.table(sid)), self._poisoned(), self.cfg,
            jnp.int32(self.mgr.state_slot(sid)))
        return start + n - 1, np.asarray(logits)

    def step(self, lanes, bucket):
        """One decode step of `lanes` [(sid, token, position)] in a program
        of `bucket` lanes: the rest are padding (null table, null slot)."""
        import jax.numpy as jnp

        tok, pos = np.zeros((bucket,), np.int32), np.zeros((bucket,), np.int32)
        tabs, slots = np.zeros((bucket, self.WIDTH), np.int32), np.zeros((bucket,), np.int32)
        for i, (sid, t, p) in enumerate(lanes):
            self.mgr.grow(sid, p + 1)
            tok[i], pos[i], tabs[i], slots[i] = t, p, self.table(sid), self.mgr.state_slot(sid)
        logits, self.kv = self.decode(
            self.params, jnp.asarray(tok), jnp.asarray(pos), jnp.asarray(tabs),
            self._poisoned(), self.cfg, jnp.asarray(slots))
        return np.asarray(logits)[: len(lanes)]


def test_chunked_prefill_then_decode_matches_the_reference_with_padding(case):
    """(a) a prompt of 40 = 16 + 16 + 8: the last chunk is half padding; (b)
    one real lane in a decode program of four."""
    cfg, params, _m, tokens, want = case
    run = _Paged(cfg, params)
    prompt = tokens[0, :40]
    run.admit("a", prompt)
    for start in (0, 16, 32):
        pos, logits = run.chunk("a", prompt, start)
        assert _err(logits, want[0, pos]) < TOL
    for pos in range(40, 60):
        logits = run.step([("a", tokens[0, pos], pos)], bucket=4)
        assert _err(logits[0], want[0, pos]) < TOL
    run.mgr.check_invariants()


def test_two_sequences_interleaved_chunk_by_chunk_keep_their_own_state(case):
    """(c) chunks of two prompts alternate, then both decode in one program
    of four lanes: each continues the state ITS chunk before left."""
    cfg, params, _m, tokens, want = case
    run = _Paged(cfg, params)
    prompts = [tokens[0, :37], tokens[1, :29]]
    slots = [run.admit(sid, p) for sid, p in zip("ab", prompts)]
    assert len(set(slots)) == 2 and 0 not in slots
    for start in (0, 16, 32):
        for i, sid in enumerate("ab"):
            if start < len(prompts[i]):
                pos, logits = run.chunk(sid, prompts[i], start)
                assert _err(logits, want[i, pos]) < TOL
    for k in range(12):
        lanes = [("a", tokens[0, 37 + k], 37 + k), ("b", tokens[1, 29 + k], 29 + k)]
        logits = run.step(lanes, bucket=4)
        assert _err(logits[0], want[0, 37 + k]) < TOL
        assert _err(logits[1], want[1, 29 + k]) < TOL


def test_a_recomputed_sequence_and_a_reused_slot_start_from_zero(case):
    """(d) a sequence gives its slot and blocks back mid-decode (preemption)
    and is admitted again with what it generated folded into its prompt; (e)
    a second sequence takes the slot the first one left: nothing leaks."""
    cfg, params, _m, tokens, want = case
    run = _Paged(cfg, params, slots=1)
    run.admit("a", tokens[0, :20])
    for start in (0, 16):
        run.chunk("a", tokens[0, :20], start)
    for pos in range(20, 27):
        run.step([("a", tokens[0, pos], pos)], bucket=2)
    assert not run.mgr.can_allocate(8)            # the one slot is held
    run.mgr.free("a")                             # preempted: slot and blocks go back
    slot = run.admit("a", tokens[0, :27])         # recompute: prompt + output
    for start in (0, 16):
        pos, logits = run.chunk("a", tokens[0, :27], start)
        assert _err(logits, want[0, pos]) < TOL
    logits = run.step([("a", tokens[0, 27], 27)], bucket=1)
    assert _err(logits[0], want[0, 27]) < TOL
    run.mgr.free("a")
    assert run.admit("b", tokens[1, :30]) == slot   # the same slot, another sequence
    for start in (0, 16):
        pos, logits = run.chunk("b", tokens[1, :30], start)
        assert _err(logits, want[1, pos]) < TOL
    assert (run.mgr.states_claimed, run.mgr.states_released) == (3, 2)
    run.mgr.check_invariants()


@pytest.mark.parametrize("wrong", list(WRONG))
def test_a_wrong_reference_fails_the_tolerance_a_hundredfold(case, wrong):
    cfg, params, m, tokens, want = case
    got = arch.make_logits({**m, **WRONG[wrong]})(params, tokens[0])
    assert _err(got[40:], want[0, 40:]) > 100 * TOL


# -------------------------------------------------------------- ops/ssm.py
def _scan_inputs(B, S, Di, N, seed=0):
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import ssm

    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    delta = jax.nn.softplus(jax.random.normal(k[0], (B, S, Di)) - 3.0)
    x = jax.random.normal(k[1], (B, S, Di))
    A = -jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32)[:, None], (N, Di))
    Bm, Cm = jax.random.normal(k[2], (B, S, N)), jax.random.normal(k[3], (B, S, N))
    s0 = jax.random.normal(k[4], (B, *ssm.state_shape(Di, N)))
    return delta, x, A, Bm, Cm, s0


def test_the_kernel_in_interpret_mode_is_the_plain_scan_at_the_published_width():
    """One layer's scan at 5120 x 16: a chunk of 12 tokens whose last 5 are
    masked beside a lane that is masked whole, the kernel's own tiling."""
    import jax.numpy as jnp

    from ray_tpu.ops import ssm

    B, S, Di, N = 2, 12, 5120, 16
    delta, x, A, Bm, Cm, s0 = _scan_inputs(B, S, Di, N)
    valid = jnp.arange(S)[None, :] < jnp.asarray([7, 0])[:, None]
    y0, s_plain = ssm.selective_scan(delta, x, A, Bm, Cm, s0, valid, kernel=False)
    masked = jnp.where(valid[..., None], delta, 0.0)
    tiled = (B, S, Di // 128, 128)
    y1, s_kernel = ssm._scan_pallas(
        masked.reshape(tiled), x.reshape(tiled), A.reshape(N, *tiled[2:]),
        Bm.reshape(B, 1, -1), Cm.reshape(B, 1, -1), s0, interpret=True)
    assert float(jnp.abs(y1.reshape(y0.shape)[0, :7] - y0[0, :7]).max()) < 1e-4
    assert float(jnp.abs(s_kernel - s_plain).max()) < 1e-5
    # the lane that is masked whole: its state comes back bit for bit, in both
    assert (np.asarray(s_kernel[1]) == np.asarray(s0[1])).all()
    assert (np.asarray(s_plain[1]) == np.asarray(s0[1])).all()


def test_a_masked_token_leaves_state_and_tail_bit_for_bit():
    """A chunk of 9 real tokens padded to 16 ends with the state and the tail
    of the 9 alone; a decode step is the chunk of one token."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import ssm

    B, S, Di, N, K = 2, 16, 256, 4, 4
    delta, x, A, Bm, Cm, s0 = _scan_inputs(B, S, Di, N, seed=1)
    valid = jnp.arange(S)[None, :] < jnp.asarray([9, 16])[:, None]
    _, s_pad = ssm.selective_scan(delta, x, A, Bm, Cm, s0, valid, kernel=False)
    _, s_cut = ssm.selective_scan(delta[:, :9], x[:, :9], A, Bm[:, :9], Cm[:, :9], s0,
                                  jnp.ones((B, 9), bool), kernel=False)
    assert (np.asarray(s_pad[0]) == np.asarray(s_cut[0])).all()
    u = jax.random.normal(jax.random.PRNGKey(2), (B, S, Di))
    tail = jax.random.normal(jax.random.PRNGKey(3), (B, K - 1, Di))
    w, b = jax.random.normal(jax.random.PRNGKey(4), (K, Di)), jnp.zeros((Di,))
    c_pad, t_pad = ssm.causal_conv(u, tail, w, b, valid)
    c_cut, t_cut = ssm.causal_conv(u[:, :9], tail, w, b, jnp.ones((B, 9), bool))
    assert (np.asarray(t_pad[0]) == np.asarray(u[0, 6:9])).all()
    assert (np.asarray(t_pad[0]) == np.asarray(t_cut[0])).all()
    assert (np.asarray(c_pad[0, :9]) == np.asarray(c_cut[0])).all()
    # token by token from the same tail: the chunk's own outputs
    t_step, outs = tail, []
    for t in range(9):
        c1, t_step = ssm.causal_conv(u[:, t:t + 1], t_step, w, b, jnp.ones((B, 1), bool))
        outs.append(c1[:, 0])
    assert float(jnp.abs(jnp.stack(outs, 1) - c_cut).max()) < 1e-6
    assert (np.asarray(t_step) == np.asarray(t_cut)).all()
    nobody = jnp.zeros((B, S), bool)
    assert (np.asarray(ssm.causal_conv(u, tail, w, b, nobody)[1]) == np.asarray(tail)).all()


# ------------------------------------------------- the layout and the manager
def test_the_layout_declares_rows_for_two_layers_and_a_state_for_the_rest(case):
    import jax.numpy as jnp

    from ray_tpu.models import gpt

    cfg, _params, m, _t, _w = case
    lay = gpt.kv_layout(cfg)
    assert (lay.depth, lay.per_group, lay.state_layers) == (2, 2, 2)
    assert lay.slot_of == (0, 0, 1, 1) and lay.windows == (0,)
    assert lay.block_bytes(BS, 4) == arch.kv_block_bytes(m, BS) * 2   # float32 here
    assert dict((n, s) for n, s, _ in lay.state) == {"conv": (3 * 128,), "ssm": (16, 1, 128)}
    kv = gpt.init_paged_cache(cfg, 10, BS, state_slots=5)
    assert kv["k"].shape == (2, 10, BS, 16) and kv["v"].shape == kv["k"].shape
    assert kv["state"]["ssm"].shape == (2, 6, 16, 1, 128)
    assert kv["state"]["ssm"].dtype == jnp.float32
    assert kv["state"]["conv"].shape == (2, 6, 384)
    full = gpt.kv_layout(gpt.CONFIGS["jamba2-3b"]())
    assert (full.depth, full.state_layers) == (2, 26)
    assert full.block_bytes(16, 2) == 16384 and full.state_bytes == 9_318_400
    assert "state" not in gpt.init_paged_cache(gpt.gpt2_small(), 4, 8)


def test_the_manager_counts_state_slots_beside_blocks():
    from ray_tpu.serve.engine import KVBlockManager
    from ray_tpu.serve.engine.kv_manager import KVCacheExhausted

    mgr = KVBlockManager(40, BS, state_slots=2)
    prompt = list(range(1, 33))                     # four full blocks
    mgr.allocate_cached("a", prompt, 33)
    mgr.register_computed("a", prompt, 32)
    assert mgr.stats().cached_blocks == 0 and mgr.num_registered("a") == 0   # nothing hashed
    table, cached = mgr.allocate_cached("b", prompt[:24] + [99] * 8, 33)
    assert cached == 0 and mgr.hits == 0 and not mgr._hot and not mgr._index
    assert not set(table) & set(mgr.block_table("a"))
    assert {mgr.state_slot("a"), mgr.state_slot("b")} == {1, 2}
    st = mgr.stats()
    assert (st.state_slots, st.state_slots_held) == (2, 2)
    assert mgr.free_blocks >= 5 and not mgr.can_allocate(8)
    with pytest.raises(KVCacheExhausted, match="state slot"):
        mgr.allocate("c", 8)
    with pytest.raises(NotImplementedError, match="snapshot"):
        mgr.fork("a", "c")
    assert mgr.prefix_digest() == []
    mgr.check_invariants()
    mgr.free("a")
    assert mgr.can_allocate(8) and mgr.stats().cached_blocks == 0
    mgr.allocate("c", 8)
    assert mgr.state_slot("c") in (1, 2) and mgr.state_slot("c") != mgr.state_slot("b")
    assert (mgr.states_claimed, mgr.states_released) == (3, 1)
    mgr.check_invariants()
    plain = KVBlockManager(40, BS)
    plain.allocate("a", 8)
    assert plain.stats().state_slots == 0 and plain.can_allocate(8)
    plain.check_invariants()


# ------------------------------------------------------------------ engine
def _engine(case, **opts):
    from ray_tpu.serve.engine import EngineOptions, InferenceEngine

    options = EngineOptions(**{**dict(num_blocks=40, block_size=BS, max_num_seqs=4,
                                      max_step_tokens=32, prefill_chunk_tokens=16,
                                      host_kv_bytes=0), **opts})
    return InferenceEngine(case[0], params=case[1], options=options)


def _drain(eng):
    while eng.scheduler.has_work():
        eng.step()
        eng.block_manager.check_invariants()


def _held_to_the_reference(case, prompt, out):
    cfg, params, m, _t, _w = case
    want = arch.make_logits(m)(params, np.asarray(prompt + out[:-1]))[len(prompt) - 1:]
    return bool((want.argmax(-1) == np.asarray(out)).all())


def test_engine_serves_exactly_with_the_prefix_cache_at_its_default(case):
    """(f) two prompts share three full blocks and a third repeats the first:
    no hit is taken, every token is the reference's, the hits not taken are
    counted, and slots turn over as sequences finish."""
    cfg, params, m, tokens, _want = case
    eng = _engine(case)
    assert eng.opts.enable_prefix_caching and eng._stateful
    shared = [int(t) for t in tokens[0, :24]]
    prompts = [shared + [int(t) for t in tokens[0, 24:41]],
               shared + [int(t) for t in tokens[1, :9]]]
    outs = []
    for p in prompts + prompts[:1]:          # one after the other: the index is warm
        rid = eng.submit(p, 12)
        _drain(eng)
        outs.append(list(eng.stream(rid)))
    for p, out in zip(prompts + prompts[:1], outs):
        assert _held_to_the_reference(case, p, out)
    assert outs[2] == outs[0]
    stats = eng.stats()
    assert stats["prefix_cache_hits"] == 0 and stats["kv_cached_blocks"] == 0
    assert (stats["state_slots_claimed"], stats["state_slots_released"]) == (3, 3)
    assert (stats["state_slots"], stats["state_slots_held"]) == (4, 0)
    assert 0 < stats["state_slot_held_ns"] < stats["state_slot_cap_ns"]
    # every prompt's chunks as shaped (16 + 16 + 16 of 41, 16 + 16 + 1 of 33)
    # and every decode step's bucket of one lane
    chunks = 2 * (16 + 16 + 16) + (16 + 16 + 1)
    assert stats["ssm_tokens_scanned"] == chunks + stats["steps_decode"]
    assert stats["ssm_tokens_masked"] == 2 * 7
    lay_bytes = 2 * (16 * 128 * 4 + 3 * 128 * 4)
    assert stats["ssm_state_bytes"] == 2 * stats["decode_lanes"] * lay_bytes


def test_engine_preempts_a_sequence_with_state_and_recomputes_it_exactly(case):
    """(d) through the scheduler: a pool too small for three growing
    sequences preempts the youngest, whose slot goes back with its blocks;
    readmitted, it starts from zero and every token is still the reference's."""
    cfg, params, m, tokens, _want = case
    eng = _engine(case, num_blocks=12, max_num_seqs=4)
    prompts = [[int(t) for t in tokens[i % 2, 7 * i: 7 * i + 20]] for i in range(3)]
    rids = [eng.submit(p, 24) for p in prompts]
    _drain(eng)
    outs = [list(eng.stream(r)) for r in rids]
    stats = eng.stats()
    assert stats["total_preemptions"] >= 1
    assert stats["state_slots_claimed"] == 3 + stats["total_preemptions"]
    assert stats["state_slots_released"] == stats["state_slots_claimed"]
    for p, out in zip(prompts, outs):
        assert len(out) == 24 and _held_to_the_reference(case, p, out)


@pytest.mark.parametrize("opts", [{"host_kv_bytes": 1 << 20}, {"role": "prefill"},
                                  {"role": "decode"}, {"spec_tokens": 2}])
def test_engine_refuses_at_construction_what_the_state_cannot_follow(case, opts):
    with pytest.raises(ValueError, match="state a sequence"):
        _engine(case, **opts)


def test_engine_refuses_export_and_import_of_a_model_with_state(case):
    eng = _engine(case)
    with pytest.raises(NotImplementedError, match="state a sequence"):
        eng.export_prompt_kv([1, 2, 3])
    with pytest.raises(NotImplementedError, match="state a sequence"):
        eng.import_blocks({"sig": eng._kv_sig(), "digests": ["00"]})


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
        "MPMD stage split": lambda c, p: gpt.make_mpmd_stage_fns(c, 0, 2),
        "GPipe pipeline": lambda c, p: gpt.pipeline_loss_fn(p, {"tokens": toks}, c, None, 1),
        "make_train_step": lambda c, p: gpt.make_train_step(c, optax.sgd(0.1)),
        "loss_fn": lambda c, p: gpt.loss_fn(p, {"tokens": toks}, c),
        "param_shardings": lambda c, p: gpt.param_logical_dims(c),
        "verify_step_paged": lambda c, p: gpt.verify_step_paged(
            p, toks, toks[0, :1], toks[0, :1], jnp.zeros((1, 4), jnp.int32),
            gpt.init_paged_cache(c, 4, BS, 1), c),
        "gpt2 init": lambda c, p: gpt.init_params(None, dataclasses.replace(c, init="gpt2")),
    }


@pytest.mark.parametrize("what", list(_refusals()))
def test_programs_that_cannot_take_state_space_layers_refuse_them_by_name(case, what):
    with pytest.raises(NotImplementedError, match="ssm_layout|state-space"):
        _refusals()[what](case[0], case[1])


def test_config_and_architecture_module_refuse_what_is_not_the_model():
    from ray_tpu.models import gpt

    with pytest.raises(ValueError, match="ssm_layout has 3 entries"):
        gpt.GPTConfig(n_layers=4, ssm_layout=(1, 0, 1))
    with pytest.raises(ValueError, match="ssm_layout"):
        gpt.GPTConfig(n_layers=2, ssm_layout=(1, 0), activation="swiglu", norm="rmsnorm")
    with pytest.raises(SystemExit, match="no model"):
        arch.program({**PUBLISHED, "program_model": "jamba-of-tomorrow"},
                     arch.dims(PUBLISHED, False))
    with pytest.raises(SystemExit, match="num_experts"):
        arch.dims({**PUBLISHED, "num_experts": 16}, False)
    full = gpt.CONFIGS["jamba2-3b"]()
    assert (full.n_layers, sum(full.ssm_layout), full.ssm_inner) == (28, 26, 5120)
    assert [i for i, kind in enumerate(full.ssm_layout) if not kind] == [7, 21]
    assert full.n_params == 3_029_337_472


# ----------------------------------------------------------- initialisation
def test_the_published_initialisation_and_a_state_that_matters(case):
    """`A_log` the log of 1..16, `b_dt` the inverse softplus of steps in
    0.001-0.1, `D` 1; and the state carries weight: a reference blind to the
    state before the last 16 tokens moves the logits by a sixth of their size
    or more, so a program that dropped it could not pass."""
    import jax

    cfg, params, m, tokens, want = case
    a = np.asarray(params["ssm_A_log"])
    assert a.shape == (2, 16, 128)
    assert np.allclose(np.exp(a[0, :, 5]), np.arange(1, 17), rtol=1e-6)
    steps = np.asarray(jax.nn.softplus(params["ssm_b_dt"]))
    assert 0.001 <= steps.min() < 0.002 and 0.05 < steps.max() <= 0.1 + 1e-6
    assert (np.asarray(params["ssm_D"]) == 1).all()
    # a tied head: the final norm's gain alternates, so that the input token's
    # own embedding does not decide the next token (`_init_unit_stream`)
    assert (np.asarray(params["ln_f_w"]) == np.where(np.arange(64) % 2, -1, 1)).all()
    assert not set(params) & {"ln_f_b", "b_o", "ln1_b", "ln2_b", "b_in", "b_out", "lm_head"}
    blind = arch.make_logits({**m, "state_reset_every": 16})(params, tokens[0])
    assert _err(blind[40:], want[0, 40:]) > 0.15
