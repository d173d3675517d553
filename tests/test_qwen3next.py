"""Qwen3-Next's layer stack (gated delta-net layers, every fourth layer gated
attention, softmax-routed experts beside a gated shared expert in every layer,
zero-centred norms) through the program's normal paths, on the CPU at a small size
(8 layers = two periods; delta chunks of 16) with seeded random weights, each
against the plain reference of `benchmarks/arch/qwen3next.py`: the new mathematics
of `ops/delta.py` piece by piece (the chunked form against the token recurrence,
decays near 0 and near 1, a masked token); `forward`; chunked paged prefill then
paged decode through the block manager's tables AND state slots (logits, not
tokens) with prefill chunks of 24 over delta chunks of 16 (a boundary of either
inside the other), a padded last chunk, padding lanes, two lanes of unequal
length, a sequence given up and recomputed, a released slot taken by another; the
engine itself with a preemption and its books; the share's ties to the model; what
the layout declares; what refuses the model; the wrong references that must fail."""

import dataclasses

import numpy as np
import pytest

from benchmarks.arch import qwen3next as arch
from benchmarks.arch import qwen3next_reference as reference

BS = 8
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 32,
    "hidden_act": "silu", "hidden_size": 64, "linear_conv_kernel_dim": 4,
    "linear_key_head_dim": 16, "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_value_head_dim": 16, "mlp_only_layers": [], "moe_intermediate_size": 32,
    "norm_topk_prob": True, "num_attention_heads": 4, "num_experts": 4,
    "num_experts_per_tok": 3, "num_hidden_layers": 8, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-6, "rope_scaling": None,
    "rope_theta": 10000000, "shared_expert_intermediate_size": 32,
    "tie_word_embeddings": False, "use_sliding_window": False, "vocab_size": 300,
    "program_model": "qwen3-next-80b-a3b",
    "deployment": {"router_experts": 8, "held_experts_start": 2, "served_positions": 256,
                   "delta_chunk": 16},
}
# float32 program against the float32 reference: the largest difference of two
# logits over the largest logit in size; both sum the same terms in float32 in
# another order (the chunked delta rule's products against the recurrence).
TOL = 2e-5
WRONG = {
    "no_delta_term": {"no_delta": True},
    "no_decay": {"no_decay": True},
    "beta_fixed_at_one": {"beta_one": True},
    "q_and_k_not_normalised": {"no_qk_norm": True},
    "gate_before_the_norm": {"gate_before_norm": True},
    "one_scalar_gate_a_head": {"head_gate_scalar": True},
    "plain_rmsnorm_gain": {"plain_norm": True},
    "rotary_over_the_whole_head": {"rotary_whole": True},
    "top_k_minus_one": {"top_k_wrong": 2},
    "shared_expert_ungated": {"shared_ungated": True},
    "state_in_bfloat16": {"state_bf16": True},
    "state_zeroed_at_chunk_edges": {"state_reset_every": 24},
    "tail_zeroed_at_chunk_edges": {"tail_reset_every": 24},
}


def _cfg(dtype="float32", **deployment):
    import jax.numpy as jnp

    from ray_tpu.models import gpt

    published = {**PUBLISHED, "deployment": {**PUBLISHED["deployment"], **deployment}}
    m = arch.dims(published, False)
    name, overrides = arch.program(published, m)
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
    assert np.abs(want).max() > 2.0
    return cfg, params, m, tokens, want


def _err(got, want):
    return float(np.abs(np.asarray(got) - want).max() / np.abs(want).max())


# ------------------------------------------------------------- ops/delta.py
def _rule_inputs(B, S, G=2, H=4, K=16, V=8, decay=1.0, seed=0):
    import jax

    from ray_tpu.ops import delta

    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (delta.l2norm(jax.random.normal(k[0], (B, S, G, K))) * K ** -0.5,
            delta.l2norm(jax.random.normal(k[1], (B, S, G, K))),
            jax.random.normal(k[2], (B, S, H, V)),
            -decay * jax.random.uniform(k[3], (B, S, H)),
            jax.nn.sigmoid(2 * jax.random.normal(k[4], (B, S, H))),
            jax.random.normal(k[5], (B, H, K, V)))


@pytest.mark.parametrize("S", [2, 15, 16, 17, 63, 64, 65, 150])
@pytest.mark.parametrize("decay", [1e-3, 1.0, 30.0])
def test_the_chunked_form_is_the_token_recurrence(S, decay):
    """Outputs AND state, at lengths around the chunk, the log decay a token near 0
    (a state that forgets nothing), of order 1, and so large that a token wipes it."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import delta

    *inputs, s0 = _rule_inputs(2, S, decay=decay, seed=S)
    valid = jnp.ones((2, S), bool)
    with jax.default_matmul_precision("highest"):
        want, s_want = delta.delta_scan(*inputs, s0, valid, form="plain")
        got, s_got = delta.delta_scan(*inputs, s0, valid, chunk=16, dtype=jnp.float32)
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) < 1e-5 * max(scale, 1.0)
    assert float(jnp.abs(s_got - s_want).max()) < 1e-5


def test_a_decode_step_continues_the_state_a_chunk_left():
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import delta

    *inputs, s0 = _rule_inputs(2, 41)
    everyone = jnp.ones((2, 41), bool)
    with jax.default_matmul_precision("highest"):
        want, s_want = delta.delta_scan(*inputs, s0, everyone, form="plain")
        _, s = delta.delta_scan(*(a[:, :40] for a in inputs), s0, everyone[:, :40],
                                chunk=16, dtype=jnp.float32)
        got, s_got = delta.delta_scan(*(a[:, 40:] for a in inputs), s, everyone[:, 40:])
    assert float(jnp.abs(got - want[:, 40:]).max()) < 1e-5
    assert float(jnp.abs(s_got - s_want).max()) < 1e-5


@pytest.mark.parametrize("form", ["plain", None])
def test_a_masked_token_leaves_state_and_tail_bit_for_bit(form):
    """Lane 0 has 21 real tokens of 40, lane 1 none: the mixer's state and tail are
    those of the real tokens alone, a lane of padding's what it brought."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import delta

    keys = iter(jax.random.split(jax.random.PRNGKey(5), 12))
    n = lambda *shape: jax.random.normal(next(keys), shape) * 0.5
    E, G, K, H, V, taps = 24, 2, 16, 4, 8, 4
    wide = 2 * G * K + H * V
    p = {"w_qkvz": n(E, wide + H * V), "w_ba": n(E, 2 * H), "conv_w": n(taps, wide),
         "dt_bias": n(H), "A_log": n(H), "norm_w": 1 + n(V), "w_out": n(H * V, E)}
    h, tail, s = n(2, 40, E), n(2, taps - 1, wide), n(2, H, K, V)
    valid = jnp.arange(40)[None, :] < jnp.asarray([21, 0])[:, None]
    run = lambda h, valid: delta.gated_delta_mixer(
        p, h, tail, s, valid, key_heads=G, chunk=16, form=form)
    with jax.default_matmul_precision("highest"):
        out, tail_new, s_new = run(h, valid)
        want, tail_want, s_want = run(h[:, :21], jnp.ones((2, 21), bool))
    assert float(jnp.abs(out[0, :21] - want[0]).max()) < 1e-5
    assert float(jnp.abs(s_new[0] - s_want[0]).max()) < 1e-5
    assert (tail_new[0] == tail_want[0]).all()
    assert (s_new[1] == s[1]).all() and (tail_new[1] == tail[1]).all()


def test_the_gate_follows_the_norm_and_the_gain_is_plain():
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import delta

    o, z = (jax.random.normal(jax.random.PRNGKey(i), (3, 4, 8)) for i in (0, 1))
    w = 1 + 0.1 * jax.random.normal(jax.random.PRNGKey(2), (8,))
    got = delta.gated_rmsnorm(o, z, w, 1e-6)
    rms = lambda a: a / np.sqrt((np.asarray(a) ** 2).mean(-1, keepdims=True) + 1e-6)
    want = rms(o) * np.asarray(w) * np.asarray(jax.nn.silu(z))
    assert float(np.abs(got - want).max()) < 1e-5
    assert float(np.abs(got - rms(o * jax.nn.silu(z)) * np.asarray(w)).max()) > 1e-2


# ------------------------------------------------------------------ forward
def test_forward_matches_the_reference(case):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.gpt import forward

    cfg, params, _m, tokens, want = case
    with jax.default_matmul_precision("highest"):
        got = forward(params, jnp.asarray(tokens), cfg)
    assert _err(got, want) < TOL


# ------------------------------------------------- the paged programs, logits
class _Paged:
    """The two paged programs over the tables and state slots a
    `KVBlockManager` gives, the pool donated from call to call as the engine
    donates it. Before every call the null block's rows and the null slot's
    state are set to a large value: no real lane may read either."""

    CHUNK, WIDTH = 24, 16

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
        import jax
        import jax.numpy as jnp

        n = min(self.CHUNK, len(prompt) - start)
        padded = np.zeros((1, self.CHUNK), np.int32)
        padded[0, :n] = prompt[start:start + n]
        with jax.default_matmul_precision("highest"):
            logits, self.kv = self.prefill(
                self.params, jnp.asarray(padded), jnp.int32(n), jnp.int32(start),
                jnp.asarray(self.table(sid)), self._poisoned(), self.cfg,
                jnp.int32(self.mgr.state_slot(sid)))
        return start + n - 1, np.asarray(logits)

    def step(self, lanes, bucket):
        """One decode step of `lanes` [(sid, token, position)] in a program
        of `bucket` lanes: the rest are padding (null table, null slot)."""
        import jax
        import jax.numpy as jnp

        tok, pos = np.zeros((bucket,), np.int32), np.zeros((bucket,), np.int32)
        tabs, slots = np.zeros((bucket, self.WIDTH), np.int32), np.zeros((bucket,), np.int32)
        for i, (sid, t, p) in enumerate(lanes):
            self.mgr.grow(sid, p + 1)
            tok[i], pos[i], tabs[i], slots[i] = t, p, self.table(sid), self.mgr.state_slot(sid)
        with jax.default_matmul_precision("highest"):
            (logits, _load), self.kv = self.decode(
                self.params, jnp.asarray(tok), jnp.asarray(pos), jnp.asarray(tabs),
                self._poisoned(), self.cfg, jnp.asarray(slots))
        return np.asarray(logits)[: len(lanes)]


def test_chunked_prefill_then_decode_matches_the_reference_with_padding(case):
    """A prompt of 61 = 24 + 24 + 13: each engine chunk is one and a half delta
    chunks (the rule's boundary at 16 inside a program, the engine's at 24 and 48
    inside a delta chunk's span), the last chunk half padding; then one real lane
    in a decode program of four."""
    cfg, params, _m, tokens, want = case
    run = _Paged(cfg, params)
    prompt = tokens[0, :61]
    run.admit("a", prompt)
    for start in (0, 24, 48):
        pos, logits = run.chunk("a", prompt, start)
        assert _err(logits, want[0, pos]) < TOL
    for pos in range(61, 75):
        logits = run.step([("a", tokens[0, pos], pos)], bucket=4)
        assert _err(logits[0], want[0, pos]) < TOL
    run.mgr.check_invariants()


def test_lanes_join_and_leave_and_each_keeps_its_own_state(case):
    """Chunks of two prompts alternate; both decode in one program of four
    lanes; one leaves, a third joins in the slot it left and starts from zero."""
    cfg, params, _m, tokens, want = case
    run = _Paged(cfg, params, slots=2)
    prompts = [tokens[0, :53], tokens[1, :29]]
    slots = [run.admit(sid, p) for sid, p in zip("ab", prompts)]
    assert len(set(slots)) == 2 and 0 not in slots
    for start in (0, 24, 48):
        for i, sid in enumerate("ab"):
            if start < len(prompts[i]):
                pos, logits = run.chunk(sid, prompts[i], start)
                assert _err(logits, want[i, pos]) < TOL
    for k in range(6):
        logits = run.step([("a", tokens[0, 53 + k], 53 + k), ("b", tokens[1, 29 + k], 29 + k)], 4)
        assert _err(logits[0], want[0, 53 + k]) < TOL and _err(logits[1], want[1, 29 + k]) < TOL
    run.mgr.free("a")
    assert run.admit("c", tokens[0, :30]) == slots[0]      # the slot `a` left
    for start in (0, 24):
        pos, logits = run.chunk("c", tokens[0, :30], start)
        assert _err(logits, want[0, pos]) < TOL
    logits = run.step([("b", tokens[1, 35], 35), ("c", tokens[0, 30], 30)], 2)
    assert _err(logits[0], want[1, 35]) < TOL and _err(logits[1], want[0, 30]) < TOL
    run.mgr.check_invariants()


def test_a_recomputed_sequence_and_a_reused_slot_start_from_zero(case):
    cfg, params, _m, tokens, want = case
    run = _Paged(cfg, params, slots=1)
    run.admit("a", tokens[0, :30])
    for start in (0, 24):
        run.chunk("a", tokens[0, :30], start)
    for pos in range(30, 37):
        run.step([("a", tokens[0, pos], pos)], bucket=2)
    run.mgr.free("a")                             # preempted: slot and blocks go back
    slot = run.admit("a", tokens[0, :37])         # recompute: prompt + output
    for start in (0, 24):
        pos, logits = run.chunk("a", tokens[0, :37], start)
        assert _err(logits, want[0, pos]) < TOL
    assert _err(run.step([("a", tokens[0, 37], 37)], bucket=1)[0], want[0, 37]) < TOL
    run.mgr.free("a")
    assert run.admit("b", tokens[1, :30]) == slot   # the same slot, another sequence
    for start in (0, 24):
        pos, logits = run.chunk("b", tokens[1, :30], start)
        assert _err(logits, want[1, pos]) < TOL
    run.mgr.check_invariants()


@pytest.mark.parametrize("wrong", list(WRONG))
def test_a_wrong_reference_fails_the_same_tolerance(case, wrong):
    cfg, params, m, tokens, want = case
    off = arch.make_logits({**m, **WRONG[wrong]})(params, tokens[0])
    assert _err(off, want[0]) > 10 * TOL, wrong


# ---------------------------------------------------------------- the engine
def _engine(case, **opts):
    from ray_tpu.serve.engine import EngineOptions, InferenceEngine

    options = EngineOptions(**{**dict(num_blocks=40, block_size=BS, max_num_seqs=4,
                                      max_step_tokens=32, prefill_chunk_tokens=24,
                                      host_kv_bytes=0), **opts})
    return InferenceEngine(case[0], params=case[1], options=options)


def _drain(eng):
    while eng.scheduler.has_work():
        eng.step()
        eng.block_manager.check_invariants()


def _held_to_the_reference(case, prompt, out):
    _cfg, params, m, _t, _w = case
    want = arch.make_logits(m)(params, np.asarray(prompt + out[:-1]))[len(prompt) - 1:]
    return bool((want.argmax(-1) == np.asarray(out)).all())


def test_engine_serves_the_references_tokens_and_counts_what_it_ran(case):
    cfg, params, m, tokens, _want = case
    eng = _engine(case)
    assert eng._stateful and eng.cfg.moe_layers == 8
    prompts = [[int(t) for t in tokens[0, :41]], [int(t) for t in tokens[1, :33]]]
    rids = [eng.submit(p, 12) for p in prompts]
    _drain(eng)
    for p, rid in zip(prompts, rids):
        assert _held_to_the_reference(case, p, list(eng.stream(rid)))
    stats = eng.stats()
    assert (stats["state_slots_claimed"], stats["state_slots_released"]) == (2, 2)
    assert stats["ssm_tokens_scanned"] > stats["ssm_tokens_masked"] > 0
    # the float32 tree of this test keeps the convolution's tail in float32 too
    assert eng._layout.state_bytes == 6 * (4 * 16 * 16 * 4 + 3 * 128 * 4)
    assert stats["ssm_state_bytes"] == 2 * stats["decode_lanes"] * eng._layout.state_bytes
    assert 0 < stats["moe_assign_held"] < stats["moe_assign_total"]    # 4 of 8 held
    # two of the eight layers keep rows: heads x keys over them alone
    assert eng._attn_heads == ((0, 2 * 4),) and eng._layout.depth == 2


def test_engine_preempts_and_recomputes_exactly(case):
    cfg, params, m, tokens, _want = case
    eng = _engine(case, num_blocks=14)
    prompts = [[int(t) for t in tokens[i % 2, 7 * i: 7 * i + 20]] for i in range(3)]
    rids = [eng.submit(p, 30) for p in prompts]
    _drain(eng)
    outs = [list(eng.stream(r)) for r in rids]
    stats = eng.stats()
    assert stats["total_preemptions"] >= 1
    assert stats["state_slots_claimed"] == 3 + stats["total_preemptions"]
    for p, out in zip(prompts, outs):
        assert len(out) == 30 and _held_to_the_reference(case, p, out)


@pytest.mark.parametrize("opts", [{"host_kv_bytes": 1 << 20}, {"role": "prefill"},
                                  {"role": "decode"}, {"spec_tokens": 2}])
def test_engine_refuses_at_construction_what_the_state_cannot_follow(case, opts):
    with pytest.raises(ValueError, match="state a sequence"):
        _engine(case, **opts)


# ------------------------------------------------------------ the share's ties
def test_four_held_ranges_and_the_shared_expert_once_add_up_to_the_uncut_layer(case):
    """The guide's share test, on the program's own MLP: the four chips holding
    experts 0-1, 2-3, 4-5 and 6-7, each WITHOUT the shared expert, plus the gated
    shared expert counted once, against the reference's layer with all 8 held."""
    import jax

    from ray_tpu.models import gpt

    cfg, m = _cfg()
    whole_m = {**m, "held_start": 0, "held_count": 8}
    whole = dataclasses.replace(cfg, moe_held=(0, 8))
    params = gpt.init_params(jax.random.PRNGKey(7), whole)
    h = jax.random.normal(jax.random.PRNGKey(8), (1, 24, 64))
    layer = 5
    p = {k: params[k][layer] for k in ("moe_router", "shared_w_gate", "shared_w_in",
                                       "shared_w_out", "shared_gate")}
    stacks = {k: params[k] for k in ("moe_w_gate", "moe_w_in", "moe_w_out")}
    with jax.default_matmul_precision("highest"):
        want = reference.experts(h[0], {**p, **stacks}, layer, whole_m)
        shared = want - reference.experts(h[0], {**p, **stacks}, layer, {**whole_m, "shared": False})
        parts = []
        for first in (0, 2, 4, 6):
            part = dataclasses.replace(cfg, moe_held=(first, 2))
            y, load = gpt._dropless_mlp(
                part, params["moe_router"][layer],
                tuple(params[k][:, first:first + 2] for k in ("moe_w_gate", "moe_w_in", "moe_w_out")),
                h, h, layer=layer)
            parts.append(y[0])
            assert float(load[3]) == 24 * 3             # every part sees all the assignments
        held = gpt._gdn_mlp(whole, params, layer, h, None)[0][0]
    assert _err(sum(parts) + shared, np.asarray(want)) < 1e-5
    assert _err(held, np.asarray(want)) < 1e-5          # the program's own layer, uncut
    assert _err(parts[0] + shared, np.asarray(want)) > 1e-2     # a part alone is not the layer


def test_the_sliced_heads_logits_are_the_whole_heads_rows(case):
    """A quarter of the vocabulary: the slice's logits are the whole head's at the
    same ids, and the embedding's rows the whole table's."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt

    cfg, params, m, tokens, want = case
    V = m["vocab_size"]
    cut = dataclasses.replace(cfg, vocab_size=V // 4)
    sliced = {**params, "tok_embed": params["tok_embed"][: V // 4],
              "lm_head": params["lm_head"][:, : V // 4]}
    ids = jnp.asarray(tokens[:1] % (V // 4))
    with jax.default_matmul_precision("highest"):
        whole = gpt.forward(params, ids, cfg)
        got = gpt.forward(sliced, ids, cut)
    assert got.shape[-1] == V // 4
    assert float(jnp.abs(got - whole[..., : V // 4]).max()) < 1e-5


# ------------------------------------------------------ layout and refusals
def test_the_layout_declares_rows_for_two_layers_and_a_state_for_six(case):
    from ray_tpu.models import gpt

    lay = gpt.kv_layout(case[0])
    assert (lay.depth, lay.state_layers, lay.key_row, lay.value_row) == (2, 6, 64, 64)
    assert lay.slot_of == (0, 1, 2, 0, 3, 4, 5, 1)
    assert dict((n, s) for n, s, _ in lay.state) == {"conv": (3 * 128,), "gdn": (4, 16, 16)}
    assert gpt.attn_heads_by_window(case[0]) == ((0, 8),)
    assert case[0].n_params == sum(a.size for a in case[1].values())


def _refusals():
    import jax.numpy as jnp
    import optax

    from ray_tpu.models import gpt

    toks = jnp.zeros((1, 4), jnp.int32)
    return {
        "dense-cache prefill": lambda c, p: gpt.prefill(p, toks, c, None),
        "dense-cache decode_step": lambda c, p: gpt.decode_step(p, toks[0], None, c),
        "pipeline stage": lambda c, p: gpt.stage_forward(p, toks, c, first=True, last=True),
        "make_train_step": lambda c, p: gpt.make_train_step(c, optax.sgd(0.1)),
        "loss_fn": lambda c, p: gpt.loss_fn(p, {"tokens": toks}, c),
        "param_shardings": lambda c, p: gpt.param_logical_dims(c),
        "verify_step_paged": lambda c, p: gpt.verify_step_paged(
            p, toks, toks[0, :1], toks[0, :1], jnp.zeros((1, 4), jnp.int32),
            gpt.init_paged_cache(c, 4, BS, 1), c),
    }


@pytest.mark.parametrize("what", list(_refusals()))
def test_programs_that_cannot_take_delta_layers_refuse_them_by_name(case, what):
    with pytest.raises(NotImplementedError, match="gdn_interval|moe_shared"):
        _refusals()[what](case[0], case[1])


@pytest.mark.parametrize("change", [
    {"gdn_interval": 3}, {"gdn_interval": 1}, {"activation": "reglu"}, {"pos": "none"},
    {"tie_embeddings": True}, {"moe_scoring": "sigmoid"}, {"moe_shared": 0},
    {"ssm_groups": 3}, {"init": "gpt2"}, {"block_pattern": "M" * 8}])
def test_the_config_refuses_what_is_not_the_model(case, change):
    with pytest.raises(ValueError, match="gdn_interval|block_pattern"):
        dataclasses.replace(case[0], **change)


def test_the_architecture_module_refuses_what_it_was_not_written_for():
    for change in ({"hidden_act": "gelu"}, {"norm_topk_prob": False},
                   {"tie_word_embeddings": True}, {"decoder_sparse_step": 2},
                   {"mlp_only_layers": [0]}, {"shared_expert_intermediate_size": 48},
                   {"num_hidden_layers": 6}, {"linear_num_value_heads": 3}):
        with pytest.raises(SystemExit, match="qwen3next"):
            arch.dims({**PUBLISHED, **change}, False)
    with pytest.raises(SystemExit, match="no model"):
        arch.program({**PUBLISHED, "program_model": "qwen4"}, arch.dims(PUBLISHED, False))
