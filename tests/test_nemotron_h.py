"""NVIDIA-Nemotron-3-Nano's block stack (blocks of ONE mixer: Mamba-2, squared-
ReLU experts of two matrices under a selection bias with a held range, grouped-
query attention without a positional term) through the program's normal paths,
on the CPU at a small size (the pattern MEM*E: every kind, two Mamba-2 blocks)
with seeded random weights, each against the plain reference of
`benchmarks/arch/nemotron_h.py`: the chunked scan against the token-by-token
recurrence around the chunk's length; a decode step after a prefill; padding bit
for bit; `forward`; chunked paged prefill then paged decode through tables and
state slots (logits) with a padded last chunk, padding lanes and lanes joining and
leaving; the engine itself; the two halves' share test; the two-matrix grouped
path against the dense form and a plain loop, its kernel in interpret mode; the
selection bias; what refuses the model; wrong references that must fail."""

import dataclasses

import numpy as np
import pytest

from benchmarks.arch import nemotron_h as arch
from benchmarks.arch import nemotron_h_reference as reference

BS = 8
PUBLISHED = {
    "hybrid_override_pattern": "MEM*EMEM*", "num_hidden_layers": 5, "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "mamba_num_heads": 8, "mamba_head_dim": 16, "expand": 2, "n_groups": 2,
    "ssm_state_size": 32, "conv_kernel": 4, "chunk_size": 16,
    "moe_intermediate_size": 32, "moe_shared_expert_intermediate_size": 64,
    "n_routed_experts": 4, "n_shared_experts": 1, "num_experts_per_tok": 3,
    "routed_scaling_factor": 2.5, "norm_topk_prob": True, "n_group": 1, "topk_group": 1,
    "mlp_hidden_act": "relu2", "mamba_hidden_act": "silu", "tie_word_embeddings": False,
    "use_conv_bias": True, "attention_bias": False, "mlp_bias": False, "use_bias": False,
    "mamba_proj_bias": False, "sliding_window": None, "vocab_size": 300, "norm_eps": 1e-5,
    "program_model": "nemotron3-nano-30b-a3b",
    "deployment": {"router_experts": 8, "held_experts_start": 0, "served_positions": 256},
}
# float32 program against the float32 reference: the largest difference of two
# logits over the largest logit in size; both sum the same terms in float32 in
# another order (the chunked scan's products against the recurrence).
TOL = 3e-5
WRONG = {
    "state_zeroed_at_chunk_edges": {"state_reset_every": 16},
    "tail_zeroed_at_chunk_edges": {"tail_reset_every": 16},
    "state_in_bfloat16": {"state_bf16": True},
    "no_skip_term": {"no_skip": True},
    "gate_after_norm": {"gate_after_norm": True},
    "one_norm_group": {"norm_groups": 1},
    "relu_for_relu2": {"expert_act": "relu"},
    "no_selection_bias": {"no_select_bias": True},
    "top_k_minus_one": {"top_k_wrong": 2},
    "rotary_put_in": {"rotary": 10000.0},
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


# ------------------------------------------------------------------ the scan
def _scan_inputs(B, S, H=8, P=16, G=2, N=32, seed=0):
    import jax
    import jax.numpy as jnp

    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(k[0], (B, S, H, P)),
            jax.nn.softplus(jax.random.normal(k[1], (B, S, H))),
            -jnp.exp(jax.random.normal(k[2], (H,)) * 0.5),
            jax.random.normal(k[3], (B, S, G, N)), jax.random.normal(k[4], (B, S, G, N)),
            jax.random.normal(k[5], (B, H, P, N)))


@pytest.mark.parametrize("S", [1, 2, 127, 128, 129, 255, 256, 257, 300])
def test_the_chunked_form_is_the_recurrence_at_lengths_around_the_chunk(S):
    import jax.numpy as jnp

    from ray_tpu.ops import ssm

    x, dt, A, Bm, Cm, s0 = _scan_inputs(2, S)
    valid = jnp.arange(S)[None, :] < jnp.asarray([[S], [max(1, S - 43)]])
    want_y, want_s = ssm.ssd_scan(x, dt, A, Bm, Cm, s0, valid, form="plain")
    got_y, got_s = ssm.ssd_scan(x, dt, A, Bm, Cm, s0, valid, 128, dtype=jnp.float32)
    assert _err(got_y, np.asarray(want_y)) < 2e-5 and _err(got_s, np.asarray(want_s)) < 2e-5
    # the operands rounded as the served program rounds them: a bfloat16's worth
    low_y, low_s = ssm.ssd_scan(x, dt, A, Bm, Cm, s0, valid, 128)
    assert _err(low_y, np.asarray(want_y)) < 2e-2 and _err(low_s, np.asarray(want_s)) < 2e-2


def test_a_decode_step_continues_the_state_a_prefill_left():
    import jax.numpy as jnp

    from ray_tpu.ops import ssm

    x, dt, A, Bm, Cm, s0 = _scan_inputs(2, 70)
    everyone = jnp.ones((2, 70), bool)
    want_y, want_s = ssm.ssd_scan(x, dt, A, Bm, Cm, s0, everyone, form="plain")
    _, s = ssm.ssd_scan(x[:, :64], dt[:, :64], A, Bm[:, :64], Cm[:, :64], s0,
                        everyone[:, :64], 16, dtype=jnp.float32)
    for t in range(64, 70):
        y, s = ssm.ssd_scan(x[:, t:t + 1], dt[:, t:t + 1], A, Bm[:, t:t + 1], Cm[:, t:t + 1],
                            s, everyone[:, :1])
        assert _err(y[:, 0], np.asarray(want_y[:, t])) < 2e-5
    assert _err(s, np.asarray(want_s)) < 2e-5


@pytest.mark.parametrize("form", ["plain", None])
def test_a_masked_token_leaves_state_and_tail_bit_for_bit(form):
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import ssm

    cfg, _m = _cfg()
    from ray_tpu.models import gpt

    params = gpt.init_params(jax.random.PRNGKey(5), cfg)
    p = {name: params["m2_" + name][0] for name in gpt._M2_KEYS}
    k = jax.random.split(jax.random.PRNGKey(1), 3)
    h = jax.random.normal(k[0], (2, 24, 64))
    tail = jax.random.normal(k[1], (2, 3, 128 + 2 * 2 * 32))
    s = jax.random.normal(k[2], (2, 8, 16, 32))
    real = jnp.arange(24)[None, :] < jnp.asarray([[10], [0]])
    _, tail_a, s_a = ssm.mamba2_mixer(p, h, tail, s, real, groups=2, chunk=16, form=form)
    _, tail_b, s_b = ssm.mamba2_mixer(p, h[:, :10], tail, s, real[:, :10], groups=2,
                                      chunk=16, form=form)
    # lane 1 is all padding: nothing moved at all; lane 0: 14 of padding change nothing
    assert (np.asarray(tail_a[1]) == np.asarray(tail[1])).all()
    assert (np.asarray(s_a[1]) == np.asarray(s[1])).all()
    assert (np.asarray(tail_a[0]) == np.asarray(tail_b[0])).all()
    assert _err(s_a[0], np.asarray(s_b[0])) < 1e-6


def test_the_gated_norm_takes_its_mean_square_a_group():
    import jax

    from ray_tpu.ops import ssm

    k = jax.random.split(jax.random.PRNGKey(2), 3)
    y, z, w = (jax.random.normal(k[0], (3, 64)), jax.random.normal(k[1], (3, 64)),
               jax.random.normal(k[2], (64,)))
    g = np.asarray(y * jax.nn.silu(z), np.float64).reshape(3, 4, 16)
    want = (g / np.sqrt((g * g).mean(-1, keepdims=True) + 1e-5)).reshape(3, 64) * np.asarray(w)
    assert _err(ssm.grouped_gated_rmsnorm(y, z, w, 4, 1e-5), want) < 1e-5
    assert _err(ssm.grouped_gated_rmsnorm(y, z, w, 1, 1e-5), want) > 1e-2


# ------------------------------------------------------------------- forward
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
        (logits, _load), self.kv = self.decode(
            self.params, jnp.asarray(tok), jnp.asarray(pos), jnp.asarray(tabs),
            self._poisoned(), self.cfg, jnp.asarray(slots))
        return np.asarray(logits)[: len(lanes)]


def test_chunked_prefill_then_decode_matches_the_reference_with_padding(case):
    """A prompt of 40 = 16 + 16 + 8: the engine's chunk is the scan's chunk here,
    so every chunk boundary is both; the last chunk is half padding; then one
    real lane in a decode program of four."""
    cfg, params, _m, tokens, want = case
    run = _Paged(cfg, params)
    prompt = tokens[0, :40]
    run.admit("a", prompt)
    for start in (0, 16, 32):
        pos, logits = run.chunk("a", prompt, start)
        assert _err(logits, want[0, pos]) < TOL
    for pos in range(40, 56):
        logits = run.step([("a", tokens[0, pos], pos)], bucket=4)
        assert _err(logits[0], want[0, pos]) < TOL
    run.mgr.check_invariants()


def test_lanes_join_and_leave_and_each_keeps_its_own_state(case):
    """Chunks of two prompts alternate; both decode in one program of four
    lanes; one leaves, a third joins in the slot it left and starts from zero."""
    cfg, params, _m, tokens, want = case
    run = _Paged(cfg, params, slots=2)
    prompts = [tokens[0, :37], tokens[1, :29]]
    slots = [run.admit(sid, p) for sid, p in zip("ab", prompts)]
    assert len(set(slots)) == 2 and 0 not in slots
    for start in (0, 16, 32):
        for i, sid in enumerate("ab"):
            if start < len(prompts[i]):
                pos, logits = run.chunk(sid, prompts[i], start)
                assert _err(logits, want[i, pos]) < TOL
    for k in range(6):
        logits = run.step([("a", tokens[0, 37 + k], 37 + k), ("b", tokens[1, 29 + k], 29 + k)], 4)
        assert _err(logits[0], want[0, 37 + k]) < TOL and _err(logits[1], want[1, 29 + k]) < TOL
    run.mgr.free("a")
    assert run.admit("c", tokens[0, :20]) == slots[0]      # the slot `a` left
    for start in (0, 16):
        pos, logits = run.chunk("c", tokens[0, :20], start)
        assert _err(logits, want[0, pos]) < TOL
    logits = run.step([("b", tokens[1, 35], 35), ("c", tokens[0, 20], 20)], 2)
    assert _err(logits[0], want[1, 35]) < TOL and _err(logits[1], want[0, 20]) < TOL
    run.mgr.check_invariants()


@pytest.mark.parametrize("wrong", list(WRONG))
def test_a_wrong_reference_fails_the_tolerance_tenfold(case, wrong):
    cfg, params, m, tokens, want = case
    off = arch.make_logits({**m, **WRONG[wrong]})(params, tokens[0])
    assert _err(off, want[0]) > 10 * TOL, wrong


# ---------------------------------------------------------------- the engine
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


def test_engine_serves_the_references_tokens_and_counts_what_it_ran(case):
    cfg, params, m, tokens, _want = case
    eng = _engine(case)
    assert eng._stateful and eng.cfg.moe_layers == 2
    prompts = [[int(t) for t in tokens[0, :41]], [int(t) for t in tokens[1, :33]]]
    rids = [eng.submit(p, 12) for p in prompts]
    _drain(eng)
    logits = arch.make_logits(m)
    for p, rid in zip(prompts, rids):
        out = list(eng.stream(rid))
        want = logits(params, np.asarray(p + out[:-1]))[len(p) - 1:]
        assert (want.argmax(-1) == np.asarray(out)).all()
    stats = eng.stats()
    assert (stats["state_slots_claimed"], stats["state_slots_released"]) == (2, 2)
    programs = stats["blocks_run"] // 5
    assert (stats["blocks_ssm"], stats["blocks_moe"], stats["blocks_attn"]) == \
        (2 * programs, 2 * programs, programs)
    # 41 = 16 + 16 + 9 in a program of 16; 33 = 16 + 16 + 1 in a program of 1
    assert stats["ssm_tokens_masked"] == (48 - 41) + \
        stats["decode_bucket_lanes"] - stats["decode_lanes"]
    # the float32 tree of this test keeps the convolution's tail in float32 too
    assert eng._layout.state_bytes == 2 * (8 * 16 * 32 * 4 + 3 * 256 * 4)
    assert stats["ssm_state_bytes"] == 2 * stats["decode_lanes"] * eng._layout.state_bytes
    assert 0 < stats["moe_assign_held"] < stats["moe_assign_total"]    # 4 of 8 held


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


# ------------------------------------------------------------- the share test
def test_both_halves_routed_parts_and_the_shared_expert_once_add_up_to_the_uncut_block(case):
    """The guide's share test, on the program's own expert block: the chip
    holding experts 0-3 and the chip holding 4-7, each WITHOUT the shared
    expert, plus the shared expert counted once, against the reference's block
    with all 8 experts held."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt

    cfg, _m = _cfg(held_experts_start=0)
    _, m = _cfg()
    whole_m = {**m, "held_start": 0, "held_count": 8}
    whole = dataclasses.replace(cfg, moe_held=(0, 8))
    params = gpt.init_params(jax.random.PRNGKey(7), whole)
    h = jax.random.normal(jax.random.PRNGKey(8), (1, 24, 64))
    own = {k: params[k][1] for k in ("moe_ln_w", "moe_router", "moe_select_bias",
                                     "shared_w_in", "shared_w_out")}
    p = {**own, "moe_w_in": params["moe_w_in"], "moe_w_out": params["moe_w_out"]}
    with jax.default_matmul_precision("highest"):
        want = reference.experts(h[0], p, 1, whole_m)
    shared = (np.maximum(np.asarray(h[0], np.float64) @ np.asarray(own["shared_w_in"], np.float64), 0)
              ** 2) @ np.asarray(own["shared_w_out"], np.float64)
    halves = []
    for first in (0, 4):
        half = dataclasses.replace(cfg, moe_held=(first, 4))
        y, load = gpt._dropless_mlp(
            half, params["moe_router"][1],
            (None, params["moe_w_in"][:, first:first + 4], params["moe_w_out"][:, first:first + 4]),
            h, h, layer=1, bias=params["moe_select_bias"][1])
        halves.append(y[0])
        assert float(load[3]) == 24 * 3             # every half sees all the assignments
    assert _err(halves[0] + halves[1] + shared, np.asarray(want)) < 1e-5
    assert _err(halves[0] + shared, np.asarray(want)) > 1e-2      # a half alone is not the block


# -------------------------------------------------- experts of two matrices
def _experts_case(N=40, D=128, F=192, X=8, K=3, seed=1):
    import jax

    from ray_tpu.ops import moe

    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(k[0], (N, D))
    w_in = jax.random.normal(k[1], (X, F, D)) / np.sqrt(D)        # out-features first
    w_out = jax.random.normal(k[2], (X, F, D)) / np.sqrt(F)
    logits, bias = jax.random.normal(k[3], (N, X)), jax.random.normal(k[4], (X,))
    idx, w = moe.dropless_route(logits, K, "sigmoid", 2.5, bias)
    want = np.zeros((N, D))
    for n in range(N):
        for j in range(K):
            e = int(idx[n, j])
            hidden = np.maximum(np.asarray(w_in[e], np.float64) @ np.asarray(x[n], np.float64), 0) ** 2
            want[n] += float(w[n, j]) * (hidden @ np.asarray(w_out[e], np.float64))
    return x, w_in, w_out, moe.dropless_combine(idx, w, X), want, (logits, bias, idx, w)


@pytest.mark.parametrize("form", ["dense", "grouped", "stacks", "kernel"])
def test_two_matrix_experts_are_a_plain_loop_in_every_form(form):
    import jax.numpy as jnp

    from ray_tpu.ops import moe

    x, w_in, w_out, combine, want, _ = _experts_case()
    if form == "dense":
        got = moe.dropless_experts(x, combine, None, w_in, w_out, "relu2")
    elif form == "grouped":
        got = moe.dropless_experts(x, combine, None, w_in, w_out, "relu2", grouped_k=3)
    elif form == "stacks":       # the whole stacks, read at a layer
        got = moe.dropless_experts(
            x, combine, None, jnp.stack([w_in * 0, w_in]), jnp.stack([w_out * 0, w_out]),
            "relu2", layer=jnp.int32(1), grouped_k=3)
    else:                        # the kernels, interpreted: one stream for up, none for a gate
        got = moe._grouped_pallas_ungated(
            x, combine, None, w_in, w_out, "relu2", None,
            *moe.dropless_groups(combine, 3, 128), 128, interpret=True)
    assert _err(got, want) < (2e-5 if form != "kernel" else 2e-4)


def test_the_selection_bias_moves_the_choice_and_never_the_weight():
    import jax

    from ray_tpu.ops import moe

    *_, (logits, bias, idx, w) = _experts_case()
    scores = np.asarray(jax.nn.sigmoid(logits))
    want_idx = np.argsort(-(scores + np.asarray(bias)), axis=-1)[:, :3]
    assert (np.sort(np.asarray(idx), -1) == np.sort(want_idx, -1)).all()
    kept = np.take_along_axis(scores, np.asarray(idx), -1)
    assert _err(w, kept / kept.sum(-1, keepdims=True) * 2.5) < 1e-6
    plain_idx, _ = moe.dropless_route(logits, 3, "sigmoid", 2.5)
    assert (np.sort(np.asarray(plain_idx), -1) != np.sort(np.asarray(idx), -1)).any()
    with pytest.raises(ValueError, match="sigmoid"):
        moe.dropless_route(logits, 3, "softmax", 1.0, bias)
    with pytest.raises(ValueError, match="two matrices"):
        moe._ungated("swiglu", None, logits)


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
        "GPipe pipeline": lambda c, p: gpt.pipeline_loss_fn(p, {"tokens": toks}, c, None, 1),
        "make_train_step": lambda c, p: gpt.make_train_step(c, optax.sgd(0.1)),
        "loss_fn": lambda c, p: gpt.loss_fn(p, {"tokens": toks}, c),
        "param_shardings": lambda c, p: gpt.param_logical_dims(c),
        "verify_step_paged": lambda c, p: gpt.verify_step_paged(
            p, toks, toks[0, :1], toks[0, :1], jnp.zeros((1, 4), jnp.int32),
            gpt.init_paged_cache(c, 4, BS, 1), c),
    }


@pytest.mark.parametrize("what", list(_refusals()))
def test_programs_that_cannot_take_blocks_of_one_mixer_refuse_them_by_name(case, what):
    with pytest.raises(NotImplementedError, match="block_pattern"):
        _refusals()[what](case[0], case[1])


@pytest.mark.parametrize("change", [
    {"block_pattern": "MEM"}, {"block_pattern": "MEMXE"}, {"activation": "swiglu"},
    {"pos": "rotary"}, {"tie_embeddings": True}, {"moe_scoring": "softmax"},
    {"ssm_groups": 3}, {"n_kv_heads": 4}, {"init": "gpt2"}])
def test_the_config_refuses_what_is_not_the_model(case, change):
    with pytest.raises(ValueError, match="block_pattern|moe_select_bias"):
        dataclasses.replace(case[0], **change)


def test_relu2_and_the_architecture_module_refuse_what_they_were_not_written_for():
    from ray_tpu.models import gpt

    with pytest.raises(ValueError, match="relu2"):
        gpt.GPTConfig(activation="relu2")
    for change in ({"mlp_hidden_act": "silu"}, {"n_group": 2}, {"tie_word_embeddings": True},
                   {"moe_shared_expert_intermediate_size": 48},
                   {"hybrid_override_pattern": "MEM-E"}):
        with pytest.raises(SystemExit, match="nemotron_h"):
            arch.dims({**PUBLISHED, **change}, False)
    with pytest.raises(SystemExit, match="no model"):
        arch.program({**PUBLISHED, "program_model": "nemotron-next"}, arch.dims(PUBLISHED, False))
