"""Phi-4-mini-flash-reasoning's layer stack (a SambaY decoder-hybrid-decoder: a
Mamba-1 + window self-decoder, ONE full layer whose rows the cross layers read,
gated memory units over one layer's scan output, differential attention in
every attention layer) through the program's normal paths, on the CPU at a small
size (8 layers: two (Mamba, window) pairs, the (Mamba, full) pair, one (memory
unit, cross) pair; window 16) with seeded random weights, each against the plain
reference of `benchmarks/arch/phi4flash.py`: the new mathematics of
`ops/sambay.py` piece by piece; `forward`; chunked paged prefill (the
cross-decoder on ONE token a chunk) then paged decode through the block manager's
group tables AND state slots (logits, not tokens) with chunk boundaries inside
and across the window and a block, a padded last chunk, padding lanes, two lanes
of unequal length, a lane past its window whose released blocks another lane
takes, a sequence given up and recomputed; the engine itself with a preemption
and its books; what the layout declares; what refuses the model; the wrong
references that must fail."""

import dataclasses

import numpy as np
import pytest

from benchmarks.arch import phi4flash as arch

BS = 8
PUBLISHED = {
    "num_hidden_layers": 8, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "intermediate_size": 96, "sliding_window": 16,
    "mb_per_layer": 2, "hidden_act": "silu", "layer_norm_eps": 1e-5,
    "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False,
    "max_position_embeddings": 256, "vocab_size": 300, "program_model": "phi4-mini-flash",
    "assumed_sizes": {"mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2,
                      "mamba_dt_rank": 8},
    "deployment": {"served_positions": 256},
}
# float32 program against the float32 reference: the largest difference of two
# logits over the largest logit in size. Both sum the same terms in float32 in
# another order; the checked positions read under 2e-6.
TOL = 2e-5
WRONG = {
    "no_lambda": {"no_lambda": True},
    "no_subln": {"no_subln": True},
    "no_lambda_scale": {"no_lambda_scale": True},
    "one_lambda_init": {"one_lambda_init": True},
    "window_one_block_wide": {"window_extra": BS},
    "m_after_gate": {"m_after_gate": True},
    "m_without_skip": {"m_without_skip": True},
    "gmu_own_input": {"gmu_own_input": True},
    "cross_reads_window_rows": {"cross_reads_pair": 1},
    "state_zeroed_at_chunk_edges": {"state_reset_every": 16},
    "tail_zeroed_at_chunk_edges": {"tail_reset_every": 16},
    "no_ln_bias": {"no_ln_bias": True},
    "state_in_bfloat16": {"state_bf16": True},
}


def _cfg():
    import jax.numpy as jnp

    from ray_tpu.models import gpt

    m = arch.dims(PUBLISHED, False)
    name, overrides = arch.program(PUBLISHED, m)
    return gpt.CONFIGS[name](**overrides, dtype=jnp.float32, param_dtype=jnp.float32,
                             remat=False), m


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
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.gpt import forward

    cfg, params, _m, tokens, want = case
    with jax.default_matmul_precision("highest"):
        got = forward(params, jnp.asarray(tokens), cfg)
    assert _err(got, want) < TOL


# ------------------------------------------------------------ ops/sambay.py
def _mixer_case(B=2, S=11, E=16, Di=32, N=4, R=3, K=4, seed=0):
    import jax

    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))
    n = lambda *shape: jax.random.normal(next(keys), shape) * 0.5
    p = {"w_in": n(E, 2 * Di), "conv_w": n(K, Di), "conv_b": n(Di), "w_x": n(Di, R + 2 * N),
         "w_dt": n(R, Di), "b_dt": n(Di), "A_log": n(N, Di), "D": n(Di), "w_out": n(Di, E)}
    return p, n(B, S, E)


def test_the_norm_free_mixer_and_its_m_are_the_token_by_token_scan():
    """Against the reference's own `_mamba` (one sequential scan over single
    tokens), in two chunks with the state and the tail carried between them, the
    second chunk padded: out AND m at every real token."""
    import jax
    import jax.numpy as jnp

    from benchmarks.arch.phi4flash_reference import _mamba
    from ray_tpu.ops import sambay, ssm

    p, h = _mixer_case()
    with jax.default_matmul_precision("highest"):
        want = [_mamba(h[b], p, {"d_state": 4, "dt_rank": 3}) for b in range(2)]
        tail = jnp.zeros((2, 3, 32))
        s = jnp.zeros((2, *ssm.state_shape(32, 4)))
        out1, m1, tail, s = sambay.mamba_mixer_plain(p, h[:, :6], tail, s, jnp.ones((2, 6), bool))
        padded = jnp.pad(h[:, 6:], ((0, 0), (0, 3), (0, 0)))
        valid = jnp.arange(8)[None, :] < jnp.asarray([5, 5])[:, None]
        out2, m2, _, _ = sambay.mamba_mixer_plain(p, padded, tail, s, valid)
    for b in range(2):
        assert _err(jnp.concatenate([out1[b], out2[b, :5]]), np.asarray(want[b][0])) < TOL
        assert _err(jnp.concatenate([m1[b], m2[b, :5]]), np.asarray(want[b][1])) < TOL


def test_the_memory_unit_is_two_products_and_a_gate():
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import sambay

    k = jax.random.split(jax.random.PRNGKey(1), 4)
    x, m = jax.random.normal(k[0], (2, 5, 16)), jax.random.normal(k[1], (2, 5, 32))
    wg, wo = jax.random.normal(k[2], (16, 32)), jax.random.normal(k[3], (32, 16))
    with jax.default_matmul_precision("highest"):
        got = sambay.gated_memory_unit(x, m, wg, wo)
        want = (m * jax.nn.silu(x @ wg)) @ wo
    assert _err(got, np.asarray(want)) < TOL


@pytest.mark.parametrize("window", [None, 5], ids=["full", "window"])
@pytest.mark.parametrize("queries", ["chunk", "one"])
def test_differential_attention_composes_from_grouped_query_attention(window, queries):
    """`diff_queries` / plain grouped-query attention over K/V PAIRS of 2 Dh /
    `diff_combine` against the reference's four dense softmax products a pair,
    every query of a chunk and the one-query form, full and under a window."""
    import jax
    import jax.numpy as jnp

    from benchmarks.arch.phi4flash_reference import _diff_attention
    from ray_tpu.ops import sambay

    T, H, Hkv, d, layer = 12, 4, 2, 8, 5
    k = jax.random.split(jax.random.PRNGKey(2), 6)
    q, kk, vv = (jax.random.normal(k[i], (T, h, d)) for i, h in enumerate((H, Hkv, Hkv)))
    p = {"lam": jax.random.normal(k[3], (4, d)) * 0.3, "sub_w": 1 + jax.random.normal(k[4], (2 * d,)) * 0.1,
         "w_o": jnp.eye(H * d), "b_o": jnp.zeros((H * d,))}
    with jax.default_matmul_precision("highest"):
        want = _diff_attention(q, kk, vv, p, jnp.float32(layer), T if window is None else window,
                               {"norm_eps": 1e-5})
        rows = slice(T - 1, T) if queries == "one" else slice(0, T)
        qh = sambay.diff_queries(q[None, rows])                       # [1, H, S, 2d]
        kp, vp = (a.reshape(T, Hkv // 2, 2 * d).transpose(1, 0, 2) for a in (kk, vv))
        i, j = jnp.arange(T)[rows, None], jnp.arange(T)[None, :]
        seen = (j <= i) & (j > i - (T if window is None else window))
        scores = jnp.einsum("grsd,gtd->grst", qh[0].reshape(Hkv // 2, -1, qh.shape[2], 2 * d),
                            kp) * d ** -0.5
        probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
        attn = jnp.einsum("grst,gtd->grsd", probs, vp).reshape(1, H, -1, 2 * d)
        got = sambay.diff_combine(attn, sambay.diff_lambda(p["lam"], jnp.int32(layer)),
                                  p["sub_w"], 1.0 - sambay.lambda_init(layer))
    assert _err(got[0], np.asarray(want)[rows]) < TOL
    assert abs(float(sambay.lambda_init(layer)) - (0.8 - 0.6 * np.exp(-1.5))) < 1e-6


# --------------------------------------------------------- the paged programs
class _Paged:
    """The two paged programs over the group tables and state slots a
    `KVBlockManager` gives, the pool donated from call to call as the engine
    donates it. Before every call the null block's rows and the null slot's
    state are set to a large value: no real lane may read either."""

    WIDTH = 16

    def __init__(self, cfg, params, slots=3, blocks=60, chunk=16):
        import jax

        from ray_tpu.models import gpt
        from ray_tpu.serve.engine import KVBlockManager

        self.cfg, self.params, self.chunk_tokens = cfg, params, chunk
        self.windows = gpt.kv_layout(cfg).windows
        self.mgr = KVBlockManager(blocks, BS, state_slots=slots, group_windows=self.windows)
        self.kv = gpt.init_paged_cache(cfg, blocks, BS, slots)
        self.prefill = jax.jit(gpt.prefill_paged, static_argnums=6, donate_argnums=5)
        self.decode = jax.jit(gpt.decode_step_paged, static_argnums=5, donate_argnums=4)

    def _poisoned(self):
        kv = dict(self.kv)
        for name in ("k", "v"):
            kv[name] = kv[name].at[:, 0].set(1e4)
        kv["state"] = {n: a.at[:, 0].set(1e4) for n, a in kv["state"].items()}
        return kv

    def tables(self, sid):
        t = np.zeros((len(self.windows), self.WIDTH), np.int32)
        for g, tab in enumerate(self.mgr.block_tables(sid)):
            t[g, : len(tab)] = tab
        return t

    def admit(self, sid, prompt):
        _, cached = self.mgr.allocate_cached(sid, [int(t) for t in prompt], len(prompt) + 1)
        assert cached == 0
        return self.mgr.state_slot(sid)

    def chunk(self, sid, prompt, start, shaped=None):
        """One prefill chunk of `prompt` from `start` in a program shaped for
        `shaped` tokens: (last position, logits)."""
        import jax
        import jax.numpy as jnp

        n = min(self.chunk_tokens, len(prompt) - start)
        self.mgr.slide(sid, start, start + n)
        padded = np.zeros((1, shaped or self.chunk_tokens), np.int32)
        padded[0, :n] = prompt[start:start + n]
        with jax.default_matmul_precision("highest"):
            logits, self.kv = self.prefill(
                self.params, jnp.asarray(padded), jnp.int32(n), jnp.int32(start),
                jnp.asarray(self.tables(sid)), self._poisoned(), self.cfg,
                jnp.int32(self.mgr.state_slot(sid)))
        return start + n - 1, np.asarray(logits)

    def step(self, lanes, bucket):
        """One decode step of `lanes` [(sid, token, position)] in a program of
        `bucket` lanes: the rest are padding (null tables, null slot)."""
        import jax
        import jax.numpy as jnp

        tok, pos = np.zeros((bucket,), np.int32), np.zeros((bucket,), np.int32)
        tabs = np.zeros((bucket, len(self.windows), self.WIDTH), np.int32)
        slots = np.zeros((bucket,), np.int32)
        for i, (sid, t, p) in enumerate(lanes):
            self.mgr.grow(sid, p + 1, first_query=p)
            tok[i], pos[i], tabs[i], slots[i] = t, p, self.tables(sid), self.mgr.state_slot(sid)
        with jax.default_matmul_precision("highest"):
            logits, self.kv = self.decode(
                self.params, jnp.asarray(tok), jnp.asarray(pos), jnp.asarray(tabs),
                self._poisoned(), self.cfg, jnp.asarray(slots))
        return np.asarray(logits)[: len(lanes)]


@pytest.mark.parametrize("chunk, prompt_len, gathered", [
    (16, 40, 128), (8, 21, 128), (32, 45, 128), (16, 40, 4)],
    ids=["across-the-window", "inside-a-block", "one-wide-chunk", "blocks-read-as-halves"])
def test_chunked_prefill_then_decode_matches_the_reference_with_padding(
        case, chunk, prompt_len, gathered, monkeypatch):
    """A prompt in several chunks (boundaries inside and across the window of 16
    and a block of 8), the last chunk padded on its right so that its last REAL
    token is not its last slot; then one real lane in a decode program of four.
    `gathered` 4: a chunk program reads each block of 8 as two of 4 (what the
    published block of 512 is read as four times: `_SAMBAY_GATHER_TOKENS`)."""
    import jax

    from ray_tpu.models import gpt

    monkeypatch.setattr(gpt, "_SAMBAY_GATHER_TOKENS", gathered)
    jax.clear_caches()
    cfg, params, _m, tokens, want = case
    run = _Paged(cfg, params, chunk=chunk)
    prompt = tokens[0, :prompt_len]
    run.admit("a", prompt)
    for start in range(0, prompt_len, chunk):
        pos, logits = run.chunk("a", prompt, start)
        assert (pos + 1) % chunk == 0 or pos == prompt_len - 1
        assert _err(logits, want[0, pos]) < TOL
    for pos in range(prompt_len, prompt_len + 20):
        logits = run.step([("a", tokens[0, pos], pos)], bucket=4)
        assert _err(logits[0], want[0, pos]) < TOL
    run.mgr.check_invariants()


def test_two_lanes_of_unequal_length_and_blocks_a_lane_released_serve_another(case):
    """Chunks of two prompts alternate, then both decode in one program of four
    lanes, one lane far past its window: each continues the state ITS chunk
    left; the blocks the long lane's window groups gave back are taken by a
    third sequence admitted into a pool that has no other room."""
    cfg, params, _m, tokens, want = case
    run = _Paged(cfg, params, blocks=38)
    prompts = [tokens[0, :47], tokens[1, :13]]
    slots = [run.admit(sid, p) for sid, p in zip("ab", prompts)]
    assert len(set(slots)) == 2 and 0 not in slots
    for start in (0, 16, 32):
        for i, sid in enumerate("ab"):
            if start < len(prompts[i]):
                pos, logits = run.chunk(sid, prompts[i], start)
                assert _err(logits, want[i, pos]) < TOL
    released = run.mgr.window_released
    assert released > 0
    for k in range(14):
        lanes = [("a", tokens[0, 47 + k], 47 + k), ("b", tokens[1, 13 + k], 13 + k)]
        logits = run.step(lanes, bucket=4)
        assert _err(logits[0], want[0, 47 + k]) < TOL
        assert _err(logits[1], want[1, 13 + k]) < TOL
    assert run.mgr.window_released > released
    # the full group keeps a block a token; a window group only its window's
    held = [sum(b != 0 for b in t) for t in run.mgr.block_tables("a")]
    assert held[-1] == -(-61 // BS) and max(held[:-1]) <= 16 // BS + 1
    free = run.mgr.free_blocks
    assert free - run.mgr.window_released < 10 <= free      # room only through the releases
    run.admit("c", tokens[1, 30:60])
    for start in (0, 16):
        pos, logits = run.chunk("c", tokens[1, 30:60], start)
    ref = arch.make_logits(case[2])(params, tokens[1, 30:60])
    assert _err(logits, ref[29]) < TOL
    run.mgr.check_invariants()


def test_a_recomputed_sequence_and_a_reused_slot_start_from_zero(case):
    cfg, params, _m, tokens, want = case
    run = _Paged(cfg, params, slots=1)
    run.admit("a", tokens[0, :20])
    for start in (0, 16):
        run.chunk("a", tokens[0, :20], start)
    for pos in range(20, 27):
        run.step([("a", tokens[0, pos], pos)], bucket=2)
    run.mgr.free("a")                             # preempted: slot and blocks go back
    slot = run.admit("a", tokens[0, :27])         # recompute: prompt + output
    for start in (0, 16):
        pos, logits = run.chunk("a", tokens[0, :27], start)
        assert _err(logits, want[0, pos]) < TOL
    assert _err(run.step([("a", tokens[0, 27], 27)], bucket=1)[0], want[0, 27]) < TOL
    run.mgr.free("a")
    assert run.admit("b", tokens[1, :30]) == slot   # the same slot, another sequence
    for start in (0, 16):
        pos, logits = run.chunk("b", tokens[1, :30], start)
        assert _err(logits, want[1, pos]) < TOL
    run.mgr.check_invariants()


def test_a_chunk_runs_the_cross_decoder_on_one_token_a_lane(case):
    """The chunk program as lowered: the cross-decoder's products carry ONE
    query row (its MLP's [1, 1, F]), the self-decoder's the chunk's 16."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt

    cfg, params, _m, _tokens, _want = case
    kv = gpt.init_paged_cache(cfg, 20, BS, 1)
    text = jax.jit(gpt.prefill_paged, static_argnums=6).lower(
        params, jnp.zeros((1, 16), jnp.int32), jnp.int32(5), jnp.int32(0),
        jnp.zeros((3, 4), jnp.int32), kv, cfg, jnp.int32(1)).as_text()
    assert "tensor<1x16x96xf32>" in text and "tensor<1x1x96xf32>" in text
    assert "tensor<1x16x300xf32>" not in text       # no logits but the last token's


@pytest.mark.parametrize("width", [1, 2])
def test_the_walk_and_the_books_ask_one_rule_for_the_cross_decoders_width(case, width, monkeypatch):
    """`gpt.sambay_cross_tokens` is asked by the program (the stream it hands the
    cross-decoder) and by the engine (`cross_decoder_tokens`): a rule that says two
    tokens a lane lowers a cross-decoder two rows wide, still exact (a cross layer
    mixes tokens only through the full layer's rows), and books two a chunk program."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt

    from ray_tpu.serve.engine import engine

    cfg, params, _m, tokens, _want = case
    monkeypatch.setattr(gpt, "sambay_cross_tokens",
                        lambda tokens, chunk: width if chunk else tokens)
    monkeypatch.setattr(engine, "_JITS", None)      # programs traced under this rule
    jax.clear_caches()
    try:
        kv = gpt.init_paged_cache(cfg, 20, BS, 1)
        text = jax.jit(lambda *a: gpt.prefill_paged(*a[:6], cfg, a[6])).lower(
            params, jnp.zeros((1, 16), jnp.int32), jnp.int32(5), jnp.int32(0),
            jnp.zeros((3, 4), jnp.int32), kv, jnp.int32(1)).as_text()
        assert f"tensor<1x{width}x96xf32>" in text
        assert ("tensor<1x2x96xf32>" in text) == (width == 2)
        eng = _engine(case)
        prompt = [int(t) for t in tokens[0, :41]]
        rid = eng.submit(prompt, 8)
        _drain(eng)
        assert _held_to_the_reference(case, prompt, list(eng.stream(rid)))
        s = eng.stats()
        assert s["attn_chunks"] == 3 and s["cross_decoder_tokens"] == 3 * width
    finally:
        jax.clear_caches()      # and none of them left for the tests that follow


@pytest.mark.parametrize("wrong", list(WRONG))
def test_a_wrong_reference_fails_the_tolerance_a_hundredfold(case, wrong):
    cfg, params, m, tokens, want = case
    got = arch.make_logits({**m, **WRONG[wrong]})(params, tokens[0])
    # a bfloat16 state is 8 bits of a state that decays within tens of tokens: tenfold
    assert _err(got[40:], want[0, 40:]) > (10 if wrong == "state_in_bfloat16" else 100) * TOL


# ------------------------------------------------------------------ the layout
def test_the_layout_is_nine_groups_one_layer_deep_with_seven_readers():
    """At the PUBLISHED sizes: 8 window groups and the one full group, a pool one
    layer deep, the seven cross layers on layer 17's group and slot and marked as
    readers, nine state layers, K/V pairs of 128 for the kernels."""
    from ray_tpu.models import gpt

    cfg = gpt.CONFIGS["phi4-mini-flash"]()
    lay = gpt.kv_layout(cfg)
    assert cfg.layer_pattern == "mw" * 8 + "mf" + "gc" * 7
    assert lay.windows == (512,) * 8 + (0,) and (lay.per_group, lay.depth) == (1, 1)
    assert [lay.group_of[l] for l in range(1, 18, 2)] == list(range(9))
    readers = [l for l, r in enumerate(lay.reads) if r]
    assert readers == list(range(19, 32, 2))
    assert {(lay.group_of[l], lay.slot_of[l]) for l in readers} == {(lay.group_of[17], lay.slot_of[17])}
    assert [lay.slot_of[l] for l in range(0, 18, 2)] == list(range(9)) and lay.state_layers == 9
    assert (lay.key_row, lay.value_row) == (1280, 1280) and lay.state_bytes == 9 * 358_400
    assert gpt.kv_head_rows(cfg) == (10, 128, 128)
    assert gpt.attn_heads_by_window(cfg) == ((512, 8 * 40), (0, 8 * 40))
    assert gpt._rowless_layers(cfg) == tuple(int(l % 2 == 0) for l in range(32))
    assert gpt.kv_layout(gpt.CONFIGS["jamba2-3b"]()).reads == ()


def test_no_row_is_written_by_a_reader(case):
    """A decode step writes one row in each of the three groups' blocks (two
    window layers, the full layer) and nowhere else: the cross layer and the
    memory unit leave the pool as it was."""
    import jax.numpy as jnp

    cfg, params, _m, tokens, _want = case
    run = _Paged(cfg, params)
    run.admit("a", tokens[0, :5])
    run.chunk("a", tokens[0, :5], 0)
    before = {n: np.asarray(run.kv[n]) for n in ("k", "v")}
    run.step([("a", tokens[0, 5], 5)], bucket=1)
    for name in ("k", "v"):
        changed = np.argwhere((np.asarray(run.kv[name]) != before[name]).any(-1))
        blocks = {int(b) for _, b, _ in changed if b != 0}      # the null block is poisoned
        assert blocks == {t[0] for t in run.mgr.block_tables("a")} and len(blocks) == 3
        assert {int(o) for _, b, o in changed if b != 0} == {5}


# ------------------------------------------------------------------ the engine
def _engine(case, **opts):
    from ray_tpu.serve.engine import EngineOptions, InferenceEngine

    options = EngineOptions(**{**dict(num_blocks=80, block_size=BS, max_num_seqs=4,
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


def test_engine_serves_exactly_and_books_the_shared_rows(case):
    cfg, params, m, tokens, _want = case
    eng = _engine(case)
    assert eng._stateful and eng._groups == 3
    prompts = [[int(t) for t in tokens[0, :41]], [int(t) for t in tokens[1, :13]],
               [int(t) for t in tokens[0, 20:55]]]
    rids = [eng.submit(p, 30) for p in prompts]
    _drain(eng)
    for p, rid in zip(prompts, rids):
        assert _held_to_the_reference(case, p, list(eng.stream(rid)))
    s = eng.stats()
    assert s["prefix_cache_hits"] == 0 and s["window_blocks_released"] > 0
    assert (s["state_slots_claimed"], s["state_slots_released"]) == (3, 3)
    # one token a chunk program through the cross-decoder: 3 + 1 + 3 chunks
    assert s["cross_decoder_tokens"] == s["attn_chunks"] == 7
    assert s["prefill_tokens"] == 41 + 13 + 35
    # the full layer and ONE cross layer read the shared rows, two window layers
    # their own: on the CPU every form covers the padded table, so half of all
    assert 0 < s["shared_kv_read_bytes"] == s["kv_read_bytes"] // 2
    lay_bytes = 3 * (16 * 128 * 4 + 3 * 128 * 4)
    assert s["ssm_state_bytes"] == 2 * s["decode_lanes"] * lay_bytes


def test_engine_preempts_and_recomputes_exactly(case):
    cfg, params, m, tokens, _want = case
    eng = _engine(case, num_blocks=30)
    prompts = [[int(t) for t in tokens[i % 2, 7 * i: 7 * i + 20]] for i in range(3)]
    rids = [eng.submit(p, 30) for p in prompts]
    _drain(eng)
    outs = [list(eng.stream(r)) for r in rids]
    assert eng.stats()["total_preemptions"] >= 1
    for p, out in zip(prompts, outs):
        assert len(out) == 30 and _held_to_the_reference(case, p, out)


@pytest.mark.parametrize("opts", [{"host_kv_bytes": 1 << 20}, {"role": "prefill"},
                                  {"role": "decode"}, {"spec_tokens": 2}])
def test_engine_refuses_at_construction_what_the_model_cannot_follow(case, opts):
    with pytest.raises(ValueError, match="KV groups"):
        _engine(case, **opts)


def test_engine_refuses_export_import_and_fork(case):
    eng = _engine(case)
    with pytest.raises(NotImplementedError, match="KV groups"):
        eng.export_prompt_kv([1, 2, 3])
    with pytest.raises(NotImplementedError, match="KV groups"):
        eng.import_blocks({"sig": eng._kv_sig(), "digests": ["00"]})
    eng.block_manager.allocate_cached("a", [1, 2, 3], 4)
    with pytest.raises((NotImplementedError, ValueError)):
        eng.block_manager.fork("a", "b")


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
            p, toks, toks[0, :1], toks[0, :1], jnp.zeros((1, 3, 4), jnp.int32),
            gpt.init_paged_cache(c, 4, BS, 1), c),
        "a paged program without state slots": lambda c, p: gpt.decode_step_paged(
            p, toks[0, :1], toks[0, :1], jnp.zeros((1, 3, 4), jnp.int32),
            gpt.init_paged_cache(c, 4, BS, 1), c),
    }


@pytest.mark.parametrize("what", list(_refusals()))
def test_programs_that_cannot_take_the_model_refuse_it_by_name(case, what):
    with pytest.raises(NotImplementedError, match="layer_pattern"):
        _refusals()[what](case[0], case[1])


@pytest.mark.parametrize("change", [
    {"layer_pattern": "mwmwmfgg"}, {"layer_pattern": "mwmfgc"}, {"norm": "rmsnorm"},
    {"tie_embeddings": False}, {"init": "gpt2"}, {"n_kv_heads": 1}, {"sliding_window": 0},
    {"pos": "rotary"}, {"activation": "gelu"}])
def test_config_refuses_what_is_not_the_model(case, change):
    with pytest.raises(ValueError, match="layer_pattern"):
        dataclasses.replace(case[0], **change)


def test_architecture_module_refuses_what_it_was_not_written_for():
    for change in ({"mb_per_layer": 1}, {"tie_word_embeddings": False}, {"mlp_bias": True},
                   {"num_hidden_layers": 6}, {"sliding_window": None}):
        with pytest.raises(SystemExit, match="phi4flash"):
            arch.dims({**PUBLISHED, **change}, False)


def test_the_published_initialisation_and_seeded_biases(case):
    cfg, params, _m, _t, _w = case
    P = 3
    assert params["sm_ssm_A_log"].shape == (P, 16, 128)
    assert np.allclose(np.exp(np.asarray(params["sm_ssm_A_log"])[0, :, 0]), np.arange(1, 17))
    step = np.log1p(np.exp(np.asarray(params["sm_ssm_b_dt"])))
    assert 0.001 <= step.min() and step.max() <= 0.1 + 1e-6
    assert (np.asarray(params["sm_ssm_D"]) == 1).all()
    for name in ("sm_ln1_b", "sa_b_qkv", "sa_b_o", "ca_b_q", "ca_lam", "sa_lam", "ln_f_b"):
        assert np.abs(np.asarray(params[name])).mean() > 0.01, name
    assert set(np.asarray(params["ln_f_w"])) == {-1.0, 1.0}
    assert cfg.n_params == sum(int(np.prod(a.shape)) for a in params.values())
