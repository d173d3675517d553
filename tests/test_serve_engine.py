"""Continuous-batching inference engine (`ray_tpu.serve.engine`).

Covers the three layers separately (KV block manager invariants, scheduler
admission/preemption policy, engine decode parity vs the dense cache) plus
the headline end-to-end property: with a long generation in flight, a short
request submitted later is admitted mid-decode and finishes FIRST —
iteration-level scheduling observable through the Serve data plane.
"""

import threading
import time
from collections import deque

import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.serve.engine import (
    KVBlockManager,
    KVCacheExhausted,
    Scheduler,
    Sequence,
)

# Tiny model shared by every engine test in this module: 2 layers keeps the
# CPU jit cheap; attn_impl="ref" (flash is a TPU Pallas kernel); f32 for
# bit-exact parity with the dense decode path. The Llama-flavored knobs
# (rotary/rmsnorm/swiglu) matter: with the vanilla GPT-2 tiny init greedy
# decode collapses to ~3 distinct tokens and a cache-position bug could
# pass parity by accident.
TINY = dict(
    vocab_size=64,
    n_layers=2,
    d_model=48,
    n_heads=3,
    d_head=16,
    d_mlp=96,
    max_seq=256,
    attn_impl="ref",
    remat=False,
    pos="rotary",
    rotary_dim=16,
    norm="rmsnorm",
    activation="swiglu",
)


def _tiny_cfg(**kw):
    import jax.numpy as jnp

    from ray_tpu.models.gpt import GPTConfig

    return GPTConfig(**{**TINY, "dtype": jnp.float32, **kw})


@pytest.fixture(scope="module")
def tiny_engine_parts():
    """(cfg, params) — params scaled up so greedy decode emits VARIED tokens
    (a random-init tiny model otherwise argmaxes one token forever and a
    cache-position bug would go unnoticed)."""
    import jax

    cfg = _tiny_cfg()
    from ray_tpu.models.gpt import init_params

    params = init_params(jax.random.PRNGKey(3), cfg)
    params = jax.tree_util.tree_map(lambda a: a * 3.0, params)
    return cfg, params


def _make_engine(cfg, params=None, **opts):
    from ray_tpu.serve.engine import EngineOptions, InferenceEngine

    defaults = dict(num_blocks=64, block_size=4, max_num_seqs=4)
    return InferenceEngine(
        cfg, params=params, options=EngineOptions(**{**defaults, **opts})
    )


def _drive(engine, max_steps=300):
    n = 0
    while engine.scheduler.has_work() and n < max_steps:
        engine.step()
        n += 1
    assert n < max_steps, "engine did not drain"
    return n


# ------------------------------------------------------- KV block manager
class TestKVBlockManager:
    def test_alloc_free_roundtrip(self):
        kv = KVBlockManager(num_blocks=9, block_size=4)
        assert kv.free_blocks == 8  # block 0 reserved
        t = kv.allocate("a", 10)  # ceil(10/4) = 3 blocks
        assert len(t) == 3 and 0 not in t
        assert kv.free_blocks == 5
        assert kv.free("a") == 3
        assert kv.free_blocks == 8
        kv.check_invariants()

    def test_grow_across_block_boundary(self):
        kv = KVBlockManager(num_blocks=9, block_size=4)
        kv.allocate("a", 4)
        assert len(kv.block_table("a")) == 1
        kv.grow("a", 5)  # crosses into a second block
        assert len(kv.block_table("a")) == 2
        kv.grow("a", 8)  # still fits block 2
        assert len(kv.block_table("a")) == 2
        kv.check_invariants()

    def test_admission_refused_at_budget(self):
        kv = KVBlockManager(num_blocks=5, block_size=4)  # 4 usable blocks
        kv.allocate("a", 12)  # 3 blocks
        assert not kv.can_allocate(8)  # would need 2, only 1 free
        with pytest.raises(KVCacheExhausted):
            kv.allocate("b", 8)
        # refusal left state intact — "b" never existed
        with pytest.raises(KeyError):
            kv.block_table("b")
        kv.check_invariants()

    def test_double_free_raises(self):
        kv = KVBlockManager(num_blocks=5, block_size=4)
        kv.allocate("a", 4)
        kv.free("a")
        with pytest.raises(KeyError):
            kv.free("a")
        kv.check_invariants()

    def test_fragmentation_reuse(self):
        """Interleaved alloc/free never loses blocks: freed tables are fully
        reusable even when frees happen out of allocation order."""
        kv = KVBlockManager(num_blocks=9, block_size=2)
        kv.allocate("a", 4)
        kv.allocate("b", 4)
        kv.allocate("c", 4)
        kv.free("b")  # hole in the middle
        t = kv.allocate("d", 6)  # needs 3: the 2 freed + 1 tail
        assert len(t) == 3
        assert kv.free_blocks == 1
        kv.free("a")
        kv.free("c")
        kv.free("d")
        assert kv.free_blocks == 8
        kv.check_invariants()

    def test_utilization_accounting(self):
        kv = KVBlockManager(num_blocks=9, block_size=4)
        assert kv.stats().utilization == 0.0
        kv.allocate("a", 16)  # 4 of 8 blocks
        st = kv.stats()
        assert st.used_blocks == 4 and st.utilization == pytest.approx(0.5)


# ----------------------------------------------------- prefix cache + COW
class TestPrefixCache:
    def test_identical_prefix_returns_identical_blocks(self):
        """Cache-hit allocation: a second prompt sharing a prefix reuses the
        first's registered blocks — identical table prefix, refcounted."""
        kv = KVBlockManager(num_blocks=32, block_size=4)
        toks = list(range(12))
        ta, cached = kv.allocate_cached("a", toks, 13)
        assert cached == 0  # cold cache
        kv.register_computed("a", toks, 12)  # engine landed the KV
        tb, cached = kv.allocate_cached("b", toks, 13)
        # 12 tokens = 3 full blocks, but the LAST one stays cold so the
        # engine has a real position to read first-token logits from.
        assert cached == 8
        assert tb[:2] == ta[:2] and tb[2] != ta[2]
        assert kv.stats().hits == 2
        kv.check_invariants()
        kv.free("a")
        kv.free("b")
        kv.check_invariants()

    def test_divergent_tail_shares_only_common_prefix(self):
        kv = KVBlockManager(num_blocks=32, block_size=4)
        sys = list(range(100, 108))  # 2 full blocks of shared system prompt
        a = sys + [1, 2, 3, 4]
        b = sys + [5, 6, 7, 8]
        kv.allocate_cached("a", a, len(a) + 1)
        kv.register_computed("a", a, len(a))
        tb, cached = kv.allocate_cached("b", b, len(b) + 1)
        assert cached == 8  # the shared system prompt only
        assert tb[:2] == kv.block_table("a")[:2]
        assert tb[2] != kv.block_table("a")[2]
        kv.check_invariants()

    def test_freed_blocks_serve_hits_until_evicted(self):
        """Retention: a finished sequence's registered blocks stay findable
        (free_blocks still counts them); exhaustion evicts them LRU."""
        kv = KVBlockManager(num_blocks=9, block_size=4)  # 8 usable
        toks = list(range(16))
        kv.allocate_cached("a", toks, 16)     # 4 blocks
        kv.register_computed("a", toks, 16)
        kv.free("a")
        st = kv.stats()
        assert st.free_blocks == 8 and st.cached_blocks == 4
        # Hit after free: content retained.
        tb, cached = kv.allocate_cached("b", toks, 17)
        assert cached == 12  # 3 of 4 full blocks (last stays cold)
        kv.free("b")
        # Exhaustion evicts cached blocks instead of failing.
        kv.allocate("big", 32)  # all 8 blocks
        st = kv.stats()
        assert st.evictions > 0 and st.cached_blocks == 0
        kv.check_invariants()
        # Evicted content no longer hits.
        kv.free("big")
        _, cached = kv.allocate_cached("c", toks, 16)
        assert cached == 0

    def test_cache_off_retains_nothing(self):
        kv = KVBlockManager(num_blocks=9, block_size=4,
                            enable_prefix_caching=False)
        toks = list(range(16))
        kv.allocate_cached("a", toks, 16)
        kv.register_computed("a", toks, 16)
        kv.free("a")
        assert kv.stats().cached_blocks == 0
        _, cached = kv.allocate_cached("b", toks, 16)
        assert cached == 0 and kv.stats().hits == 0
        kv.check_invariants()

    def test_fork_cow_never_mutates_shared_block(self):
        """fork shares every LANDED block; extending into the shared
        partial last block forks it copy-on-write — the table rewrites to
        a FRESH block and a physical (src, dst) copy is queued for the
        engine. (The parent's landed watermark covers its whole allocation
        here, so the child shares the full table.)"""
        kv = KVBlockManager(num_blocks=16, block_size=4)
        toks = [1, 2, 3, 4, 5, 6]
        kv.allocate_cached("parent", toks, 6)  # blocks [b0, b1], b1 half full
        kv.register_computed("parent", toks, 6)  # landed watermark = 6
        pt = kv.block_table("parent")
        kv.fork("parent", "child")
        assert kv.block_table("child") == pt
        kv.check_invariants()
        # Child extends: position 6 lands in shared b1 -> COW.
        ct = kv.grow("child", 7)
        assert ct[0] == pt[0], "full shared block must stay shared"
        assert ct[1] != pt[1], "shared partial block extended IN PLACE"
        copies = kv.drain_cow()
        assert copies == [(pt[1], ct[1])]
        assert kv.stats().cow_copies == 1
        assert kv.block_table("parent") == pt  # parent untouched
        kv.check_invariants()
        # Parent can now extend its own (no longer shared) last block freely.
        assert kv.grow("parent", 8)[1] == pt[1]
        assert kv.drain_cow() == []
        kv.free("parent")
        kv.free("child")
        kv.check_invariants()

    def test_fork_of_speculatively_overgrown_sequence_trims_child(self):
        """The PR 7 caveat, now HANDLED: a parent whose allocation was
        speculatively overgrown (grow() past the landed watermark to fund
        drafts the verify step later rejects) forks a child trimmed to the
        landed watermark — the child can never write into the undefined
        tail, and its own extension COWs correctly at the real boundary."""
        kv = KVBlockManager(num_blocks=16, block_size=4)
        toks = [1, 2, 3, 4, 5, 6]
        kv.allocate_cached("parent", toks, 7)   # 6 prompt + 1 gen slot
        kv.register_computed("parent", toks, 6)  # landed watermark = 6
        # Speculative overgrowth: fund 4 draft slots nothing has computed.
        kv.grow("parent", 11)
        assert kv.seq_len("parent") == 11
        kv.fork("parent", "child")
        # Child trimmed to the landed watermark: 6 tokens -> 2 blocks.
        assert kv.seq_len("child") == 6
        ct = kv.block_table("child")
        pt = kv.block_table("parent")
        assert ct == pt[:2]
        kv.check_invariants()
        # Child extending into the shared partial block COWs at the REAL
        # write position (6), not the overgrown one (11).
        grown = kv.grow("child", 8)
        assert grown[1] != pt[1], "shared partial block mutated in place"
        assert kv.drain_cow() == [(pt[1], grown[1])]
        kv.check_invariants()
        # An un-overgrown fork still shares the whole landed table.
        kv2 = KVBlockManager(num_blocks=16, block_size=4)
        kv2.allocate_cached("p", toks, 6)
        kv2.register_computed("p", toks, 6)
        kv2.fork("p", "c")
        assert kv2.block_table("c") == kv2.block_table("p")
        kv2.check_invariants()

    def test_randomized_alloc_fork_extend_free_stress(self):
        """Free-list conservation, no double-free, COW-not-in-place, and
        table/len consistency under a randomized op soup (the invariants
        check runs after EVERY op)."""
        import random

        rng = random.Random(1234)
        kv = KVBlockManager(num_blocks=33, block_size=4)
        live = {}   # seq_id -> token list
        nid = 0
        shared_full = set()  # (block at moment of registration) snapshots
        for i in range(600):
            op = rng.random()
            kv.check_invariants()
            if i % 5 == 0:
                # The engine applies queued COW copies before every kernel
                # launch; draining also re-exposes the sources to eviction.
                kv.drain_cow()
            if op < 0.35 or not live:
                nid += 1
                sid = f"s{nid}"
                n = rng.randint(1, 24)
                toks = [rng.randint(0, 7) for _ in range(n)]
                try:
                    _, cached = kv.allocate_cached(sid, toks, n)
                    assert cached % kv.block_size == 0
                    assert cached <= max(0, n - 1)
                    live[sid] = toks
                    kv.register_computed(sid, toks, n)
                except KVCacheExhausted:
                    pass
            elif op < 0.55:
                sid = rng.choice(list(live))
                nid += 1
                cid = f"s{nid}"
                try:
                    kv.fork(sid, cid)
                    live[cid] = list(live[sid])
                except (KVCacheExhausted, ValueError):
                    pass
            elif op < 0.8:
                sid = rng.choice(list(live))
                toks = live[sid]
                cur = len(toks)
                add = rng.randint(1, 6)
                old_table = kv.block_table(sid)
                refs = {b: kv._ref[b] for b in old_table}
                try:
                    table = kv.grow(
                        sid, cur + add, token_ids=toks, num_computed=cur
                    )
                except KVCacheExhausted:
                    continue
                toks.extend(rng.randint(0, 7) for _ in range(add))
                # COW check: the block this grow writes into (position `cur`)
                # must be swapped out of the table if it was shared.
                wi = cur // kv.block_size
                if wi < len(old_table) and refs[old_table[wi]] > 1:
                    assert table[wi] != old_table[wi], (
                        "shared block mutated in place"
                    )
            else:
                sid = rng.choice(list(live))
                kv.free(sid)
                del live[sid]
                with pytest.raises(KeyError):
                    kv.free(sid)  # double free must raise
        for sid in list(live):
            kv.free(sid)
        kv.drain_cow()  # what the engine does before its next launch
        kv.check_invariants()
        # Conservation: every block ends blank or cached (all reclaimable
        # once no copies are pending), none lost.
        st = kv.stats()
        assert st.free_blocks == 32 and st.used_blocks == 0


# -------------------------------------------------------------- scheduler
def _sched_step(sched):
    """schedule() + simulate the engine landing every chunk's KV (advance
    the prefill cursor) — scheduler-only tests have no engine."""
    out = sched.schedule()
    for c in out.prefills:
        c.seq.num_computed = c.start + c.num_tokens
    return out


class TestScheduler:
    def _seq(self, rid, prompt_len=4, max_new=8, fill=1):
        return Sequence(
            request_id=rid, prompt=[fill] * prompt_len, max_new_tokens=max_new
        )

    def test_admission_mid_decode(self):
        kv = KVBlockManager(num_blocks=64, block_size=4)
        sched = Scheduler(kv, max_num_seqs=4)
        a = self._seq("a", max_new=50)
        sched.add(a)
        out = _sched_step(sched)
        assert [c.seq for c in out.prefills] == [a] and out.decodes == []
        assert out.prefills[0].last  # short prompt: one chunk covers it
        a.append_token(1)
        out = _sched_step(sched)
        assert out.decodes == [a]
        # New arrival joins the NEXT iteration, not after "a" finishes.
        b = self._seq("b", max_new=2)
        sched.add(b)
        a.append_token(1)
        out = _sched_step(sched)
        assert b in [c.seq for c in out.prefills] and a in out.decodes

    def test_admission_refused_queues(self):
        kv = KVBlockManager(num_blocks=5, block_size=4)  # 16 usable slots
        sched = Scheduler(kv, max_num_seqs=4)
        a = self._seq("a", prompt_len=12, max_new=3)  # 13 slots at admission
        b = self._seq("b", prompt_len=12, max_new=3)
        sched.add(a)
        sched.add(b)
        out = _sched_step(sched)
        assert [c.seq for c in out.prefills] == [a]
        assert sched.queue_depth == 1  # b queued, not crashed
        a.append_token(1)
        sched.finish(a, "length")  # blocks freed...
        out = _sched_step(sched)
        # ...and b admitted the very next step
        assert [c.seq for c in out.prefills] == [b]

    def test_preemption_recompute(self):
        kv = KVBlockManager(num_blocks=7, block_size=2)  # 6 usable blocks
        sched = Scheduler(kv, max_num_seqs=4)
        # Distinct prompts: identical ones would prefix-cache-SHARE their
        # first full block and the pool would never fill.
        a = self._seq("a", prompt_len=3, max_new=5)
        b = self._seq("b", prompt_len=3, max_new=5, fill=2)
        sched.add(a)
        sched.add(b)
        _sched_step(sched)      # admits a: 2 blocks
        a.append_token(7)
        _sched_step(sched)      # a grows to 3 blocks; admits b: 2 blocks
        a.append_token(7)
        b.append_token(8)
        _sched_step(sched)      # b grows to 3 blocks — pool now full
        a.append_token(7)
        b.append_token(8)
        out = _sched_step(sched)  # a needs a 4th block — b (youngest) preempted
        assert out.preempted == [b]
        assert b.state == "WAITING"
        assert b.prompt == [2, 2, 2, 8, 8]  # generated tokens folded in
        assert b.max_new_tokens == 3        # generation budget shrunk to match
        assert b.num_computed == 0          # prefill restarts (cache may hit)
        kv.check_invariants()

    def test_oversized_request_rejected_at_add(self):
        kv = KVBlockManager(num_blocks=5, block_size=2)
        sched = Scheduler(kv, max_num_seqs=4)
        with pytest.raises(KVCacheExhausted):
            sched.add(self._seq("big", prompt_len=20, max_new=20))

    def test_chunked_prefill_budget_and_decode_mix(self):
        """A long prompt advances `prefill_chunk` tokens per step while the
        decode lane keeps emitting every step — the chunked-prefill
        property, plus the per-step token budget cap."""
        kv = KVBlockManager(num_blocks=64, block_size=4)
        sched = Scheduler(
            kv, max_num_seqs=4, max_step_tokens=12, prefill_chunk=8
        )
        short = self._seq("short", prompt_len=4, max_new=20)
        sched.add(short)
        out = _sched_step(sched)
        assert out.prefills[0].last
        short.append_token(1)
        # fill=3: a [1]-filled prompt would prefix-hit short's cached block
        # and start the cursor at 4 instead of 0.
        long = self._seq("long", prompt_len=30, max_new=4, fill=3)
        sched.add(long)
        starts = []
        for _ in range(4):  # 30 tokens / chunk 8 (budget 12-1=11) -> 4 steps
            out = _sched_step(sched)
            assert out.decodes == [short], "decode stalled by a prefill chunk"
            assert len(out.prefills) == 1 and out.prefills[0].seq is long
            assert out.step_tokens <= 12
            starts.append(out.prefills[0].start)
            short.append_token(1)
        assert starts == [0, 8, 16, 24]
        assert out.prefills[0].last and long.num_computed == 30
        out = _sched_step(sched)  # fully prefilled; no token emitted yet
        assert out.prefills == []
        long.append_token(1)      # engine samples token 0 off the last chunk
        out = _sched_step(sched)
        assert long in out.decodes and short in out.decodes
        kv.check_invariants()


# ------------------------------------------------------------ engine core
class TestEngineDecode:
    def test_parity_with_dense_decode(self, tiny_engine_parts):
        """Paged block-table decode must be token-for-token identical to the
        dense-cache `make_generate` path (greedy, f32)."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.models.gpt import make_generate

        cfg, params = tiny_engine_parts
        prompt = [7, 3, 11, 60, 2, 9, 1]
        N = 12
        eng = _make_engine(cfg, params)
        rid = eng.submit(prompt, max_new_tokens=N)
        res = {}
        t = threading.Thread(
            target=lambda: res.setdefault("toks", list(eng.stream(rid)))
        )
        t.start()
        _drive(eng)
        t.join(10)
        ref = jax.jit(make_generate(cfg, N))(
            params, jnp.asarray([prompt], jnp.int32), jax.random.PRNGKey(0)
        )[0].tolist()
        assert res["toks"] == ref
        assert len(set(ref)) > 3, "degenerate decode — parity proves nothing"
        eng.block_manager.check_invariants()

    def test_short_request_admitted_mid_decode_finishes_first(
        self, tiny_engine_parts
    ):
        """THE iteration-level scheduling property, deterministically: start
        a long generation, submit a short one three iterations in, and watch
        the short one retire while the long one is still decoding."""
        cfg, params = tiny_engine_parts
        eng = _make_engine(cfg, params)
        finish_order = []
        orig_finish = eng.scheduler.finish

        def record(seq, reason):
            finish_order.append(seq.request_id)
            orig_finish(seq, reason)

        eng.scheduler.finish = record
        long_id = eng.submit([1] * 8, max_new_tokens=40)
        for _ in range(3):
            eng.step()
        long_seq = eng.scheduler.get(long_id)
        assert long_seq.state == "RUNNING" and len(long_seq.output) >= 1
        short_id = eng.submit([2] * 4, max_new_tokens=3)
        _drive(eng)
        assert finish_order == [short_id, long_id]
        eng.block_manager.check_invariants()
        assert eng.block_manager.free_blocks == 63  # everything returned

    def test_kv_pressure_queues_and_preempts_without_crashing(
        self, tiny_engine_parts
    ):
        """Pool sized for ~1.3 requests; three submitted at once. Admission
        refusal queues, mid-decode exhaustion preempts (recompute), and all
        three still produce their full outputs."""
        cfg, params = tiny_engine_parts
        eng = _make_engine(cfg, params, num_blocks=9, block_size=4)
        ids = [eng.submit([3] * 8, max_new_tokens=16) for _ in range(3)]
        outs = [eng.stream(i) for i in ids]
        res = [None] * 3
        ts = [
            threading.Thread(
                target=lambda i=i: res.__setitem__(i, list(outs[i]))
            )
            for i in range(3)
        ]
        for t in ts:
            t.start()
        _drive(eng, max_steps=500)
        for t in ts:
            t.join(10)
        assert all(len(r) == 16 for r in res)
        eng.block_manager.check_invariants()
        assert eng.block_manager.free_blocks == 8

    def test_submit_rejects_impossible_requests(self, tiny_engine_parts):
        cfg, params = tiny_engine_parts
        eng = _make_engine(cfg, params, num_blocks=5, block_size=4)
        with pytest.raises(ValueError):
            eng.submit([1] * 8, max_new_tokens=300)  # > cfg.max_seq
        with pytest.raises(ValueError):
            eng.submit([1] * 10, max_new_tokens=10)  # > whole KV pool

    def test_stream_after_finish_keeps_tokens(self, tiny_engine_parts):
        """A fast request can finish before the caller reaches stream() —
        the output must survive until claimed (and be claimable once)."""
        cfg, params = tiny_engine_parts
        eng = _make_engine(cfg, params)
        rid = eng.submit([5, 6, 7], max_new_tokens=2)
        _drive(eng)  # fully finished; nobody has attached yet
        out = eng.stream(rid)
        toks = list(out)
        assert len(toks) == 2 and out.finish_reason == "length"
        with pytest.raises(KeyError):
            eng.stream(rid)  # single-consumer: claimed streams are gone

    def test_chunked_prefill_parity_with_monolithic(self, tiny_engine_parts):
        """ACCEPTANCE: chunked and monolithic prefill produce token-identical
        outputs. Same 30-token prompt through (a) one monolithic prefill,
        (b) 8-token chunks, (c) 8-token chunks with the prefix pre-cached by
        an earlier identical request — all three must match the dense-cache
        reference exactly."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.models.gpt import make_generate

        cfg, params = tiny_engine_parts
        prompt = [int(t) for t in
                  jax.random.randint(jax.random.PRNGKey(9), (30,), 0, 64)]
        N = 10
        ref = jax.jit(make_generate(cfg, N))(
            params, jnp.asarray([prompt], jnp.int32), jax.random.PRNGKey(0)
        )[0].tolist()
        assert len(set(ref)) > 3, "degenerate decode — parity proves nothing"

        def run(eng):
            rid = eng.submit(prompt, max_new_tokens=N)
            res = {}
            t = threading.Thread(
                target=lambda: res.setdefault("t", list(eng.stream(rid)))
            )
            t.start()
            _drive(eng)
            t.join(10)
            return res["t"]

        mono = _make_engine(cfg, params, prefill_chunk_tokens=256)
        assert run(mono) == ref
        chunked = _make_engine(cfg, params, prefill_chunk_tokens=8,
                               max_step_tokens=16)
        assert run(chunked) == ref
        # 30 tokens / 8-token chunks -> starts 0, 8, 16, 24
        assert run(chunked) == ref  # second pass rides the prefix cache
        assert chunked.block_manager.stats().hits > 0
        chunked.block_manager.check_invariants()

    def test_prefix_cache_speeds_identical_prompts(self, tiny_engine_parts):
        """Two requests sharing a 24-token prefix: the second admission
        starts its prefill cursor past the shared blocks (cache hits), and
        outputs are unaffected by riding cached KV."""
        cfg, params = tiny_engine_parts
        shared = [11, 7, 3, 60, 2, 9, 1, 44] * 3   # 24 tokens = 6 blocks
        a_prompt = shared + [5, 6]
        b_prompt = shared + [8, 9]
        eng = _make_engine(cfg, params)
        base = _make_engine(cfg, params, enable_prefix_caching=False)

        def run(e, p):
            rid = e.submit(p, max_new_tokens=6)
            out = e.stream(rid)
            res = {}
            t = threading.Thread(target=lambda: res.setdefault("t", list(out)))
            t.start()
            _drive(e)
            t.join(10)
            return res["t"]

        assert run(eng, a_prompt) == run(base, a_prompt)
        st0 = eng.block_manager.stats()
        toks_b = run(eng, b_prompt)
        st1 = eng.block_manager.stats()
        assert st1.hits - st0.hits == 6, "shared 24-token prefix = 6 blocks"
        assert toks_b == run(base, b_prompt), (
            "cache-hit decode diverged from cold decode"
        )
        b_seq_cached = eng.stats()["prefix_cache_hits"]
        assert b_seq_cached >= 6
        eng.block_manager.check_invariants()

    def test_paged_kernels_compile_once_per_bucket(self, tiny_engine_parts):
        """CI guard: across a mixed workload (varied prompt/output lengths,
        concurrent lanes), the jitted paged programs compile once per
        (batch-bucket, width-bucket) / (chunk-bucket, width-bucket) pair —
        a bucket-policy regression that recompiles per step trips this."""
        cfg, params = tiny_engine_parts
        eng = _make_engine(cfg, params, num_blocks=128, block_size=4,
                           max_num_seqs=4, prefill_chunk_tokens=8,
                           max_step_tokens=32)
        pre0 = eng._prefill._cache_size()
        dec0 = eng._decode._cache_size()
        import jax

        key = jax.random.PRNGKey(5)
        lens = [3, 7, 9, 14, 22, 30, 5, 17, 11, 26]
        for i, L in enumerate(lens):
            toks = [int(t) for t in
                    jax.random.randint(jax.random.PRNGKey(i), (L,), 0, 64)]
            eng.submit(toks, max_new_tokens=4 + (i % 9))
            if i % 2:
                _drive(eng)  # drain sometimes -> batch sizes churn
        _drive(eng)
        # Distinct shape buckets actually reachable here: prefill chunks pad
        # to pow2 <= 8 (4 buckets) x width buckets; decode batches pad to
        # pow2 <= 4 (3) x widths. Bound them, with slack for width buckets.
        d_pre = eng._prefill._cache_size() - pre0
        d_dec = eng._decode._cache_size() - dec0
        assert d_pre <= 4 * 4, f"prefill compiled {d_pre} programs"
        assert d_dec <= 3 * 4, f"decode compiled {d_dec} programs"
        # Steady state: the SECOND pass may add a few smaller chunk buckets
        # (prefix-cache hits shrink the first chunk), but by the THIRD pass
        # every reachable bucket is warm — zero new compiles.
        def rerun():
            for i, L in enumerate(lens):
                toks = [int(t) for t in
                        jax.random.randint(jax.random.PRNGKey(i), (L,), 0, 64)]
                eng.submit(toks, max_new_tokens=4 + (i % 9))
            _drive(eng, max_steps=600)

        rerun()
        pre1, dec1 = eng._prefill._cache_size(), eng._decode._cache_size()
        rerun()
        assert eng._prefill._cache_size() == pre1, "prefill recompiled"
        assert eng._decode._cache_size() == dec1, "decode recompiled"
        eng.block_manager.check_invariants()

    def test_eos_stops_early(self, tiny_engine_parts):
        cfg, params = tiny_engine_parts
        eng = _make_engine(cfg, params)
        prompt = [7, 3, 11, 60, 2, 9, 1]
        # Whatever greedy decode of this prompt emits first (it depends on
        # the installed jax's RNG and numerics) is the stop token.
        rid = eng.submit(prompt, max_new_tokens=1)
        first = eng.stream(rid)
        _drive(eng)
        (eos,) = list(first)
        rid = eng.submit(prompt, max_new_tokens=12, eos_token=eos)
        out = eng.stream(rid)
        res = {}
        t = threading.Thread(target=lambda: res.setdefault("t", list(out)))
        t.start()
        _drive(eng)
        t.join(10)
        assert res["t"][-1] == eos and len(res["t"]) < 12
        assert out.finish_reason == "eos"


# ------------------------------------------- steps chained on the device
def _reference(cfg, params, prompt, n):
    """The dense path's greedy ids (`prefill` / `decode_step`)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.gpt import make_generate

    return jax.jit(make_generate(cfg, n))(
        params, jnp.asarray([prompt], jnp.int32), jax.random.PRNGKey(0)
    )[0].tolist()


def _plans(eng):
    """Every work order the engine's loop runs from now on, as it runs."""
    plans, schedule = [], eng.scheduler.schedule

    def planned():
        plans.append(schedule())
        return plans[-1]

    eng.scheduler.schedule = planned
    return plans


def _walk(eng, max_steps=600):
    """Drive by hand; after every `step()` the block accounting holds.
    Yields (plan, decode programs dispatched, of them chained) a step."""
    plans = _plans(eng)
    n = 0
    while eng.scheduler.has_work():
        before = (eng.total_decode_dispatched, eng.total_decode_chained)
        eng.step()
        eng.block_manager.check_invariants()
        held = [s.slot for s in eng.scheduler.running]
        assert sorted(held + eng.scheduler._free_slots) == list(
            range(eng.opts.max_num_seqs)), "a lane slot was lost or shared"
        yield (plans[-1], eng.total_decode_dispatched - before[0],
               eng.total_decode_chained - before[1])
        n += 1
        assert n < max_steps, "engine did not drain"


MIXED = [([7, 3, 11, 60, 2, 9, 1], 12), ([5, 5, 5, 9, 8], 7),
         (list(range(1, 14)), 10), ([44, 2], 14)]


class TestChainedSteps:
    """The engine dispatches step n+1 before it reads step n's ids: the ids
    a client gets are still the dense reference's, id for id."""

    @pytest.mark.parametrize("num_blocks", [64, 12], ids=["roomy", "tight"])
    def test_chained_greedy_ids_are_the_dense_references(
            self, tiny_engine_parts, num_blocks):
        cfg, params = tiny_engine_parts
        eng = _make_engine(cfg, params, num_blocks=num_blocks)
        rids = [eng.submit(p, n) for p, n in MIXED]
        for _ in _walk(eng):
            pass
        for rid, (p, n) in zip(rids, MIXED):
            assert list(eng.stream(rid)) == _reference(cfg, params, p, n)
        st = eng.stats()
        assert st["total_tokens"] == sum(n for _, n in MIXED)
        assert st["decode_chained"] > st["decode_dispatched"] // 2
        # 11 blocks of 4 hold less than the four requests' 70 tokens
        assert (st["total_preemptions"] > 0) == (num_blocks == 12)
        assert eng.block_manager.free_blocks == num_blocks - 1

    def test_eos_stops_the_stream_though_the_lane_rides_one_step_more(
            self, tiny_engine_parts):
        cfg, params = tiny_engine_parts
        prompt, n = MIXED[0]
        ref = _reference(cfg, params, prompt, n)
        k = max(i for i, t in enumerate(ref[:8]) if t not in ref[:i])
        assert k >= 2, ref            # an id whose FIRST occurrence is late
        # a pool the request fills: what comes next is admitted into its blocks
        eng = _make_engine(cfg, params, num_blocks=6)
        rid = eng.submit(prompt, n, eos_token=ref[k])
        steps = list(_walk(eng))
        out = eng.stream(rid)
        assert list(out) == ref[:k + 1] and out.finish_reason == "eos"
        # the eos was read after the next step was dispatched: k decode
        # steps gave ids k..1 after the chunk's, one more ran for nothing
        assert sum(d for _, d, _ in steps) == k + 1
        assert eng.stats()["total_tokens"] == k + 1
        assert eng.block_manager.free_blocks == 5
        other = [9, 1, 33, 7, 3, 60, 2, 2, 11]
        rid = eng.submit(other, 10)
        for _ in _walk(eng):
            pass
        assert list(eng.stream(rid)) == _reference(cfg, params, other, 10)

    def test_one_program_a_bucket_chained_or_not(self, tiny_engine_parts):
        """What `benchmarks/traffic.py:warm_plan` assumes: one decode
        program a (lanes, width) bucket and one prefill program a (chunk,
        width) bucket, whether a step takes its ids from the device or
        from the host."""
        cfg, params = tiny_engine_parts
        import dataclasses

        cfg = dataclasses.replace(cfg, d_mlp=cfg.d_mlp + 8)  # programs of its own
        import jax

        from ray_tpu.models.gpt import init_params

        eng = _make_engine(cfg, init_params(jax.random.PRNGKey(1), cfg),
                           num_blocks=128, prefill_chunk_tokens=8,
                           max_step_tokens=32)
        pre0, dec0 = eng._prefill._cache_size(), eng._decode._cache_size()
        decode_buckets, prefill_buckets, kinds = set(), set(), set()

        def drive():
            for step, (plan, dispatched, chained) in enumerate(_walk(eng)):
                if step % 3 == 0:
                    eng._collect()  # as a drain would: the next step is unchained
                for c in plan.prefills:
                    prefill_buckets.add((
                        1 << (c.num_tokens - 1).bit_length(),
                        1 << (-(-(len(c.seq.prompt) + 1) // 4) - 1).bit_length()))
                if dispatched:
                    decode_buckets.add((plan.batch_bucket, plan.width_bucket))
                    kinds.add((plan.batch_bucket, plan.width_bucket, chained))

        for i, (length, new) in enumerate(
                [(3, 9), (7, 5), (9, 12), (14, 4), (22, 8), (30, 3), (5, 17)]):
            eng.submit([(5 * i + j) % 60 + 1 for j in range(length)], new)
            if i == 3:          # lanes and widths churn: drain once midway
                drive()
        drive()
        assert len(decode_buckets) >= 4 and len(prefill_buckets) >= 4
        # some bucket ran both ways
        assert len(kinds) > len(decode_buckets)
        assert eng._decode._cache_size() - dec0 <= len(decode_buckets)
        assert eng._prefill._cache_size() - pre0 <= len(prefill_buckets)

    def test_counters_say_which_steps_were_chained(self, tiny_engine_parts):
        cfg, params = tiny_engine_parts
        # one request: every decode step takes its id from the device, the
        # first from the final chunk's program
        eng = _make_engine(cfg, params)
        eng.submit([1, 2, 3, 4, 5], 9)
        steps = list(_walk(eng))
        assert [(d, c) for _, d, c in steps] == [(0, 0)] + [(1, 1)] * 8 + [(0, 0)]
        st = eng.stats()
        assert (st["decode_dispatched"], st["decode_chained"]) == (8, 8)
        assert type(st["decode_dispatched"]) is type(st["decode_chained"]) is int

        # an export is served drained: the decode step after it is not chained
        from concurrent.futures import Future

        prompt = list(range(1, 10))
        eng.submit(prompt, 6)
        walk = _walk(eng)
        for _ in range(3):
            next(walk)
        fut = Future()
        eng._side_work.append(("export", eng.prompt_digests(prompt), fut))
        _, dispatched, chained = next(walk)
        assert fut.done() and (dispatched, chained) == (1, 0)
        assert [c for _, d, c in walk if d] == [1, 1]

        # the preemption of a lane whose newest id is unread is planned
        # from values: the engine reads the step in flight first
        eng = _make_engine(cfg, params, num_blocks=9)
        for _ in range(3):
            eng.submit([3] * 8, 16)
        drained = 0
        unread = {}
        for plan, dispatched, chained in _walk(eng):
            if any(unread.get(v.request_id) for v in plan.preempted):
                drained += 1
                assert chained == 0
            unread = {s.request_id: s.unread for s in eng.scheduler.running}
        assert eng.total_preemptions > 0 and drained > 0

    def test_verify_steps_run_drained(self, tiny_engine_parts):
        cfg, params = tiny_engine_parts
        prompt = [5, 6, 7, 8] * 4
        eng = _make_engine(cfg, params, spec_tokens=4, max_step_tokens=12)
        rid = eng.submit(prompt, 20)
        steps = list(_walk(eng))
        assert list(eng.stream(rid)) == _reference(cfg, params, prompt, 20)
        verify = [(d, c) for plan, d, c in steps if plan.drafts]
        assert verify and all(v == (1, 0) for v in verify)
        assert eng.stats()["spec_accepted"] > 0

    def test_a_seed_repeats_its_ids_and_lanes_draw_apart(self, tiny_engine_parts):
        cfg, params = tiny_engine_parts

        def run(seed):
            eng = _make_engine(cfg, params, temperature=1.5, seed=seed)
            rids = [eng.submit([7, 3, 11, 60], 16) for _ in range(2)]
            _drive(eng)
            return [list(eng.stream(r)) for r in rids]

        a, b, c = run(11), run(11), run(12)
        assert a == b and a != c
        assert a[0] != a[1]     # one prompt, one step, two lanes: two draws

    def test_sampler_draws_from_softmax_of_logits_over_temperature(self):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from ray_tpu.models.gpt import sample_ids

        logits = jnp.asarray([[2.0, 0.5, 0.0, -1.0, 1.0, 0.0],
                              [0.0, 0.0, 3.0, 0.0, -2.0, 1.0]], jnp.float32)

        @jax.jit                # temperature traced, as in the engine's programs
        def draws(temperature, key):
            keys = jax.random.split(key, 4000)
            return jax.vmap(lambda k: sample_ids(logits, temperature, k))(keys)

        for t in (0.7, 2.0):
            ids = np.asarray(draws(jnp.float32(t), jax.random.PRNGKey(5)))
            want = np.asarray(jax.nn.softmax(logits / t, axis=-1))
            for lane in range(2):
                freq = np.bincount(ids[:, lane], minlength=6) / len(ids)
                assert np.abs(freq - want[lane]).max() < 0.03, (t, freq, want[lane])
        greedy = np.asarray(draws(jnp.float32(0.0), jax.random.PRNGKey(5)))
        assert (greedy == np.asarray([0, 2])).all()
        # equal maxima: the first, as NumPy's argmax
        ties = jnp.asarray([[1.0, 3.0, 3.0, 0.0]], jnp.float32)
        assert sample_ids(ties, 0.0, None).tolist() == [int(np.argmax(ties[0]))]

    @pytest.mark.parametrize("threaded", [False, True], ids=["by-hand", "loop"])
    def test_shutdown_with_a_step_in_flight_closes_every_stream(
            self, tiny_engine_parts, threaded):
        cfg, params = tiny_engine_parts
        eng = _make_engine(cfg, params)
        rid = eng.submit([1, 2, 3, 4], 40)
        out = eng.stream(rid)
        if threaded:
            eng.start()
            assert next(iter(out)) is not None      # the loop is running
        else:
            for _ in range(3):
                eng.step()
            assert eng._inflight is not None
        got = {}

        def consume():
            try:
                got["toks"] = list(out)
            except RuntimeError as e:
                got["err"] = e

        t = threading.Thread(target=consume, daemon=True)
        t.start()
        eng.shutdown()
        t.join(10)
        assert not t.is_alive(), "a consumer hangs on a stream nobody closes"
        assert "err" in got and eng._inflight is None
        with pytest.raises(RuntimeError):
            eng.submit([1], 1)


# ------------------------------------------------ the paged programs alone
# Three kinds of model through the same three functions (`models/gpt.py`
# `_paged_layers`), each held to the dense `prefill` / `decode_step` logits:
# learned positions; full rotary; and GPT-J's parallel block with
# rotary_dim < d_head. Two have H*Dh = 48, not a multiple of the 128 lanes
# the pool's rows are laid out for: they must stay correct, not fast.
PAGED_PRESETS = {
    "learned-48": dict(pos="learned", norm="layernorm", activation="gelu"),
    "rotary-48": {},
    "gptj-128": dict(
        d_model=64, n_heads=4, d_head=32, d_mlp=128, rotary_dim=8,
        parallel_block=True, tie_embeddings=False, norm="layernorm",
        activation="gelu",
    ),
}
_BS, _NB = 4, 32


def _paged_case(name):
    """(cfg, params, tokens [40], dense logits): dense[i] is the dense
    path's next-token logits after tokens[: i + 1], for i >= 8."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.gpt import (
        decode_step, init_cache, init_params, prefill,
    )

    cfg = _tiny_cfg(**PAGED_PRESETS[name])
    params = jax.tree_util.tree_map(
        lambda a: a * 3.0, init_params(jax.random.PRNGKey(5), cfg)
    )
    tokens = jax.random.randint(jax.random.PRNGKey(11), (40,), 0, 64)
    logits, cache = jax.jit(prefill, static_argnums=2)(
        params, tokens[None, :9], cfg, init_cache(cfg, 1, 64)
    )
    dense = {8: logits[0]}
    step = jax.jit(decode_step, static_argnums=3)
    for i in range(9, 40):
        logits, cache = step(params, tokens[i][None], cache, cfg)
        dense[i] = logits[0]
    return cfg, params, tokens, dense


@pytest.fixture(scope="module", params=list(PAGED_PRESETS))
def paged_case(request):
    return _paged_case(request.param)


# Keys a trip of `_paged_layers`' key loop covers in these tests: the tables
# below (8 blocks of 4) are then one tile (one shot, no loop) or four.
ONE_SHOT, TILED = 1 << 20, 8


@pytest.fixture(params=[ONE_SHOT, TILED], ids=["one-shot", "tiled"])
def paged_jits(request, tile_keys):
    with tile_keys(request.param) as jits:
        yield jits


def _paged(cfg, jits):
    """The three programs and a pool."""
    from ray_tpu.models.gpt import init_paged_cache

    return (*jits, init_paged_cache(cfg, _NB, _BS))


def _prefill_chunks(prefill, params, cfg, tokens, table, kv, start, chunks):
    """Prefill tokens[start:] into `table` in the given chunk lengths, each
    right-padded to a bucket of 16; returns (last chunk's logits, kv)."""
    import jax.numpy as jnp

    pos = start
    for n in chunks:
        padded = jnp.zeros((1, 16), jnp.int32).at[0, :n].set(tokens[pos:pos + n])
        logits, kv = prefill(
            params, padded, jnp.int32(n), jnp.int32(pos), table, kv, cfg
        )
        pos += n
    return logits, kv


def _close(a, b):
    import numpy as np

    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4)


class TestPagedPrograms:
    # Scattered, unordered physical blocks: a table is a map, not a range.
    TABLE_A = [7, 3, 21, 12, 5, 30, 9, 18]
    TABLE_B = [7, 3, 21, 14, 2, 25, 11, 6]     # shares A's first three blocks
    TABLE_C = [4, 13, 22, 31, 1, 10, 19, 28]   # shares none

    def test_pool_is_rows_of_all_heads(self, paged_case):
        from ray_tpu.models.gpt import init_paged_cache

        cfg = paged_case[0]
        kv = init_paged_cache(cfg, _NB, _BS)
        shape = (cfg.n_layers, _NB, _BS, cfg.n_heads * cfg.d_head)
        assert kv["k"].shape == kv["v"].shape == shape
        assert kv["k"].dtype == cfg.dtype

    def test_chunked_prefill_matches_dense(self, paged_case, paged_jits):
        """A 23-token prompt in chunks of 9, 7, 7 (non-zero `pos_offset`,
        padded buckets, chunks that straddle blocks), then a second
        sequence that shares the first three blocks as a cached prefix and
        prefills only from position 12: both give the dense logits."""
        import jax.numpy as jnp
        import numpy as np

        cfg, params, tokens, dense = paged_case
        prefill, _, _, kv = _paged(cfg, paged_jits)
        table = jnp.asarray(self.TABLE_A, jnp.int32)
        logits, kv = _prefill_chunks(
            prefill, params, cfg, tokens, table, kv, 0, (9, 7, 7)
        )
        _close(logits, dense[22])
        before = np.array(kv["k"])
        logits, kv = _prefill_chunks(
            prefill, params, cfg, tokens, jnp.asarray(self.TABLE_B, jnp.int32),
            kv, 12, (4, 7),
        )
        _close(logits, dense[22])
        after = np.asarray(kv["k"])
        shared = self.TABLE_B[:3]
        np.testing.assert_array_equal(after[:, shared], before[:, shared])

    def test_decode_lanes_match_dense_and_padding_writes_block_0(
        self, paged_case, paged_jits
    ):
        """Two sequences at unrelated positions and two padded lanes in one
        bucket of four: each real lane's logits are the dense path's, step
        after step, and the pool changes only in the two rows written and
        in the null block."""
        import jax.numpy as jnp
        import numpy as np

        cfg, params, tokens, dense = paged_case
        prefill, decode, _, kv = _paged(cfg, paged_jits)
        ta = jnp.asarray(self.TABLE_A, jnp.int32)
        tb = jnp.asarray(self.TABLE_C, jnp.int32)
        _, kv = _prefill_chunks(prefill, params, cfg, tokens, ta, kv, 0, (16, 7))
        _, kv = _prefill_chunks(prefill, params, cfg, tokens, tb, kv, 0, (10,))
        tables = jnp.stack([ta, jnp.zeros_like(ta), tb, jnp.zeros_like(ta)])
        for step in range(3):
            pa, pb = 23 + step, 10 + step
            before = {n: np.array(a) for n, a in kv.items()}
            logits, kv = decode(
                params, jnp.asarray([tokens[pa], 0, tokens[pb], 0]),
                jnp.asarray([pa, 0, pb, 0], jnp.int32), tables, kv, cfg,
            )
            _close(logits[0], dense[pa])
            _close(logits[2], dense[pb])
            for name, arr in kv.items():
                changed = np.argwhere(
                    (np.asarray(arr) != before[name]).any(axis=(0, 3))
                )
                written = {(int(ta[pa // _BS]), pa % _BS),
                           (int(tb[pb // _BS]), pb % _BS)}
                assert {(b, o) for b, o in changed.tolist() if b} == written
                assert all(o == 0 for b, o in changed.tolist() if b == 0)

    def test_verify_matches_sequential_decode(self, paged_case, paged_jits):
        """Three tokens a lane in one forward: logits[b, j] are the dense
        path's after tokens 0..pos+j; a lane with a shorter `valid_len`
        and a padded lane write nothing outside their own rows and block
        0."""
        import jax.numpy as jnp
        import numpy as np

        cfg, params, tokens, dense = paged_case
        prefill, _, verify, kv = _paged(cfg, paged_jits)
        ta = jnp.asarray(self.TABLE_A, jnp.int32)
        tb = jnp.asarray(self.TABLE_C, jnp.int32)
        _, kv = _prefill_chunks(prefill, params, cfg, tokens, ta, kv, 0, (14,))
        _, kv = _prefill_chunks(prefill, params, cfg, tokens, tb, kv, 0, (11,))
        before = np.array(kv["v"])
        logits, kv = verify(
            params,
            jnp.stack([tokens[14:17], tokens[11:14], jnp.zeros(3, jnp.int32),
                       jnp.zeros(3, jnp.int32)]),
            jnp.asarray([14, 11, 0, 0], jnp.int32),
            jnp.asarray([3, 2, 0, 0], jnp.int32),
            jnp.stack([ta, tb, jnp.zeros_like(ta), jnp.zeros_like(ta)]),
            kv, cfg,
        )
        for j in range(3):
            _close(logits[0, j], dense[14 + j])
        for j in range(2):
            _close(logits[1, j], dense[11 + j])
        changed = np.argwhere((np.asarray(kv["v"]) != before).any(axis=(0, 3)))
        written = {(int(ta[p // _BS]), p % _BS) for p in (14, 15, 16)} | {
            (int(tb[p // _BS]), p % _BS) for p in (11, 12)}
        assert {(b, o) for b, o in changed.tolist() if b} == written

    @pytest.mark.parametrize("tile", [4, 8], ids=["8-tiles", "4-tiles"])
    def test_key_loop_gives_what_one_shot_gives(self, paged_case, tile_keys, tile):
        """The loop over key tiles against the one-shot form on the same
        pool, to float32 rounding: a prompt's first, middle and last chunk
        (the last one ends in the table's last tile), then decode lanes of
        very different lengths beside padding lanes, then a verify step."""
        import jax.numpy as jnp
        import numpy as np

        cfg, params, tokens, dense = paged_case
        ta = jnp.asarray(self.TABLE_A, jnp.int32)
        tc = jnp.asarray(self.TABLE_C, jnp.int32)
        null = jnp.zeros_like(ta)
        got = {}
        for keys in (ONE_SHOT, tile):
            with tile_keys(keys) as jits:
                prefill, decode, verify, kv = _paged(cfg, jits)
                out, pos = [], 0
                for n in (9, 7, 13):
                    logits, kv = _prefill_chunks(
                        prefill, params, cfg, tokens, ta, kv, pos, (n,))
                    out.append(logits)
                    pos += n
                _, kv = _prefill_chunks(prefill, params, cfg, tokens, tc, kv, 0, (3,))
                logits, kv = decode(
                    params, jnp.asarray([0, tokens[29], 0, tokens[3]]),
                    jnp.asarray([0, 29, 0, 3], jnp.int32),
                    jnp.stack([null, ta, null, tc]), kv, cfg)
                out += [logits[1], logits[3]]
                logits, kv = verify(
                    params, jnp.stack([tokens[4:6], jnp.zeros(2, jnp.int32),
                                       tokens[30:32]]),
                    jnp.asarray([4, 0, 30], jnp.int32), jnp.asarray([1, 0, 2], jnp.int32),
                    jnp.stack([tc, null, ta]), kv, cfg)
                out += [logits[0, 0], logits[2]]
                got[keys] = [np.asarray(o) for o in out] + [
                    np.asarray(kv["k"])[:, 1:], np.asarray(kv["v"])[:, 1:]]
        for a, b in zip(got[ONE_SHOT], got[tile]):
            np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)
        _close(got[tile][2], dense[28])     # and both are the dense path's
        _close(got[tile][3], dense[29])
        _close(got[tile][6][1], dense[31])

    def test_key_loop_only_where_a_table_is_wider_than_a_tile(self):
        """Structure: a table of one tile lowers with no inner loop at all
        (the operations from before the loop: every program of a model
        whose tables fit a tile); a wider one with one loop a layer, whose
        trip count is a run-time value and not the table's width."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.models import gpt
        from ray_tpu.ops import paged_attention

        cfg = _tiny_cfg()
        params = jax.eval_shape(
            lambda k: gpt.init_params(k, cfg), jax.random.PRNGKey(0))
        kv = jax.eval_shape(lambda: gpt.init_paged_cache(cfg, 64, _BS))
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731

        def inner_loops(tile):
            with pytest.MonkeyPatch.context() as mp:
                mp.setattr(paged_attention, "_ATTN_TILE_KEYS", tile)
                jaxpr = jax.make_jaxpr(
                    lambda *a: gpt.decode_step_paged(*a, cfg)
                )(params, i32(4), i32(4), i32(4, 8), kv)
            (scan,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
            return [e for e in scan.params["jaxpr"].jaxpr.eqns
                    if e.primitive.name == "while"]

        assert paged_attention.paged_attn_tiling(8, _BS) == (8, 1)      # the module's own tile
        assert inner_loops(paged_attention._ATTN_TILE_KEYS) == []
        assert inner_loops(8 * _BS) == []
        assert len(inner_loops(TILED)) == 1

    def test_key_loop_stops_at_the_last_tile_a_lane_can_see(self, paged_case, tile_keys):
        """Behaviour of the bounds: with NaN in the V rows of every block
        past the tile that holds the longest real lane's position, the loop
        still gives the dense logits (0 x NaN would poison a form that
        computes over the whole table, as the one-shot form shows); the
        padding lanes take no part in the bounds."""
        import jax.numpy as jnp
        import numpy as np

        cfg, params, tokens, dense = paged_case
        ta = jnp.asarray(self.TABLE_A, jnp.int32)
        null = jnp.zeros_like(ta)
        tables = jnp.stack([null, ta, null, null])
        finite = {}
        for keys in (ONE_SHOT, TILED):
            with tile_keys(keys) as jits:
                prefill, decode, _, kv = _paged(cfg, jits)
                _, kv = _prefill_chunks(prefill, params, cfg, tokens, ta, kv, 0, (14,))
                kv["v"] = kv["v"].at[:, jnp.asarray(self.TABLE_A[4:])].set(jnp.nan)
                logits, kv = decode(
                    params, jnp.asarray([0, tokens[14], 0, 0]),
                    jnp.asarray([0, 14, 0, 0], jnp.int32), tables, kv, cfg)
                finite[keys] = bool(np.isfinite(np.asarray(logits[1])).all())
                if keys == TILED:
                    _close(logits[1], dense[14])
        assert finite == {ONE_SHOT: False, TILED: True}

    def test_host_counts_keys_with_the_programs_own_bounds(self):
        """`paged_attn_cover`, what the engine counts a dispatched program's
        attention by, at the module's own tile: the trips of the longest
        REAL lane over every lane of the bucket, against the padded tables;
        a table of one tile counts whole. The bounds are one function for
        `numpy` and `jax.numpy`."""
        import jax.numpy as jnp
        import numpy as np

        from ray_tpu.models import gpt
        from ray_tpu.ops import paged_attention
        from ray_tpu.ops.paged_attention import KEY_LOOP, paged_attn_trips

        def keys(form, width, block, pos, real):    # (run, padded) in a global layer
            return paged_attention.paged_attn_cover(form, (), width, block, pos, pos, real)[:2]

        T = paged_attention._ATTN_TILE_KEYS
        assert paged_attention.paged_attn_tiling(256, 64) == (T // 64, 256 * 64 // T)
        pos = np.asarray([9 * T - 24, 300, 0, 0])
        real = np.asarray([True, True, False, False])
        assert keys(KEY_LOOP, 256, 64, pos, real) == (4 * 9 * T, 4 * 256 * 64)
        assert keys(KEY_LOOP, 256, 64, pos[::-1], real) == (4 * T, 4 * 256 * 64)
        assert keys(KEY_LOOP, 256, 64, np.asarray([511]), True) == (T, 256 * 64)
        assert keys(paged_attention.ONE_SHOT, T // 64, 64, pos[:2] % T, True) == (2 * T, 2 * T)
        rng = np.random.default_rng(0)
        last = rng.integers(0, 16 * T, (50, 8))
        first = last - rng.integers(0, 600, (50, 8))
        real = rng.random((50, 8)) < 0.7
        for window in (gpt._NO_WINDOW, 4 * T, T + 5):
            for f, l, r in zip(first, last, real):
                want = paged_attn_trips(np, f, l, r, window, T, 16)
                got = paged_attn_trips(
                    jnp, jnp.asarray(f), jnp.asarray(l), jnp.asarray(r), window, T, 16)
                assert (np.asarray(got[0]) == want[0]).all() and int(got[1]) == want[1]
                # every key a real lane's queries may see lies inside the bounds
                lo = np.maximum(f - window + 1, 0) // T
                assert (want[0] <= lo)[r].all()
                assert ((l // T)[r] < (want[0] + want[1])[r]).all()

    @pytest.mark.parametrize("program", ["prefill", "decode", "verify"])
    def test_pool_is_the_layer_scans_carry(self, program, monkeypatch):
        """Structure: the layer scan carries the two pool arrays and has no
        xs or ys of the pool's size — as xs -> ys the device rewrote the
        whole pool in every program (PERF.md §6, PR 25). The key loop
        inside a layer reads the pool where it lies: nothing of the pool's
        size is among the values it carries from trip to trip."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.models import gpt
        from ray_tpu.ops import paged_attention

        monkeypatch.setattr(paged_attention, "_ATTN_TILE_KEYS", TILED)
        cfg = _tiny_cfg()
        params = jax.eval_shape(
            lambda k: gpt.init_params(k, cfg), jax.random.PRNGKey(0))
        kv = jax.eval_shape(lambda: gpt.init_paged_cache(cfg, 64, _BS))
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
        fn, args = {
            "prefill": (gpt.prefill_paged,
                        (params, i32(1, 16), i32(), i32(), i32(8), kv)),
            "decode": (gpt.decode_step_paged,
                       (params, i32(4), i32(4), i32(4, 8), kv)),
            "verify": (gpt.verify_step_paged,
                       (params, i32(4, 3), i32(4), i32(4), i32(4, 8), kv)),
        }[program]
        jaxpr = jax.make_jaxpr(lambda *a: fn(*a, cfg))(*args)
        scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
        assert len(scans) == 1, "one layer scan"
        (scan,) = scans
        nc, ncar = scan.params["num_consts"], scan.params["num_carry"]
        pool = kv["k"].shape
        carry = [v.aval.shape for v in scan.invars[nc:nc + ncar]]
        assert carry.count(pool) == 2, f"pool not carried: {carry}"
        assert [v.aval.shape for v in scan.outvars[:ncar]].count(pool) == 2
        streamed = scan.invars[nc + ncar:] + scan.outvars[ncar:]
        assert all(v.aval.size < kv["k"].size for v in streamed), (
            "a pool-sized array rides the scan as xs or ys"
        )
        (loop,) = [e for e in scan.params["jaxpr"].jaxpr.eqns
                   if e.primitive.name == "while"]
        consts = loop.params["cond_nconsts"] + loop.params["body_nconsts"]
        carried = loop.invars[consts:] + loop.outvars
        assert all(v.aval.size < kv["k"].size for v in carried), (
            "the key loop carries a pool-sized array"
        )
        assert [v.aval.shape for v in loop.invars[:consts]].count(pool) == 2

    def test_cow_copies_every_layers_rows(self, tiny_engine_parts):
        """Copy-on-write of a forked partial block on the physical pool:
        the fresh block gets the source's rows in every layer, K and V,
        and no other block changes."""
        import numpy as np

        cfg, params = tiny_engine_parts
        eng = _make_engine(cfg, params)
        toks = [9, 8, 7, 6, 5, 4]
        rid = eng.submit(toks, max_new_tokens=1)
        out = eng.stream(rid)
        _drive(eng)
        list(out)
        bm = eng.block_manager
        with eng._lock:
            bm.allocate_cached("parent", toks, 6)
            bm.register_computed("parent", toks, 6)
            src = bm.block_table("parent")[1]
            bm.fork("parent", "child")
            dst = bm.grow("child", 7)[1]
        assert dst != src
        rng = np.random.default_rng(0)
        eng.kv = {
            n: a.at[:, src].set(rng.standard_normal(a.shape[0:1] + a.shape[2:]))
            for n, a in eng.kv.items()
        }
        before = {n: np.asarray(a) for n, a in eng.kv.items()}
        eng._apply_cow()
        for n, a in eng.kv.items():
            a = np.asarray(a)
            np.testing.assert_array_equal(a[:, dst], before[n][:, src])
            assert np.abs(a[:, dst]).sum() > 0
            others = [b for b in range(a.shape[1]) if b != dst]
            np.testing.assert_array_equal(a[:, others], before[n][:, others])


@pytest.fixture(scope="module")
def v5e_chip():
    """One described (not attached) v5e device; only ever asked for inside a
    test of this file, so collection is the same in every xdist worker."""
    try:
        from jax.experimental import topologies

        topo = _within(60, lambda: topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"))
    except Exception as e:  # noqa: BLE001 — no TPU compiler here: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return topo.devices[0]


def _within(seconds, fn):
    """fn() on a daemon thread, with this test's own time limit: a compile
    that hangs fails here and does not hold the whole run."""
    res = {}

    def run():
        try:
            res["value"] = fn()
        except BaseException as e:  # noqa: BLE001 — re-raised below
            res["error"] = e

    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(seconds)
    if t.is_alive():
        raise TimeoutError(f"not done after {seconds} s")
    if "error" in res:
        raise res["error"]
    return res["value"]


def test_paged_programs_compile_for_v5e_without_a_pool_copy(v5e_chip):
    """Compile-only, for the chip's own compiler: at H*Dh = 256 (two lane
    tiles) the device keeps the pool row-major, so each of the three
    programs updates it in place: temporaries smaller than the pool and no
    pool-sized copy or transpose. With the pool [.., BS, Dh] as the scan's
    xs -> ys this read 1.5 x the pool and four relayout copies a layer."""
    import jax.numpy as jnp

    from ray_tpu.models.gpt import GPTConfig
    from scripts.paged_rehearse import rehearse

    cfg = GPTConfig(
        vocab_size=256, n_layers=2, d_model=64, n_heads=4, d_head=64,
        d_mlp=256, max_seq=256, attn_impl="ref", remat=False,
        dtype=jnp.bfloat16,
    )
    report = _within(240, lambda: rehearse(
        cfg, v5e_chip, 64, 16, lanes=4, width=4, chunk=16, spec=2))
    pool_bytes = report["pool_GiB"] * 2**30
    assert set(report["programs"]) == {
        "decode_step_paged", "prefill_paged", "verify_step_paged"}
    for name, prog in report["programs"].items():
        assert "refused" not in prog, (name, prog)
        assert prog["temp_GiB"] * 2**30 < pool_bytes, (name, prog)
        assert prog["alias_GiB"] * 2**30 >= pool_bytes, f"{name}: not donated"
        # every stacked weight of this preset is smaller than a layer's pool
        moved = [o for o in prog["pool_sized_ops"]
                 if o["MiB"] >= report["layer_pool_MiB"]
                 and ("copy" in o["op"] or "transpose" in o["op"])]
        assert not moved, (name, moved)


OURO_PROGRAMS = {
    "decode-1": "decode_step_paged@1", "decode-2": "decode_step_paged@2",
    "decode-4": "decode_step_paged", "decode-8": "decode_step_paged@8",
    "decode-16": "decode_step_paged@16", "chunk-256": "prefill_paged",
    "verify": "verify_step_paged",
}


@pytest.fixture(scope="module")
def ouro_rehearsal(v5e_chip):
    """`ouro-2.6b.reason`'s paged programs compiled once for the described
    chip, at the cell's pool (320 blocks of 16) and with the kernels the chip
    runs, as `scripts.paged_rehearse`'s `main` steers them."""
    from ray_tpu.models.gpt import CONFIGS
    from ray_tpu.ops import attention
    from scripts.paged_rehearse import config_program, rehearse

    model, overrides = config_program("ouro-2.6b")
    cfg = CONFIGS[model](**overrides, remat=False, remat_policy=None)
    on_tpu, attention._on_tpu = attention._on_tpu, lambda: True
    try:
        return cfg, _within(400, lambda: rehearse(
            cfg, v5e_chip, 320, 16, lanes=4, width=32, chunk=256, spec=4,
            decode_lanes=(1, 2, 8, 16)))
    finally:
        attention._on_tpu = on_tpu


@pytest.mark.parametrize("program", list(OURO_PROGRAMS))
def test_the_looped_models_programs_read_the_held_stack_as_it_lies(ouro_rehearsal, program):
    """Compile-only, at the published size: no paged program of `ouro-2.6b`
    moves a parameter before it computes with it. From the public form of the
    fused q/k/v stack, [48, 2048, 3, 16, 128], every one of them but the
    one-lane decode step rewrote all of it once a call (a `copy` of 1,152 MiB,
    1.126 GiB of temporaries; PERF.md §6, PR 50); from the form the engine
    holds (`gpt.hold_served`) none holds a `copy` or `transpose` of a layer
    of that stack or more."""
    cfg, report = ouro_rehearsal
    prog = report["programs"][OURO_PROGRAMS[program]]
    assert "refused" not in prog, prog
    assert prog["param_relayout_MiB"] == 0, prog
    assert prog["temp_GiB"] < 0.01, prog
    layer_MiB = cfg.d_model * 3 * cfg.n_heads * cfg.d_head * 2 / 2**20
    assert layer_MiB == 24 and report["layer_pool_MiB"] == 20
    moved = [o for o in prog["pool_sized_ops"]    # listed from half a layer's pool up
             if o["MiB"] >= layer_MiB and ("copy" in o["op"] or "transpose" in o["op"])]
    assert not moved, moved


def test_two_kinds_of_layer_compile_for_v5e_over_one_pool_in_place(v5e_chip):
    """Compile-only, at SmallThinker's published widths (one period of four
    layers, the whole vocabulary, a pool of 1,024 blocks of 64 tokens): the
    pool [layers a group, NB, BS, 512] is still the scan's in-place carry
    with a table a group and is not copied into the key loop, a 512-token
    chunk's scores are one tile's and not the lane's 256 blocks', and a
    decode step of a few lanes keeps the expert stacks whole (no copy of a
    layer's experts into the loop)."""
    from ray_tpu.models.gpt import CONFIGS, kv_layout
    from ray_tpu.ops.paged_attention import _ATTN_TILE_KEYS
    from scripts.paged_rehearse import rehearse

    cfg = CONFIGS["smallthinker-21b-a3b"](
        n_layers=4, rope_layout=(0, 1, 1, 1), sliding_window_layout=(0, 1, 1, 1),
        remat=False)
    assert kv_layout(cfg).per_group == 1 and len(kv_layout(cfg).windows) == 4
    report = _within(400, lambda: rehearse(
        cfg, v5e_chip, 1024, 64, lanes=4, width=256, chunk=512, spec=2))
    pool_bytes = report["pool_GiB"] * 2**30
    assert report["pool_shape"] == [1, 1024, 64, 512]
    for name, prog in report["programs"].items():
        assert "refused" not in prog, (name, prog)
        assert prog["alias_GiB"] * 2**30 >= pool_bytes, f"{name}: not donated"
        assert prog["temp_GiB"] < 0.25, (name, prog)
        moved = [o for o in prog["pool_sized_ops"]
                 if "copy" in o["op"] or "transpose" in o["op"]
                 or "64,2560,768]" in o["result"] or "64,768,2560]" in o["result"]]
        assert not moved, (name, moved)
    # a 512-token chunk's scores, [4 K/V heads, 7 x 512 queries, keys], are
    # one tile wide on every layer: with the whole 16,384-token table on a
    # global layer (0.9 GiB) and 73 blocks on a window layer this read 0.88
    # GiB of temporaries (PERF.md §6, PR 29)
    scores = {o["result"].split("{")[0]
              for o in report["programs"]["prefill_paged"]["pool_sized_ops"]
              if o["result"].startswith("f32[4,3584,")}
    assert scores == {f"f32[4,3584,{_ATTN_TILE_KEYS}]"}, scores


# ------------------------------------------------- serve data-plane wiring
@pytest.fixture
def serve_instance():
    ray_tpu.init(local_mode=True, ignore_reinit_error=True)
    serve.start()
    yield
    serve.shutdown()
    ray_tpu.shutdown()


class TestLLMDeployment:
    def test_short_beats_long_through_serve(self, serve_instance):
        """proxy-less data plane: handle → router → LLMDeployment replica.
        A short request submitted ~1s into a long decode completes first —
        the engine admits it at an iteration boundary while the long one is
        mid-generation (with @serve.batch it would wait out the whole long
        decode)."""
        app = serve.LLMDeployment.bind(
            model="gpt2-small",
            model_overrides=TINY,
            engine_options=dict(num_blocks=64, block_size=4, max_num_seqs=4),
        )
        handle = serve.run(app, name="llm", route_prefix="/llm", timeout_s=120)
        done = {}

        def call(name, prompt, n):
            out = handle.generate.remote(prompt, max_new_tokens=n).result(
                timeout_s=120
            )
            done[name] = (time.monotonic(), out)

        tl = threading.Thread(target=call, args=("long", [1] * 8, 40))
        tl.start()
        time.sleep(1.0)
        ts = threading.Thread(target=call, args=("short", [2] * 4, 3))
        ts.start()
        tl.join(120)
        ts.join(120)
        assert len(done["long"][1]["tokens"]) == 40
        assert len(done["short"][1]["tokens"]) == 3
        assert done["short"][0] < done["long"][0], (
            "short request did not finish first — no iteration-level admission"
        )
        stats = handle.engine_stats.remote().result(timeout_s=30)
        assert stats["total_finished"] == 2
        assert stats["kv_utilization"] == 0.0  # all blocks returned
        # Streaming plane on the same replica: one chunk per engine
        # iteration through handle.options(stream=True).
        chunks = list(
            handle.options(stream=True).generate_stream.remote(
                [3] * 4, max_new_tokens=5
            )
        )
        assert len(chunks) == 5
        serve.delete("llm")


# ------------------------------------------- host phases and request spans
IN_SPAN = ("sched_ns", "side_ns", "build_ns", "dispatch_ns", "fetch_ns",
           "sample_ns")


@pytest.fixture
def ring(monkeypatch):
    """A fresh flight ring that nothing drains: returns the spans of one
    name recorded since the test began."""
    from ray_tpu.util import flight

    monkeypatch.setenv("RAY_TPU_FLIGHT", "1")
    monkeypatch.setattr(flight, "flush", lambda: 0)   # the flusher's target
    flight._reset_for_tests()
    yield lambda name: [e for e in flight.recorder().snapshot()
                        if e["name"].startswith(name)]
    flight._reset_for_tests()


class TestEnginePhases:
    def test_step_record_carries_phases_that_sum_to_its_duration(self, ring):
        """Every `engine.step` record carries the seven phase attrs, the
        idle wait and the three gauges; the six phases inside the span
        account for its duration to within 2% (a model wide enough that a
        step is milliseconds: what is left over is interpreter time between
        two phases, microseconds a step)."""
        import jax

        from ray_tpu.models.gpt import init_params
        from ray_tpu.util import flight

        cfg = _tiny_cfg(vocab_size=2048, n_layers=6, d_model=256, n_heads=4,
                        d_head=64, d_mlp=1024)
        eng = _make_engine(cfg, init_params(jax.random.PRNGKey(0), cfg))
        # the first pass compiles, the second (prefix-cache hits: other
        # chunk shapes) compiles again; the third is steady and is judged
        for _ in range(3):
            for i in range(4):
                eng.submit([1 + i] * 8, max_new_tokens=12)
            first = len(ring("engine.step"))
            _drive(eng)
        steps = ring("engine.step")[first:]
        assert len(steps) >= 12
        for ev in steps:
            a = ev["args"]
            assert set(flight.SERVE_STEP_PHASES) | {
                "waited_ns", "queue_depth", "running", "kv_util",
                "prefills", "decodes", "chained", "tokens", "attn_keys_run",
                "attn_keys_padded"} <= set(a)
            # tables of one tile: the programs compute over all they gather
            # (a step that only read the ids of the one before it ran none)
            assert a["attn_keys_run"] == a["attn_keys_padded"]
            assert (a["attn_keys_run"] > 0) == bool(a["prefills"] or a["decodes"])
            assert a["chained"] in (0, 1) and a["chained"] <= a["decodes"]
            assert all(isinstance(a[k], int) and a[k] >= 0
                       for k in flight.SERVE_STEP_PHASES)
            assert a["waited_ns"] == 0          # driven by step(): no _loop
            assert a["lane"] == "serve/engine-mixed"
            assert sum(a[k] for k in IN_SPAN) <= ev["dur"] * 1e9 + 1e3
        # the median step, so that one step descheduled between two phases
        # on a loaded machine does not decide it
        shares = sorted(sum(ev["args"][k] for k in IN_SPAN) / (ev["dur"] * 1e9)
                        for ev in steps)
        assert 0.98 <= shares[len(shares) // 2] <= 1.0, shares
        last = steps[-1]["args"]
        assert last["queue_depth"] == 0 and last["export_ns"] > 0
        total = eng.stats()
        assert total["attn_keys_padded"] >= sum(
            ev["args"]["attn_keys_padded"] for ev in steps)
        assert total["attn_keys_run"] == total["attn_keys_padded"]

    def test_idle_wait_is_carried_into_the_next_step_record(
        self, tiny_engine_parts, ring
    ):
        cfg, params = tiny_engine_parts
        eng = _make_engine(cfg, params)
        eng.start()
        try:
            assert eng.generate([1, 2, 3], 2) and eng.generate([1, 2, 3], 2)
            time.sleep(0.35)                     # the driver thread idles
            assert len(eng.generate([4, 5, 6], 3)) == 3
        finally:
            eng.shutdown()
        waits = [e["args"]["waited_ns"] for e in ring("engine.step")]
        # the wait shows on the first step after it, and only there
        assert max(waits) >= 0.3e9
        after = waits[waits.index(max(waits)) + 1:]
        assert after and all(w < 0.05e9 for w in after)

    def test_request_spans_go_through_the_ring_in_order_after_preemption(
        self, tiny_engine_parts, ring, monkeypatch
    ):
        """The five request spans of a traced request land in the flight
        ring with their names, attrs and trace id, submit <= admit <= first
        <= end, also when the request was preempted and recomputed; an
        untraced request records none."""
        from ray_tpu.util import tracing

        cfg, params = tiny_engine_parts
        eng = _make_engine(cfg, params, num_blocks=9, block_size=4)
        ids = iter(["t-a", "t-b", None])
        monkeypatch.setattr(tracing, "get_trace_id", lambda: next(ids))
        rids = [eng.submit([3] * 8, max_new_tokens=16) for _ in range(3)]
        _drive(eng, max_steps=500)
        assert eng.total_preemptions > 0
        spans = ring("engine.")
        names = ["engine.queue_wait", "engine.admission", "engine.prefill",
                 "engine.first_token", "engine.completion"]
        for tid, rid in zip(("t-a", "t-b"), rids):
            mine = {e["name"]: e for e in spans if e["trace"] == tid}
            assert list(mine) == names
            for e in mine.values():
                assert e["args"]["request_id"] == rid
                assert e["args"]["tokens"] == 16
                assert e["args"]["lane"] == "serve/engine-mixed/requests"
            assert mine["engine.completion"]["args"]["finish_reason"] == "length"
            q, p, f, c = (mine[n] for n in (names[0], *names[2:]))
            eps = 1e-6
            assert q["ts"] + q["dur"] <= p["ts"] + eps          # submit <= admit
            assert abs(mine["engine.admission"]["ts"] - p["ts"]) < eps
            assert p["ts"] + p["dur"] <= f["ts"] + eps          # admit <= first
            assert abs(f["ts"] - c["ts"]) < eps and c["dur"] > 0  # first <= end
        traced = {e["trace"] for e in spans if e["name"] != "engine.step"}
        assert traced == {"t-a", "t-b"}

    def test_driver_thread_sends_no_trace_event(
        self, tiny_engine_parts, monkeypatch
    ):
        """A finished traced request costs the decode thread no control-
        plane send: its spans leave the process on the ring's flusher (here:
        an explicit flush from this thread), never from `llm-engine`."""
        import types

        from ray_tpu.core import api
        from ray_tpu.util import flight, tracing

        sends = []

        class Backend:
            def record_trace_event(self, events):
                sends.append((threading.current_thread().name,
                              [e["name"] for e in events]))

        rt = types.SimpleNamespace(
            backend=Backend(), _context=types.SimpleNamespace(trace_id="t-x"))
        monkeypatch.setenv("RAY_TPU_FLIGHT", "1")
        monkeypatch.setattr(api, "_runtime_or_attach", lambda: rt)
        flight._reset_for_tests()
        assert tracing.get_trace_id() == "t-x"
        cfg, params = tiny_engine_parts
        eng = _make_engine(cfg, params)
        eng.start()
        try:
            assert len(eng.generate([1, 2, 3], 4)) == 4
        finally:
            eng.shutdown()
        flight.flush()      # what the flusher thread does every half second
        flight._reset_for_tests()
        shipped = [n for _, names in sends for n in names]
        assert "engine.completion" in shipped and "engine.step" in shipped
        assert all(thread != "llm-engine" for thread, _ in sends), sends

    def test_step_thread_sends_no_metric(self, tiny_engine_parts, metric_sink):
        """A step's gauges and counters cost the stepping thread no control-
        plane send (they were six blocking round trips a step): they fold
        into the process's pending table and leave on `metrics-flusher`.
        One flush later the backend holds the counter's whole sum and each
        gauge's last value, and `stats()` carries the process's totals."""
        from ray_tpu.util import metrics

        cfg, params = tiny_engine_parts
        eng = _make_engine(cfg, params)
        r0, s0 = (eng.stats()[k] for k in ("metric_records", "metric_sends"))
        eng.submit([1, 2, 3, 4, 5], 6)
        eng.submit([7, 8, 9], 4)
        me = threading.current_thread().name
        steps = []
        while eng.scheduler.has_work():
            steps.append(eng.step())
            assert len(steps) < 50, "engine did not drain"
        assert not [m for m in metric_sink.sent if m[0] == me], metric_sink.sent
        metrics.flush()
        shipped = sum(m[3] for m in metric_sink.series("serve_engine_tokens_total"))
        assert shipped == eng.total_tokens == 10
        for name, key in (("serve_engine_queue_depth", "queue_depth"),
                          ("serve_engine_running_seqs", "running"),
                          ("serve_engine_kv_utilization", "kv_utilization"),
                          ("serve_engine_tokens_per_s", "tokens_per_s")):
            assert metric_sink.series(name)[-1][3] == steps[-1][key], name
        st = eng.stats()
        assert type(st["metric_records"]) is int
        assert type(st["metric_sends"]) is int
        assert st["metric_records"] - r0 >= 6 * len(steps)
        assert st["metric_sends"] - s0 == len(metric_sink.sent)

    def test_phases_are_annotations_in_a_profiler_session(
        self, tiny_engine_parts, tmp_path
    ):
        """Under a `jax.profiler` session the same phases are on the
        profiler's clock: `engine.*` annotations in the host plane of the
        `.xplane.pb`, the phases nested in `engine.step`."""
        import glob

        import jax
        from jax.profiler import ProfileData

        cfg, params = tiny_engine_parts
        eng = _make_engine(cfg, params)
        eng.submit([1, 2, 3, 4], max_new_tokens=3)
        _drive(eng)                                   # compiled
        eng.submit([5, 6, 7, 8], max_new_tokens=3)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            _drive(eng)
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
        events = [
            (e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:CPU")
            for line in plane.lines for e in line.events
            if e.name.startswith("engine.")]
        names = {n for n, _, _ in events}
        assert {"engine.step", "engine.schedule", "engine.side_work",
                "engine.build", "engine.dispatch", "engine.fetch_ids",
                "engine.sample", "engine.export_metrics"} <= names, names
        steps = [(s, e) for n, s, e in events if n == "engine.step"]
        # three dispatch a program each; the fourth reads the third's id
        assert len(steps) == 4
        for n, s, e in events:
            if n != "engine.step":
                assert any(s0 <= s and e <= e0 for s0, e0 in steps), n


# ------------------------------------------------------------------ books
COUNT_BOOKS = ("steps", "steps_chunk", "steps_decode", "steps_decode_only",
               "decode_lanes", "decode_bucket_lanes",
               "decode_lanes_beside_chunk", "prefill_tokens",
               "prefill_tokens_padded")
STREAM_BOOKS = ("stream_tokens", "stream_wake_ns", "stream_send_ns",
                "stream_behind")


def _booked_run(cfg, params):
    """Six prompts of 21 tokens in chunks of 8 (the last one 5, padded to
    8) over four lanes, stepped by hand: the same course of steps whatever
    the recorder does. Returns the engine's stats."""
    eng = _make_engine(cfg, params, prefill_chunk_tokens=8)
    rids = [eng.submit([1 + i] * 21, max_new_tokens=40) for i in range(6)]
    _drive(eng)
    assert all(len(list(eng.stream(rid))) == 40 for rid in rids)
    return eng.stats()


class TestEngineBooks:
    @pytest.fixture(scope="class")
    def booked_counts(self, tiny_engine_parts):
        """The count books of the run above, taken once (the first run
        also compiles its programs, for the runs judged after it)."""
        st = _booked_run(*tiny_engine_parts)
        return {k: st[k] for k in COUNT_BOOKS}

    @pytest.mark.parametrize("recorder", ["on", "off", "cap64"])
    def test_books_are_kept_whatever_the_recorder_does(
        self, tiny_engine_parts, booked_counts, ring, monkeypatch, recorder
    ):
        """The books are the step records' values summed where the records
        are made: with the recorder on they equal the sums over the run's
        `engine.step` records; with `RAY_TPU_FLIGHT=0` (no record) and
        with a ring of 64 (the span sums fall short) they are the same
        counts. The identities hold in each."""
        from ray_tpu.util import flight

        if recorder == "off":
            monkeypatch.setenv("RAY_TPU_FLIGHT", "0")
        elif recorder == "cap64":
            monkeypatch.setenv("RAY_TPU_FLIGHT_CAP", "64")
            flight._reset_for_tests()
        st = _booked_run(*tiny_engine_parts)
        books = ("steps_slow", "step_ns", "step_chunk_ns",
                 "step_decode_only_ns", "host_ns", "slow_ns", "loop_ns",
                 "waited_ns", *flight.SERVE_STEP_PHASES, *COUNT_BOOKS,
                 *STREAM_BOOKS, "gc_ns", "gc_collections")
        assert all(type(st[k]) is int and st[k] >= 0 for k in books), st
        assert {k: st[k] for k in COUNT_BOOKS} == booked_counts
        assert st["steps"] > 64 and st["steps_chunk"] == 18
        assert st["prefill_tokens"] == 6 * 21
        assert st["prefill_tokens_padded"] == 6 * 24
        assert st["stream_tokens"] == st["total_tokens"] == 6 * 40
        # the identities
        assert st["steps_chunk"] + st["steps_decode_only"] <= st["steps"]
        assert st["steps_decode_only"] <= st["steps_decode"]
        assert st["decode_lanes_beside_chunk"] <= st["decode_lanes"]
        assert st["decode_lanes"] <= st["decode_bucket_lanes"] <= 4 * st["steps_decode"]
        assert st["prefill_tokens"] <= st["prefill_tokens_padded"]
        assert sum(st[k] for k in IN_SPAN) <= st["step_ns"]
        assert st["host_ns"] == st["step_ns"] - st["fetch_ns"]
        assert st["step_chunk_ns"] + st["step_decode_only_ns"] <= st["step_ns"]
        assert st["loop_ns"] == st["waited_ns"] == 0    # stepped by hand
        # against the records
        steps = [e["args"] for e in ring("engine.step")]
        assert len(steps) == {"on": st["steps"], "off": 0, "cap64": 64}[recorder]
        sums = {
            "steps": len(steps),
            "steps_chunk": sum(1 for a in steps if a["prefills"]),
            "steps_decode": sum(1 for a in steps if a["decodes"]),
            "steps_decode_only": sum(
                1 for a in steps if a["decodes"] and not a["prefills"]),
            "decode_lanes": sum(a["decodes"] for a in steps),
            "decode_lanes_beside_chunk": sum(
                a["decodes"] for a in steps if a["prefills"]),
            **{k: sum(a[k] for a in steps)
               for k in ("waited_ns", *flight.SERVE_STEP_PHASES)},
        }
        if recorder == "on":
            assert sums == {k: st[k] for k in sums}
            spans = 1e9 * sum(e["dur"] for e in ring("engine.step"))
            assert abs(spans - st["step_ns"]) < 1e-6 * st["step_ns"]
        elif recorder == "cap64":
            assert all(sums[k] < st[k] for k in ("steps", "decode_lanes", "build_ns"))

    @pytest.mark.parametrize("kind", ["dense", "experts", "looped"])
    def test_gauge_books_equal_the_means_over_the_step_records(self, ring, kind):
        """What rides the step record as a FLOAT has integer books (ISSUE 53):
        on a tiny dense model, an expert model and a looped one, stepped by
        hand with the recorder on, each ratio of the new books equals the
        same mean taken over the `engine.step` records: `experts_touched`,
        `expert_load_max` and `exit_step_mean` over the steps that read
        them, `kv_util` over TIME (as `_tick_slots` weights it exactly, as
        `readers.span_time_mean` weights it nearly: the two differ by
        which end of a step its own duration is booked at). And the span is
        whole: `between_ns` + the six phases = `step_ns`, to the
        nanosecond."""
        import jax
        from benchmarks import readers
        from ray_tpu.models.gpt import init_params
        from ray_tpu.util import flight

        cfg = _tiny_cfg(**{
            "dense": {},
            "experts": dict(mlp_type="moe", moe_routing="dropless",
                            moe_experts=4, moe_top_k=2),
            "looped": dict(ut_steps=3)}[kind])
        params = jax.tree_util.tree_map(
            lambda a: a * 3.0, init_params(jax.random.PRNGKey(3), cfg))

        def run():
            eng = _make_engine(cfg, params, prefill_chunk_tokens=8)
            eng.step()                  # no work: the books' clock starts here
            st0, w0 = eng.stats(), flight.recorder().wall(flight.now_ns())
            rids = [eng.submit([1 + i] * 21, max_new_tokens=12 + 5 * i)
                    for i in range(6)]
            _drive(eng)
            w1 = flight.recorder().wall(flight.now_ns())
            assert [len(list(eng.stream(r))) for r in rids] == [
                12 + 5 * i for i in range(6)]
            st1 = eng.stats()
            return {k: st1[k] - st0[k] for k in st1 if type(st1[k]) is int}, w0, w1

        run()                           # compiles the programs
        flight._reset_for_tests()
        d, w0, w1 = run()
        steps = ring("engine.step")
        assert len(steps) == d["steps"] == d["flight_spans_recorded"] > 20
        assert d["flight_spans_dropped"] == 0
        # the span, whole
        assert d["between_ns"] + sum(d[k] for k in IN_SPAN) == d["step_ns"]
        assert 0 <= d["between_ns"] < 0.2 * d["step_ns"]
        # the gauges that come back with the ids
        decodes = [e["args"] for e in steps if e["args"]["decodes"]]
        mean = lambda key: sum(a[key] for a in decodes) / len(decodes)
        ratio = lambda num, den, scale: scale * d[num] / d[den]
        assert d["decode_lanes"] / d["steps_decode"] == mean("decodes")
        if kind == "experts":
            assert d["moe_steps_read"] == len(decodes)
            assert ratio("moe_experts_touched_milli", "moe_steps_read", 1e-3) == (
                pytest.approx(mean("experts_touched"), abs=6e-4))
            assert ratio("moe_load_max_ppm", "moe_steps_read", 1e-6) == (
                pytest.approx(mean("expert_load_max"), abs=6e-7))
            assert 2.0 <= mean("experts_touched") <= 4.0
        else:
            assert d["moe_steps_read"] == d["moe_experts_touched_milli"] == 0
        if kind == "looped":
            assert d["ut_steps_read"] == len(decodes)
            assert ratio("ut_exit_step_milli", "ut_steps_read", 1e-3) == (
                pytest.approx(mean("exit_step_mean"), abs=6e-4))
            assert 1.0 < mean("exit_step_mean") < 3.0
        else:
            assert d["ut_steps_read"] == d["ut_exit_step_milli"] == 0
        # the pool over time: every step of this run wrote a record, so the
        # ticks are the records' own edges
        ends = [w0] + [e["ts"] + e["dur"] for e in steps]
        util = [0.0] + [e["args"]["kv_util"] for e in steps]
        held = sum(util[i] * (e["ts"] - ends[i]) + util[i + 1] * e["dur"]
                   for i, e in enumerate(steps))
        books = d["kv_block_held_ns"] / d["kv_block_cap_ns"]
        assert d["kv_block_cap_ns"] % 63 == 0      # 64 blocks less the null block
        assert books == pytest.approx(held / (ends[-1] - w0), rel=0.05)
        obs = {"window": {"t0": w0, "seconds": w1 - w0}, "spans": steps}
        twin = readers.span_time_mean(obs, readers.reader_spec("kv_util_mean"))
        assert 100.0 * books == pytest.approx(twin, rel=0.15) and 5.0 < twin < 60.0

    def test_stream_tokens_are_what_eight_concurrent_consumers_took(
        self, tiny_engine_parts
    ):
        """Eight consumer threads drain their streams at once, under a
        short switch interval, while a ninth reads `stats()`: the delivery
        books lose no token (`stream_tokens` is what the consumers took,
        one of them having walked away early, one having stopped and come
        back), a reading never goes back, and the loop's time covers its
        waits and its steps."""
        import sys

        cfg, params = tiny_engine_parts
        eng = _make_engine(cfg, params, prefill_chunk_tokens=8)
        took, readings, errors = [0] * 8, [], []

        def consume(i):
            try:
                out = eng.stream(eng.submit([1 + i] * 21, max_new_tokens=40))
                for _tok in out:
                    took[i] += 1
                    if i < 2 and took[i] == 5:
                        break               # the generator is closed here
                if i == 1:                  # and the second comes back for
                    took[i] += len(list(out))   # the rest: counted once
            except Exception as e:  # noqa: BLE001 — reported below
                errors.append(e)

        def read():
            while any(t.is_alive() for t in threads):
                readings.append(eng.stats()["stream_tokens"])
                time.sleep(0.002)

        threads = [threading.Thread(target=consume, args=(i,)) for i in range(8)]
        reader = threading.Thread(target=read)
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            eng.start()
            for t in threads + [reader]:
                t.start()
            for t in threads + [reader]:
                t.join(timeout=120)
            assert not any(t.is_alive() for t in threads + [reader])
        finally:
            sys.setswitchinterval(interval)
            eng.shutdown()
        assert not errors, errors
        assert took == [5] + [40] * 7
        st = eng.stats()
        assert st["stream_tokens"] == sum(took)
        assert st["total_tokens"] >= 5 + 7 * 40
        assert readings == sorted(readings) and readings[-1] <= sum(took)
        assert 0 <= st["stream_behind"] <= st["stream_tokens"]
        assert st["stream_wake_ns"] > 0 and st["stream_send_ns"] > 0
        assert not eng._delivery._open      # every stream folded its sums
        assert 0 < st["waited_ns"] + st["step_ns"] + st["export_ns"] <= st["loop_ns"]

    def test_a_slow_host_part_is_booked_and_leaves_one_stall_span(
        self, tiny_engine_parts, ring
    ):
        """`_book_step` on spans made by hand: a step is judged by the
        host's part of its span (the span less `fetch_ns`) against ONE
        running mean, from the 65th step on. A compile among the first
        steps and a long wait for the device are no stall; 122 ms of host
        against a mean of 2 is the one stall: `steps_slow` 1, `slow_ns` its
        excess over the mean, ONE `engine.stall` span over the step's own
        span with its phases, the GC inside it, the bucket and the load;
        the stall moves the mean as a step at the edge of slow would."""
        from ray_tpu.serve.engine.scheduler import SchedulerOutput
        from ray_tpu.util import flight

        eng = _make_engine(*tiny_engine_parts)
        ms = 1_000_000
        load = {"bucket": 4, "queue_depth": 2, "running": 3, "gc_ns": 0}
        chunk = SchedulerOutput([object()], [1, 2, 3], [], 4, 8)
        plain = SchedulerOutput([], [1, 2, 3], [], 4, 8)

        def book(out, host_ms, fetch_ms, **extra):
            times = {"waited_ns": 0, **dict.fromkeys(flight.SERVE_STEP_PHASES, 0),
                     "build_ns": host_ms * ms, "fetch_ns": fetch_ms * ms}
            eng._book_step(out, 5 * ms, (5 + host_ms + fetch_ms) * ms, times,
                           {**load, **extra})

        book(plain, 130, 10)                # a compile: the mean is young
        for _ in range(63):
            book(plain, 0, 10)
        assert eng._host_mean[0] == 64
        assert eng._host_mean[1] == pytest.approx(130 * ms / 64, rel=1e-4)
        for _ in range(8):                  # chunks run 70 ms on the device
            book(chunk, 2, 70)
        book(plain, 2, 498)                 # the device kept it waiting
        assert eng.stats()["steps_slow"] == 0 and not ring("engine.stall")
        eng._host_mean[1] = 2 * ms
        book(plain, 122, 10, gc_ns=3 * ms)
        st = eng.stats()
        assert (st["steps"], st["steps_chunk"], st["steps_decode_only"]) == (74, 8, 66)
        assert st["step_chunk_ns"] == 8 * 72 * ms
        assert st["host_ns"] == st["step_ns"] - st["fetch_ns"] == (130 + 18 + 122) * ms
        assert st["steps_slow"] == 1 and st["slow_ns"] == 120 * ms
        assert eng._host_mean == [74, 2 * ms + 6 * ms // 64]
        (stall,) = ring("engine.stall")
        a = stall["args"]
        assert stall["dur"] == pytest.approx(0.132) and a["lane"] == "serve/engine-mixed"
        assert (a["mean_ns"], a["build_ns"], a["fetch_ns"]) == (2 * ms, 122 * ms, 10 * ms)
        assert set(a) == {"lane", "mean_ns", "gc_ns", "bucket", "queue_depth",
                          "running", *flight.SERVE_STEP_PHASES[:-1]}
        assert (a["gc_ns"], a["bucket"], a["queue_depth"], a["running"]) == (3 * ms, 4, 2, 3)
        (named,) = flight.serve_report(ring("engine."))["stalls"]
        assert named["phase"] == "build" and named["host_ms"] == pytest.approx(122.0)

    def test_an_injected_sleep_in_one_step_leaves_its_stall_span(
        self, tiny_engine_parts, ring, monkeypatch
    ):
        """Seventy decode-only steps with 10 ms of sleep in each (so that a
        loaded machine's few milliseconds are no stall), one of them 0.4 s
        longer: the engine books it and writes its `engine.stall` span over
        that step's own span, with the decode bucket and the load. (Another
        stall is the machine's, not the engine's: the test finds its own by
        its length.)"""
        cfg, params = tiny_engine_parts
        warm = _make_engine(cfg, params)
        warm.submit([3] * 6, max_new_tokens=3)
        _drive(warm)                            # the programs are compiled
        eng = _make_engine(cfg, params)
        run_decode, n = eng._run_decode, [0]

        def slowed(out):
            n[0] += 1
            time.sleep(0.41 if n[0] == 68 else 0.01)
            return run_decode(out)

        monkeypatch.setattr(eng, "_run_decode", slowed)
        eng.submit([3] * 6, max_new_tokens=75)
        _drive(eng)
        st = eng.stats()
        assert st["steps_decode_only"] >= 70 and st["steps_slow"] >= 1
        assert st["slow_ns"] > 0.35e9
        (stall,) = [e for e in ring("engine.stall") if e["dur"] > 0.4]
        a = stall["args"]
        assert a["bucket"] == 1 and a["running"] == 1 and a["queue_depth"] == 0
        assert 0.005e9 < a["mean_ns"] < 0.1e9 and a["gc_ns"] >= 0
        twin = [e for e in ring("engine.step") if e["ts"] == stall["ts"]]
        assert len(twin) == 1 and twin[0]["dur"] == stall["dur"]

    def test_a_forced_collection_moves_gc_ns(self, tiny_engine_parts):
        """The engines of a process share ONE `gc.callbacks` hook, there
        while any of them runs: a forced collection adds its pause to
        `gc_ns`, and `shutdown` (twice: once counts) gives the hook up."""
        import gc

        from ray_tpu.serve.engine import engine as engine_mod

        cfg, params = tiny_engine_parts
        pauses = engine_mod._GC
        held = pauses._engines
        eng = _make_engine(cfg, params)
        assert pauses._engines == held + 1
        assert gc.callbacks.count(pauses._hook) == 1
        before = eng.stats()
        gc.collect()
        after = eng.stats()
        assert after["gc_collections"] > before["gc_collections"]
        assert after["gc_ns"] > before["gc_ns"]
        eng.shutdown()
        eng.shutdown()
        assert pauses._engines == held
        assert gc.callbacks.count(pauses._hook) == (1 if held else 0)


@pytest.mark.parametrize("lanes, tokens", [(1, 256), (64, 1)])
def test_the_selective_scan_kernel_compiles_for_v5e_at_the_published_width(
        v5e_chip, lanes, tokens):
    """Compile-only: `ops/ssm.py`'s `ssm_scan` at AI21-Jamba2-3B's inner width
    (5120 channels x 16 states) for a 256-token chunk and for a decode step
    of 64 lanes: what the chip's compiler refuses of a kernel (tiling, VMEM,
    an SMEM block) shows here, and the state comes back in place."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.ops import ssm

    one_chip = SingleDeviceSharding(v5e_chip)
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    tiled = (lanes, tokens, 40, 128)
    compiled = _within(120, lambda: jax.jit(ssm._scan_pallas, donate_argnums=5).lower(
        f32(*tiled), f32(*tiled), f32(16, 40, 128), f32(lanes, 1, tokens * 16),
        f32(lanes, 1, tokens * 16), f32(lanes, 16, 40, 128)).compile())
    assert "ssm_scan" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == lanes * 16 * 5120 * 4     # the state, in place
    assert mem.temp_size_in_bytes < 1 << 20


CHUNK_KERNEL_SHAPES = {
    # K/V heads, folded query rows, key row, value row (None: inside the key
    # row, its first 512 columns), lanes
    "ax-k1-latent-64x512": (1, 64 * 512, 640, None, 1),
    "smallthinker-grouped-7x512": (4, 7 * 512, 128, 128, 1),
    "ouro-multi-head-verify-3": (16, 3, 128, 128, 4),
}


@pytest.mark.parametrize("shape", list(CHUNK_KERNEL_SHAPES))
def test_the_chunk_attention_kernel_compiles_for_v5e_at_the_cells_widths(v5e_chip, shape):
    """Compile-only: `ops/paged_attention.py`'s `paged_chunk_attention` over a
    table of 4 tiles of 1,024 keys at the widths the serving cells bring it
    (a latent chunk's 32,768 folded rows of 640, a grouped chunk's 3,584 of
    128, a verify step's 3 rows a head padded to a sublane tile): its tiles
    fit the chip's fast memory, and the scores are no temporary of the
    program (the plain loop's were 128 MiB a tile for the first)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.ops import paged_attention
    from ray_tpu.ops.paged_attention import _ATTN_TILE_KEYS, NO_WINDOW

    heads, rows, key_row, value_row, lanes = CHUNK_KERNEL_SHAPES[shape]
    one_chip = SingleDeviceSharding(v5e_chip)
    arr = lambda dt, *s: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)  # noqa: E731
    keys = 4 * _ATTN_TILE_KEYS
    dv = value_row or 512

    def run(q, k, v, qpos, first, trips):
        return paged_attention.paged_chunk_attention(
            q, k, v, qpos, first, trips, NO_WINDOW, tile_keys=_ATTN_TILE_KEYS,
            dv=dv, sm_scale=0.1)

    compiled = _within(120, lambda: jax.jit(run).lower(
        arr(jnp.bfloat16, lanes, heads, rows, key_row),
        arr(jnp.bfloat16, lanes, keys, heads * key_row),
        None if value_row is None else arr(jnp.bfloat16, lanes, keys, heads * dv),
        arr(jnp.int32, lanes, rows), arr(jnp.int32, lanes), arr(jnp.int32)).compile())
    assert paged_attention.PAGED_CHUNK_KERNEL in compiled.as_text()
    out = lanes * heads * rows * dv * 2
    assert compiled.memory_analysis().temp_size_in_bytes <= 2 * out + (1 << 20)


# One device's call of a layer in the two train cells: (batch, heads, S, Dh)
FLASH_KERNEL_SHAPES = {
    "gpt2-large.train-13x20-heads-of-64": (13, 20, 1024, 64),
    "gptj-6b.train-fsdp4-2x16-heads-of-256": (2, 16, 2048, 256),
}


@pytest.mark.parametrize("shape", list(FLASH_KERNEL_SHAPES))
def test_the_flash_kernels_compile_for_v5e_at_the_train_cells_shapes(v5e_chip, shape,
                                                                     monkeypatch):
    """Compile-only: a layer's attention as `models/gpt.py` `_block` says it
    (the projections, the transposes to and from `attend`'s [B, H, S, Dh],
    the public wrapper the train step takes), forward and gradient, causal,
    bfloat16, default blocks, at the shapes the two train cells bring, by
    the chip's compiler. The unrolled walk over sub-tiles lowers at both
    head widths (a pair of heads of 64 a grid step; a 2 x 2 grid a head of
    256 at 2,048) and each kernel is there once under the name the
    benchmark's `flash_*_roofline` finds it by. At heads of 64 the kernels
    read q, k, v and dO and write o, dq, dk and dv as the flat projections
    lay them, and NO array of a layer's attention is copied or transposed on
    its way (eleven were, PERF.md §6, PR 49); heads of 256 stay a row each
    (`ops/attention.py` `heads_a_step`) and keep their relayouts."""
    import math
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.models import gpt
    from ray_tpu.ops import attention

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    B, H, S, D = FLASH_KERNEL_SHAPES[shape]
    E = H * D
    one_chip = SingleDeviceSharding(v5e_chip)
    arr = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)  # noqa: E731

    def layer(h, p):
        q, k, v = (a.transpose(0, 2, 1, 3) for a in gpt._project_qkv(None, p, h))
        attn = attention.flash_attention_with_stats(q, k, v, causal=True)[0]
        return gpt._merge_heads(attn, p["w_o"])

    def run(h, p, g):
        out, vjp = jax.vjp(layer, h, p)
        return out, vjp(g)

    p = {"w_qkv": arr(E, 3, H, D), "b_qkv": arr(3, H, D), "w_o": arr(H, D, E)}
    text = _within(240, lambda: jax.jit(run).lower(
        arr(B, S, E), p, arr(B, S, E)).compile()).as_text()
    assert attention.flash_kernels_in(text) == dict.fromkeys(attention.FLASH_KERNELS, 1)
    if not attention.heads_a_step(H, D):
        return
    moved = [line.strip()[:160] for line in text.splitlines()
             for m in [re.search(r"= (?:bf16|f32)\[([\d,]+)\]\S* (?:copy|transpose)\(", line)]
             if m and math.prod(map(int, m.group(1).split(","))) == B * H * S * D]
    assert not moved, moved


# (K/V heads, query heads a K/V head, key row, value row or None, block, table, lanes)
DECODE_KERNEL_SHAPES = {
    "ouro-R1-16-heads-blocks-of-16": (16, 1, 128, 128, 16, 128, 16),
    "smallthinker-R7-blocks-of-64": (4, 7, 128, 128, 64, 256, 16),
    "laguna-R8-blocks-of-64": (8, 8, 128, 128, 64, 256, 32),
    "jamba-R20-blocks-of-128": (1, 20, 128, 128, 128, 16, 64),
    "ax-k1-latent-R64-rows-of-640": (1, 64, 640, None, 64, 256, 16),
}


@pytest.mark.parametrize("shape", list(DECODE_KERNEL_SHAPES))
def test_the_decode_attention_kernel_compiles_for_v5e_at_the_cells_widths(v5e_chip, shape):
    """Compile-only: `ops/paged_attention.py`'s `paged_decode_attention` at the
    widths the serving cells bring it, in bfloat16, by the chip's compiler:
    its two buffers of a DMA group fit the chip's fast memory, the pool is an
    operand as it lies (no copy of it among the temporaries)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.ops import paged_attention
    from ray_tpu.ops.paged_attention import NO_WINDOW

    heads, R, key_row, value_row, bs, width, lanes = DECODE_KERNEL_SHAPES[shape]
    one_chip = SingleDeviceSharding(v5e_chip)
    arr = lambda dt, *s: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)  # noqa: E731
    dv = value_row or 512
    pool = (3, 512, bs)

    def run(q, k, v, slot, table, pos, real):
        return paged_attention.paged_decode_attention(
            q, k, v, slot, table, pos, real, NO_WINDOW, dv=dv, sm_scale=0.1)

    compiled = _within(120, lambda: jax.jit(run).lower(
        arr(jnp.bfloat16, lanes, heads, R, key_row),
        arr(jnp.bfloat16, *pool, heads * key_row),
        None if value_row is None else arr(jnp.bfloat16, *pool, heads * dv),
        arr(jnp.int32), arr(jnp.int32, lanes, width), arr(jnp.int32, lanes),
        arr(jnp.bool_, lanes)).compile())
    assert paged_attention.PAGED_DECODE_KERNEL in compiled.as_text()
    layer = pool[1] * bs * heads * key_row * 2
    assert compiled.memory_analysis().temp_size_in_bytes < layer // 2


# ------------------------------------------- the decode step's attention kernel
@pytest.mark.parametrize("kernel", [False, True], ids=["gather", "kernel-rule"])
def test_decode_programs_are_counted_on_the_programs_rule(tiny_engine_parts, kernel):
    """`attn_decodes_kernel` counts the decode programs whose form the rule
    says is the decode kernel, beside `decode_dispatched`; such a program's
    keys are each real lane's own blocks (`attn_keys_run` falls, the padded
    tables' keys do not move). The engine asks the rule once a dispatched
    program. The rule is stubbed on the HOST alone: the tokens are the same
    programs'."""
    import types

    import numpy as np

    from ray_tpu.ops import paged_attention

    cfg, params = tiny_engine_parts
    eng = _make_engine(cfg, params)
    rule, asked = paged_attention.paged_attn_form, []
    assert rule(1, 8, *eng._attn_shapes) == paged_attention.ONE_SHOT       # off the chip

    def form(tokens, width, *shapes):
        asked.append((tokens, *shapes))
        return (paged_attention.DECODE_KERNEL if kernel and tokens == 1
                else rule(tokens, width, *shapes))

    eng._paged_attention = types.SimpleNamespace(
        **{**vars(paged_attention), "paged_attn_form": form})
    by_hand = [0, 0]
    count = eng._count_attn

    def counted(tokens, width, last_pos, real, first_pos=None):
        if first_pos is None:       # a decode program
            lanes = np.size(last_pos)
            real = np.asarray(real) & np.ones(lanes, bool)
            by_hand[0] += int((np.asarray(last_pos)[real] // eng.opts.block_size + 1).sum()
                              ) * eng.opts.block_size
            by_hand[1] += lanes * width * eng.opts.block_size
        return count(tokens, width, last_pos, real, first_pos)

    eng._count_attn = counted
    before = eng.stats()
    ids = [eng.submit(prompt, new) for prompt, new in MIXED]
    _drive(eng)
    st = eng.stats()
    assert st["decode_dispatched"] > 10 and type(st["attn_decodes_kernel"]) is int
    assert st["attn_decodes_kernel"] == (st["decode_dispatched"] if kernel else 0)
    assert len(asked) == st["decode_dispatched"] + st["attn_chunks"]
    assert {shapes[1:] for shapes in asked} == {eng._attn_shapes}
    assert sum(shapes[0] == 1 for shapes in asked) == st["decode_dispatched"]
    prefill_keys = sum(      # what the chunk programs counted, the same either way
        -(-len(prompt) // 4) * 4 for prompt, _ in MIXED)
    run = st["attn_keys_run"] - before["attn_keys_run"]
    if kernel:
        assert run == by_hand[0] + prefill_keys
    else:
        assert run > by_hand[0] + prefill_keys
    assert [len(list(eng.stream(rid))) for rid in ids] == [new for _, new in MIXED]


def test_a_mixed_run_compiles_the_parents_programs_with_the_decode_kernel(monkeypatch):
    """A mixed run whose decode steps take the kernel (interpret mode, traced
    as the chip traces them) meets the (lanes, width) buckets of the run that
    gathers, compiles its chunk programs and emits its tokens; the run that
    gathers compiles one decode program a (lanes, width) bucket it met, the
    kernel's run one a LANE bucket: its tables have one width a lane count
    (`engine._decode_tables`)."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    from ray_tpu.models import gpt
    from ray_tpu.ops import attention, norms, paged_attention
    from ray_tpu.serve.engine import engine as engine_module

    cfg = gpt.GPTConfig(**{**TINY, "n_heads": 2, "d_head": 128, "rotary_dim": 32},
                        dtype=jnp.float32)
    params = jax.tree_util.tree_map(
        lambda a: a * 3.0, gpt.init_params(jax.random.PRNGKey(3), cfg))
    seen = {}
    for by_kernel in (False, True):
        with monkeypatch.context() as mp:
            mp.setattr(engine_module, "_JITS", None)
            if by_kernel:
                mp.setattr(attention, "_on_tpu", lambda: True)
                mp.setattr(paged_attention, "paged_decode_attention", functools.partial(
                    paged_attention.paged_decode_attention, interpret=pltpu.InterpretParams()))
                mp.setattr(norms, "_rmsnorm_pallas", norms._rmsnorm_ref)
            eng = _make_engine(cfg, params, block_size=8, num_blocks=32,
                               prefill_chunk_tokens=8, max_step_tokens=32)
            buckets = set()
            ids = [eng.submit(prompt, new) for prompt, new in MIXED[:3]]
            for plan, dispatched, _ in _walk(eng):
                if dispatched:
                    buckets.add((plan.batch_bucket, plan.width_bucket))
            st = eng.stats()
            seen[by_kernel] = (buckets, eng._decode._cache_size(), eng._prefill._cache_size(),
                               [list(eng.stream(rid)) for rid in ids],
                               st["attn_decodes_kernel"] == st["decode_dispatched"])
    assert seen[True][0] == seen[False][0] and len(seen[True][0]) >= 3
    assert seen[True][2:4] == seen[False][2:4]
    assert seen[False][1] == len(seen[False][0])    # one decode program a bucket
    assert seen[True][1] == len({lanes for lanes, _ in seen[True][0]}) < seen[False][1]
    assert (seen[False][4], seen[True][4]) == (False, True)


# ------------------------------------- the decode step's tables: one width a lane bucket
def _steer_to_the_decode_kernel(eng):
    """The HOST's copy of the rule says a one-token step takes the decode
    kernel, as it does on the chip for heads of 128; the programs keep the
    CPU's forms, whose masks come from positions."""
    import types

    from ray_tpu.ops import paged_attention

    rule = paged_attention.paged_attn_form
    eng._paged_attention = types.SimpleNamespace(**{
        **vars(paged_attention),
        "paged_attn_form": lambda tokens, width, *shapes: (
            paged_attention.DECODE_KERNEL if tokens == 1 else rule(tokens, width, *shapes))})


# (block size, pool blocks, max_seq, lanes) -> the kernel's one width: the pool's
# blocks or 32 KiB of int32 a lane, no wider than a sequence's blocks (a power of two)
DECODE_TABLE_WIDTHS = {
    "the-pool": ((4, 64, 4096, 4), 64),
    "a-sequence": ((16, 64, 256, 4), 16),
    "a-sequence-rounded-up": ((4, 512, 200, 1), 64),
    "the-scalar-operand": ((4, 2048, 4096, 32), 256),
    "the-scalar-operand-at-four-lanes": ((4, 4096, 65536, 4), 2048),
}


@pytest.mark.parametrize("form", ["DECODE_KERNEL", "ONE_SHOT", "KEY_LOOP"])
@pytest.mark.parametrize("case", list(DECODE_TABLE_WIDTHS))
def test_decode_tables_take_the_kernels_one_width_or_the_bucket(case, form):
    """The width rule's truth table (`engine._decode_tables`): for the decode
    kernel, the kernel module's own width a lane count, clipped to what a
    sequence this engine admits can hold and never under the scheduler's
    bucket, each such program booked; for any other form (every CPU run,
    heads of 64 on the chip): the bucket, to the byte."""
    import numpy as np

    from ray_tpu.ops import paged_attention
    from ray_tpu.serve.engine import engine as engine_module

    (bs, blocks, max_seq, lanes), one = DECODE_TABLE_WIDTHS[case]
    form = getattr(paged_attention, form)
    kernel = form == paged_attention.DECODE_KERNEL
    eng = _make_engine(_tiny_cfg(max_seq=max_seq), block_size=bs, num_blocks=blocks)
    assert paged_attention.decode_table_width(lanes, blocks) == min(
        blocks, paged_attention._DECODE_TABLE_BYTES // (4 * lanes)) >= one
    for n, bucket in enumerate([1 << k for k in range(13)], 1):
        tables = engine_module._decode_tables(eng, form, lanes, bucket)
        assert tables.dtype == np.int32 and not tables.any()
        assert tables.shape == (lanes, max(bucket, one) if kernel else bucket)
        assert eng.stats()["decode_width_fixed"] == (n if kernel else 0)
    eng._groups = 5         # one table a KV group: [lanes, groups, width]
    assert engine_module._decode_tables(eng, form, lanes, 2).shape == (
        lanes, 5, one if kernel else 2)


GROWING = [([7, 3, 11], 34), ([5, 5, 5, 9, 8, 2], 20), ([44], 27)]   # 1 -> 10 blocks of 4


def _grown_run(monkeypatch, cfg, params, fixed: bool, waves):
    """One engine over fresh programs, its width rule steered to the kernel's
    answer or not, `waves` of (prompt, new tokens) each submitted together
    and drained before the next -> ((lanes, width) buckets dispatched, decode
    programs compiled, the tokens by request, the engine's stats)."""
    from ray_tpu.serve.engine import engine as engine_module

    with monkeypatch.context() as mp:
        mp.setattr(engine_module, "_JITS", None)
        eng = _make_engine(cfg, params, block_size=4, num_blocks=64,
                           prefill_chunk_tokens=8, max_step_tokens=32)
        if fixed:
            _steer_to_the_decode_kernel(eng)
        buckets, ids = set(), []
        for wave in waves:
            ids += [eng.submit(prompt, new) for prompt, new in wave]
            for plan, dispatched, _ in _walk(eng):
                if dispatched:
                    buckets.add((plan.batch_bucket, plan.width_bucket))
        return (buckets, eng._decode._cache_size(),
                [list(eng.stream(rid)) for rid in ids], eng.stats())


def test_tables_at_one_width_give_the_bucketed_runs_tokens(monkeypatch, tiny_engine_parts):
    """The mask, not the table's width, decides what a query sees: over
    requests whose tables grow through four buckets, the run whose decode
    tables all have the kernel's one width emits, token for token, what the
    bucketed run emits (the dense reference's), plans the same buckets and
    counts the same padded keys: the books keep the width the scheduler planned."""
    cfg, params = tiny_engine_parts
    bucketed, fixed = (_grown_run(monkeypatch, cfg, params, f, [GROWING]) for f in (False, True))
    assert fixed[2] == bucketed[2] == [
        _reference(cfg, params, prompt, new) for prompt, new in GROWING]
    assert fixed[0] == bucketed[0] and len({w for _, w in fixed[0]}) >= 4
    assert fixed[3]["attn_keys_padded"] == bucketed[3]["attn_keys_padded"]
    assert fixed[3]["decode_width_fixed"] == fixed[3]["decode_dispatched"] > 30
    assert bucketed[3]["decode_width_fixed"] == 0


def test_a_warm_plan_compiles_one_decode_program_a_lane_bucket(monkeypatch, tiny_engine_parts):
    """The benchmark's warm-up (`benchmarks.traffic.warm_plan`: a wave a table
    width, each passing the lanes through every bucket) replayed through
    `submit`: lanes x widths decode programs at the scheduler's widths, ONE a
    lane bucket at the kernel's width, the first wave's; the same tokens."""
    import numpy as np

    from benchmarks import traffic

    cfg, params = tiny_engine_parts
    mix = {"pool_seed": 7, "arrivals": {"process": "poisson", "rate_rps": 2.0},
           "prompt_len": {"dist": "uniform", "min": 2, "max": 40},
           "output_len": {"dist": "uniform", "min": 2, "max": 20}, "max_total": 64}
    plan = traffic.warm_plan(mix, 8.0, block_size=4, max_num_seqs=4, chunk=8)[:-1]
    rng = np.random.default_rng(0)
    waves = [[(rng.integers(1, cfg.vocab_size, n).tolist(), new) for n, new in wave]
             for wave in plan]
    bucketed, fixed = (_grown_run(monkeypatch, cfg, params, f, waves) for f in (False, True))
    assert len(waves) >= 3 and fixed[2] == bucketed[2]
    lanes, widths = ({b[i] for b in bucketed[0]} for i in (0, 1))
    assert lanes == {1, 2, 4} and len(widths) >= len(waves)
    pinned = sorted(widths)[-3:]    # the widths the last waves' long requests pin
    assert {(n, w) for n in lanes for w in pinned} <= bucketed[0], bucketed[0]
    assert bucketed[1] == len(bucketed[0]) >= len(lanes) * len(waves)
    assert fixed[1] == len(lanes)


# ------------------------------------------------- the step's export phase
class _ScriptedClock:
    """`time` as `engine.py` alone sees it, its monotonic clock set by the
    test (every other name is the real module's)."""

    def __init__(self, now: float):
        self.now = now

    def monotonic(self):
        return self.now

    def monotonic_ns(self):
        return int(self.now * 1e9)

    def __getattr__(self, name):
        return getattr(time, name)


class _CountedWindow(deque):
    """A deque that counts what is done to it; walking it whole (what a
    rebuild does) is counted too."""

    appends = pops = walks = 0

    def append(self, x):
        self.appends += 1
        super().append(x)

    def popleft(self):
        self.pops += 1
        return super().popleft()

    def __iter__(self):
        self.walks += 1
        return super().__iter__()


class TestExportPhase:
    """`export_ns` pays for what happened in the step, never for what the
    replica did in the last ten seconds: the token window is kept, not
    rebuilt, and a metric's series key is made once."""

    def test_tokens_per_s_is_the_old_definition_on_a_scripted_clock(
        self, tiny_engine_parts, monkeypatch
    ):
        from ray_tpu.serve.engine import engine as engine_module

        clock = _ScriptedClock(1000.0)
        monkeypatch.setattr(engine_module, "time", clock)
        eng = _make_engine(*tiny_engine_parts)
        assert eng.step()["tokens_per_s"] == 0.0        # the empty window
        eng._tok_window.append(clock.now)               # one stamp: the floor
        assert eng.step()["tokens_per_s"] == 1 / 1e-3
        # 4,000 stamps spread unevenly over 25 s, read at several nows: the
        # parent's two lines, written out, on a list of its own
        stamps = sorted(1000.0 + 25.0 * ((i * 0.6180339887) % 1.0) ** 2
                        for i in range(4000))
        eng._tok_window.clear()
        eng._tok_window.extend(stamps)
        old, seen = list(stamps), set()
        for now in (1025.0, 1025.0005, 1027.5, 1031.0, 1033.25, 1034.9, 1035.0,
                    1040.0):
            clock.now = now
            old = [t for t in old if now - t <= 10.0]
            want = len(old) / max(now - old[0], 1e-3) if old else 0.0
            assert eng.step()["tokens_per_s"] == want, now
            assert list(eng._tok_window) == old
            seen.add(want)
        # a replica that emits nothing for ten seconds drains to empty
        assert want == 0.0 and not eng._tok_window and len(seen) >= 6

    def test_a_step_touches_what_it_emitted_and_what_expired(
        self, tiny_engine_parts, monkeypatch
    ):
        """50,000 stamps inside the window: a step that emits k tokens and
        expires m stamps appends k, pops m and walks nothing; the window is
        the same object after it."""
        from ray_tpu.serve.engine import engine as engine_module

        clock = _ScriptedClock(5000.0)
        monkeypatch.setattr(engine_module, "time", clock)
        eng = _make_engine(*tiny_engine_parts)
        for i in range(3):
            eng.submit([1 + i] * 6, max_new_tokens=8)
        while eng.step()["step_tokens"] < 3:      # all three lanes decode
            pass
        live = [4990.5 + 9.0 * i / 50_000 for i in range(50_000)]
        for m in (0, 7, 1300):
            clock.now += 0.001
            gone = [clock.now - 10.0 - 0.01 * (m - i) for i in range(m)]
            window = eng._tok_window = _CountedWindow(gone + live)
            before = len(window)
            stats = eng.step()
            k = stats["step_tokens"]
            assert k == 3 and eng._tok_window is window
            assert (window.appends, window.pops, window.walks) == (k, m, 0)
            assert len(window) == before + k - m
            assert stats["tokens_per_s"] == len(window) / (clock.now - live[0])
            live = list(window)

    @pytest.mark.parametrize("kind, method", [
        ("gauge", "set"), ("counter", "inc"), ("histogram", "observe")])
    def test_a_record_lands_in_the_series_the_parent_keyed(self, metric_sink, kind,
                                                           method):
        """A metric with constant default tags records into the pending
        table under the key the parent's `record` computed a call (written
        out here): the name, the kind, the merged tags as sorted pairs of
        strings. A per-call `tags=` still makes a series of its own."""
        from ray_tpu.util import metrics

        tags = {"replica": "app#D#r0", "app": "app", "role": "mixed",
                "deployment": "D", "shard": 3}
        name = f"xp_{kind}"
        m = getattr(metrics, kind.capitalize())(name, "help text").set_default_tags(tags)
        write = getattr(m, method)
        own = (name, kind, (("app", "app"), ("deployment", "D"),
                            ("replica", "app#D#r0"), ("role", "mixed"),
                            ("shard", "3")))
        routed = (name, kind, (("app", "app"), ("deployment", "D"),
                               ("replica", "app#D#r0"), ("role", "other"),
                               ("route", "7"), ("shard", "3")))
        pending = metrics._FLUSHER._pending
        with metrics._FLUSHER._flush_lock:          # one interval
            write(2.0)
            entry = pending[own]
            write(0.5)
            write(4.0, tags={"route": 7, "role": "other"})
            write(1.0)
            assert pending[own] is entry and set(pending) >= {own, routed}
            assert [k for k in pending if k[0] == name] == [own, routed]
            # default tags set anew move the metric's series with them
            m.set_default_tags({"replica": "r1"})
            write(8.0)
            assert (name, kind, (("replica", "r1"),)) in pending
        metrics.flush()
        (msg,) = metric_sink.series(name, **tags)
        (other,) = metric_sink.series(name, **{**tags, "route": 7, "role": "other"})
        (moved,) = metric_sink.series(name, replica="r1")
        assert msg[2] == other[2] == moved[2] == kind
        if kind == "gauge":         # its last value
            assert (msg[3], other[3], moved[3]) == (1.0, 4.0, 8.0)
        elif kind == "counter":     # its sum
            assert (msg[3], other[3], moved[3]) == (3.5, 4.0, 8.0)
        else:                       # every observation
            assert (msg[5]["count"], msg[5]["sum"]) == (3, 3.5)
            assert (other[5]["count"], moved[5]["sum"]) == (1, 8.0)
        assert msg[5]["help"] == "help text"

    def test_a_replicas_engine_exports_the_parents_series(
        self, tiny_engine_parts, metric_sink
    ):
        """A scripted run under a replica's context: every series of the
        engine arrives under the replica's four tags, the counters as the
        run's totals, the histograms with every observation."""
        from ray_tpu.serve import context
        from ray_tpu.util import metrics

        context._set_replica_context(
            context.ReplicaContext("app", "LLM", "app#LLM#r0"))
        try:
            eng = _make_engine(*tiny_engine_parts)
        finally:
            context._set_replica_context(None)
        with metrics._FLUSHER._flush_lock:          # one interval
            rids = [eng.submit([1 + i] * 6, max_new_tokens=5) for i in range(3)]
            steps = _drive(eng)
        assert all(len(list(eng.stream(rid))) == 5 for rid in rids)
        metrics.flush()
        tags = {"app": "app", "deployment": "LLM", "replica": "app#LLM#r0",
                "role": "mixed"}
        sent = {m[1]: m for m in metric_sink.sent if m[1].startswith("serve_engine_")}
        assert all(m[4] == tags for m in sent.values())
        assert len(sent) == len([m for m in metric_sink.sent
                                 if m[1].startswith("serve_engine_")])
        assert {n: m[2] for n, m in sent.items()} == {
            "serve_engine_queue_depth": "gauge",
            "serve_engine_running_seqs": "gauge",
            "serve_engine_kv_utilization": "gauge",
            "serve_engine_tokens_per_s": "gauge",
            "serve_engine_host_tier_bytes": "gauge",
            "serve_engine_tokens_total": "counter",
            "serve_engine_prefix_cache_misses_total": "counter",
            "serve_engine_ttft_s": "histogram",
            "serve_engine_tpot_s": "histogram",
            "serve_engine_step_budget_tokens": "histogram",
        }
        assert sent["serve_engine_tokens_total"][3] == 15.0
        assert sent["serve_engine_queue_depth"][3] == 0.0
        assert sent["serve_engine_running_seqs"][3] == 0.0
        assert sent["serve_engine_ttft_s"][5]["count"] == 3
        assert sent["serve_engine_tpot_s"][5]["count"] == 3       # one a request
        assert 0 < sent["serve_engine_step_budget_tokens"][5]["count"] <= steps
