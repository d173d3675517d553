"""Disaggregated prefill/decode serving + the tiered cluster-wide KV cache
(`ray_tpu.serve.engine.kv_tier` / `kv_transfer`, fleet pools, router
handoff orchestration).

Layers covered separately, then end to end:

  * host tier — HBM evictions SAVE into host RAM, digests stay advertised,
    re-admissions hit the tier instead of recomputing;
  * kv_transfer — span-table frames over a REAL BulkServer on every native
    lander path (stream/ring/off), including the all-or-nothing contract
    when the source dies mid-pull;
  * engine handoff — disaggregated prefill->export->import->decode is
    token-for-token identical to colocated decode (the merge gate), with
    and without a usable descriptor;
  * serve fleet — a 2-pool deployment over a real cluster: role
    assignment, handoff counters, parity through the public handle, and
    the SIGKILL-the-prefill-replica chaos path (request recomputes on a
    decode replica; no partial KV import; no wedged stream).
"""

import json
import os
import secrets
import signal
import threading
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.serve.engine import KVBlockManager
from ray_tpu.serve.engine.kv_tier import HostKVTier

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


def _tiny_cfg():
    import jax.numpy as jnp

    from ray_tpu.models.gpt import GPTConfig

    return GPTConfig(**{**TINY, "dtype": jnp.float32})


@pytest.fixture(scope="module")
def tiny_engine_parts():
    import jax

    from ray_tpu.models.gpt import init_params

    cfg = _tiny_cfg()
    params = init_params(jax.random.PRNGKey(3), cfg)
    # Scaled so greedy decode emits VARIED tokens — a collapsed argmax
    # would let a KV-corruption bug pass parity by accident.
    params = jax.tree_util.tree_map(lambda a: a * 3.0, params)
    return cfg, params


def _make_engine(cfg, params=None, **opts):
    from ray_tpu.serve.engine import EngineOptions, InferenceEngine

    defaults = dict(num_blocks=64, block_size=4, max_num_seqs=4)
    return InferenceEngine(
        cfg, params=params, options=EngineOptions(**{**defaults, **opts})
    )


# ----------------------------------------------------------- host tier
class TestHostTier:
    def test_eviction_saves_and_readmission_hits_tier(self):
        """Fill the pool with registered prefixes, force evictions, and
        re-admit the first prompt: its blocks come back from the host tier
        (queued as loads, counted as host hits), not as recompute misses."""
        tier = HostKVTier(1 << 20)
        kv = KVBlockManager(num_blocks=9, block_size=4, host_tier=tier)
        blob = {}
        prompts = {}
        for i in range(4):  # 4 seqs x 2 blocks = every allocatable block
            toks = [i * 16 + j for j in range(9)]  # 2 full blocks + tail
            prompts[i] = toks
            kv.allocate_cached(f"s{i}", toks, 9)
            kv.register_computed(f"s{i}", toks, 9)
            kv.free(f"s{i}")
            kv.check_invariants()
        # Simulate the engine's save drain: bytes keyed by hash.
        for h, b in kv.drain_saves():
            tier.put(h, np.full((4,), b, np.int32))
        kv.drain_loads()
        # s0's two blocks were LRU -> evicted by later admissions. Their
        # content must now live in the tier.
        assert kv.evictions > 0
        table, cached = kv.allocate_cached("again", prompts[0], 9)
        for h, b in kv.drain_saves():
            tier.put(h, np.full((4,), b, np.int32))
        assert cached == 8, "host tier did not serve the evicted prefix"
        assert kv.host_hits >= 1
        loads = kv.drain_loads()
        assert {b for _, b, _, _ in loads} <= set(table)
        assert all(not remote for *_, remote in loads), (
            "tier re-admissions must not be flagged as remote imports"
        )
        kv.check_invariants()

    def test_hot_digest_survives_hbm_eviction_until_tier_eviction(self):
        """Satellite: `prefix_digest` entries used to die with the HBM
        eviction. With bytes surviving in the host tier, the digest must
        stay advertised (affinity routing keeps steering matching prompts
        here) and die only when the TIER evicts the bytes for real."""
        tier = HostKVTier(3 * 16)  # three 16-byte blobs
        kv = KVBlockManager(num_blocks=4, block_size=2, host_tier=tier)
        toks = [1, 2, 3, 4, 5]
        kv.allocate_cached("a", toks, 5)          # 3 blocks, last half full
        kv.register_computed("a", toks, 4)        # registers 2 full blocks
        digest_before = set(kv.prefix_digest())
        assert len(digest_before) == 2
        kv.free("a")
        # New allocation needs all 3 blocks: evicts both cached ones.
        kv.allocate("b", 6)
        assert kv.evictions == 2
        saves = kv.drain_saves()
        assert len(saves) == 2
        for h, b in saves:
            tier.put(h, np.zeros(4, np.int32))  # 16 bytes each
        assert set(kv.prefix_digest()) == digest_before, (
            "host-resident digests must stay advertised"
        )
        # Tier eviction (budget overflow) drops the advertisement.
        tier.put(b"x" * 16, np.zeros(4, np.int32))
        tier.put(b"y" * 16, np.zeros(4, np.int32))
        assert len(set(kv.prefix_digest()) & digest_before) < 2, (
            "tier-evicted digest still advertised"
        )
        kv.check_invariants()

    def test_pending_load_eviction_drops_load_and_skips_save(self):
        """A block adopted for an import whose bytes never landed must not
        be SAVED on eviction (its HBM content is garbage) and its load
        order must die with it."""
        tier = HostKVTier(1 << 16)
        kv = KVBlockManager(num_blocks=3, block_size=2, host_tier=tier)
        b1 = kv.adopt_block(b"h" * 16, np.zeros(3, np.int32))
        assert b1 is not None
        # Exhaust the pool so the adopted (cached) block is the evictee.
        kv.allocate("s", 4)
        assert kv.holds(b"h" * 16) is None, "adopted block not evicted"
        assert kv.drain_saves() == [], "garbage bytes saved to the tier"
        assert all(b != b1 for _, b, _, _ in kv.drain_loads()), (
            "dropped load still pending"
        )
        kv.check_invariants()

    def test_tier_budget_lru(self):
        tier = HostKVTier(64)
        tier.put(b"a", np.zeros(4, np.int32))  # 16 bytes
        tier.put(b"b", np.zeros(4, np.int32))
        tier.put(b"c", np.zeros(4, np.int32))
        tier.put(b"d", np.zeros(4, np.int32))
        assert tier.bytes_used <= 64
        tier.get(b"b")  # touch
        tier.put(b"e", np.zeros(4, np.int32))
        assert tier.contains(b"b") and tier.bytes_used <= 64


# ------------------------------------------------------- span transport
@pytest.fixture
def bulk_pair():
    """A store + BulkServer pair (no cluster) — the kv-transfer span path
    driven directly, per native-lander mode."""
    from ray_tpu.core import bulk, store

    os.environ.setdefault("RAY_TPU_AUTH_TOKEN", secrets.token_hex(8))
    old_tag = store.SESSION_TAG
    store.set_session_tag(f"kd{os.getpid()}")
    src = store.make_store(create_arena=True, arena_capacity=64 << 20)
    srv = bulk.BulkServer(src, bind_host="127.0.0.1")
    port = srv.start()
    dst = store.LocalStore()
    try:
        yield src, f"127.0.0.1:{port}", dst, srv
    finally:
        srv.stop()
        dst.close_all(unlink=True)
        src.close_all(unlink=True)
        if hasattr(src, "arena"):
            src.arena.detach()
            try:
                src.arena.unlink()
            except OSError:
                pass
        store.set_session_tag(old_tag)


def _lander_env(mode):
    from ray_tpu.core import config as rt_config

    os.environ["RAY_TPU_BULK_NATIVE_LANDER"] = mode
    rt_config._reset_cache_for_tests()


def _pack_and_store(src, n_blocks=6, block_elems=512):
    from ray_tpu.serve.engine import kv_transfer

    rng = np.random.default_rng(7)
    blobs = [
        rng.standard_normal(block_elems).astype(np.float32)
        for _ in range(n_blocks)
    ]
    digests = [secrets.token_bytes(16) for _ in range(n_blocks)]
    hexes = [h.hex() for h in digests]
    payload, buffers, spans = kv_transfer.pack_frame(hexes, blobs)
    from ray_tpu.core import serialization

    size = serialization.packed_size(payload, buffers)
    frame = bytearray(size)
    serialization.pack_into(payload, buffers, memoryview(frame))
    name, _ = src.create_raw(secrets.token_hex(28), bytes(frame))
    desc = {
        "v": 1, "digests": hexes, "spans": spans,
        "dtype": blobs[0].dtype.str, "shape": blobs[0].shape,
    }
    return name, desc, blobs, hexes


@pytest.mark.parametrize("lander", ["stream", "ring", "off"])
class TestSpanTransport:
    def _maybe_skip_native(self, lander):
        if lander in ("stream", "ring"):
            from ray_tpu import native as native_mod

            if native_mod.load_bulk_lib() is None:
                pytest.skip(
                    f"native bulk lander unbuildable: "
                    f"{native_mod.bulk_build_error()}"
                )

    def test_span_pull_rebuilds_blocks(self, bulk_pair, lander):
        """Every needed block (full set AND a sparse subset with coalesced
        runs) pulls byte-exact over the bulk plane on this lander path."""
        self._maybe_skip_native(lander)
        from ray_tpu.serve.engine import kv_transfer

        src, addr, dst, _srv = bulk_pair
        name, desc, blobs, hexes = _pack_and_store(src)
        old = os.environ.get("RAY_TPU_BULK_NATIVE_LANDER")
        try:
            _lander_env(lander)
            for needed in (list(range(len(blobs))), [0, 1, 4]):
                got = kv_transfer._fetch_remote_runs(
                    {"bulk": addr, "name": name}, desc, needed, 10.0,
                    store=dst,
                )
                assert got is not None and sorted(got) == sorted(needed)
                for k in needed:
                    np.testing.assert_array_equal(got[k], blobs[k])
        finally:
            if old is None:
                os.environ.pop("RAY_TPU_BULK_NATIVE_LANDER", None)
            else:
                os.environ["RAY_TPU_BULK_NATIVE_LANDER"] = old
            _lander_env(old or "auto")

    def test_source_death_mid_pull_imports_nothing(self, bulk_pair, lander):
        """Chaos at the transfer layer: the source's bulk server dies
        mid-handoff -> fetch_blocks returns None (all-or-nothing), never a
        partial block set — the importer recomputes from scratch."""
        self._maybe_skip_native(lander)
        from ray_tpu.serve.engine import kv_transfer

        src, addr, dst, srv = bulk_pair
        name, desc, blobs, hexes = _pack_and_store(src)
        srv.stop()  # source gone before (= worst case of "mid") the pull
        old = os.environ.get("RAY_TPU_BULK_NATIVE_LANDER")
        try:
            _lander_env(lander)
            with pytest.raises(Exception):
                kv_transfer._fetch_remote_runs(
                    {"bulk": addr, "name": name}, desc,
                    list(range(len(blobs))), 2.0, store=dst,
                )
        finally:
            if old is None:
                os.environ.pop("RAY_TPU_BULK_NATIVE_LANDER", None)
            else:
                os.environ["RAY_TPU_BULK_NATIVE_LANDER"] = old
            _lander_env(old or "auto")


# ------------------------------------------------------- engine handoff
def _drive(engine, fn, max_steps=400):
    n = 0
    while True:
        done = fn()
        if done:
            return
        engine.step()
        n += 1
        assert n < max_steps, "engine made no progress"


class TestDisaggEngineParity:
    def test_disagg_token_parity_with_colocated(self, tiny_engine_parts):
        """THE merge gate: prefill on engine P -> export -> import on
        engine D -> decode continues after the handed-off first token,
        token-for-token identical to colocated mixed decode. Import is
        asserted REAL (D's admission hits every exported block)."""
        cfg, params = tiny_engine_parts
        prompt = [(7 * i + 3) % 60 + 1 for i in range(18)]  # 4 full blocks
        N = 12

        colo = _make_engine(cfg, params)
        colo.start()
        ref = colo.generate(prompt, N)
        colo.shutdown()

        pre = _make_engine(cfg, params, role="prefill")
        pre.start()
        rid = pre.submit(prompt, 1)
        first = list(pre.stream(rid))
        desc = pre.export_prompt_kv(prompt)
        assert desc is not None and len(desc["digests"]) == len(prompt) // 4
        # One block on the wire: [k/v, L, BS, H*Dh], a token's row whole.
        assert tuple(desc["shape"]) == (
            2, cfg.n_layers, 4, cfg.n_heads * cfg.d_head
        )
        pre.shutdown()

        dec = _make_engine(cfg, params, role="decode")
        dec.start()
        imported = dec.import_blocks(desc)
        assert imported == len(desc["digests"])
        rest = dec.generate(prompt + first, N - 1)
        st = dec.stats()
        dec.shutdown()
        assert first + rest == ref, (
            f"disagg {first + rest} != colocated {ref}"
        )
        assert st["prefix_cache_hits"] >= imported, (
            "imported blocks never served the admission"
        )
        assert st["role"] == "decode" and st["blocks_imported"] == imported

    def test_disagg_parity_without_descriptor(self, tiny_engine_parts):
        """Degraded handoff (export failed / source died): the decode
        replica recomputes the prompt and the output is STILL identical —
        greedy determinism is what makes every fallback safe."""
        cfg, params = tiny_engine_parts
        prompt = [(5 * i + 2) % 60 + 1 for i in range(13)]
        N = 8
        colo = _make_engine(cfg, params)
        colo.start()
        ref = colo.generate(prompt, N)
        colo.shutdown()

        pre = _make_engine(cfg, params, role="prefill")
        pre.start()
        first = list(pre.stream(pre.submit(prompt, 1)))
        pre.shutdown()

        dec = _make_engine(cfg, params, role="decode")
        dec.start()
        assert dec.import_blocks(None) == 0
        rest = dec.generate(prompt + first, N - 1)
        dec.shutdown()
        assert first + rest == ref

    def test_concurrent_import_overlap_adopts_the_rest(
        self, tiny_engine_parts, monkeypatch
    ):
        """Two handoffs sharing a hot prefix race onto one decode replica:
        a block adopted between this import's `needed` snapshot and its
        adoption loop must be SKIPPED, not treated as pool exhaustion —
        breaking there used to discard every remaining already-fetched
        block and force recompute of bytes already pulled."""
        cfg, params = tiny_engine_parts
        prompt = [(7 * i + 3) % 60 + 1 for i in range(18)]  # 4 full blocks
        N = 12
        colo = _make_engine(cfg, params)
        colo.start()
        ref = colo.generate(prompt, N)
        colo.shutdown()

        pre = _make_engine(cfg, params, role="prefill")
        pre.start()
        first = list(pre.stream(pre.submit(prompt, 1)))
        desc = pre.export_prompt_kv(prompt)
        pre.shutdown()
        assert desc is not None and len(desc["digests"]) == 4

        dec = _make_engine(cfg, params, role="decode")
        dec.start()
        from ray_tpu.serve.engine import kv_transfer as kvt

        real = kvt.fetch_blocks

        def racing_fetch(d, needed, **kw):
            blobs = real(d, needed, **kw)
            hx, blob = blobs[0]  # the shared leading block lands first
            with dec._lock:
                assert dec.block_manager.adopt_block(
                    bytes.fromhex(hx), blob
                ) is not None
            return blobs

        monkeypatch.setattr(kvt, "fetch_blocks", racing_fetch)
        n = dec.import_blocks(desc)
        assert n == len(desc["digests"]) - 1, (
            "overlap with a concurrent import discarded fetched blocks"
        )
        rest = dec.generate(prompt + first, N - 1)
        dec.shutdown()
        assert first + rest == ref

    @pytest.mark.parametrize("mismatch", ["block_size", "block_layout"])
    def test_import_rejects_mismatched_layout(self, tiny_engine_parts, mismatch):
        """A descriptor from an engine with another block size, or from one
        that kept its blocks head-major (the signature before PR 25:
        the same bytes in another order), is refused whole; the importer
        recomputes the prompt and answers as if nothing had been offered."""
        cfg, params = tiny_engine_parts
        pre = _make_engine(cfg, params, block_size=4)
        pre.start()
        prompt = list(range(1, 18))
        first = list(pre.stream(pre.submit(prompt, 1)))
        ref = first + pre.generate(prompt + first, 5)
        desc = pre.export_prompt_kv(prompt)
        pre.shutdown()
        assert desc is not None and desc["sig"].endswith(":rows")
        if mismatch == "block_layout":
            desc = {**desc, "sig": desc["sig"][: -len(":rows")]}
            other = _make_engine(cfg, params, block_size=4)
        else:
            other = _make_engine(cfg, params, block_size=8)
        other.start()
        assert other.import_blocks(desc) == 0, (
            "imported KV across incompatible block layouts"
        )
        rest = other.generate(prompt + first, 5)
        st = other.stats()
        other.shutdown()
        assert first + rest == ref and st["blocks_imported"] == 0

    def test_host_tier_round_trip_through_engine(self, tiny_engine_parts):
        """A pool too small to retain a prefix evicts it to the host tier;
        the SAME prompt re-admitted comes back via tier loads with output
        identical to a fresh engine (bytes round-tripped exactly)."""
        cfg, params = tiny_engine_parts
        # 9 allocatable blocks, bs=4: one 18-token prompt + decode fills
        # most of the pool; a second prompt forces evictions.
        p1 = [(3 * i + 1) % 60 + 1 for i in range(18)]
        p2 = [(11 * i + 5) % 60 + 1 for i in range(18)]
        ref_engine = _make_engine(cfg, params, num_blocks=10)
        ref_engine.start()
        ref1 = ref_engine.generate(p1, 6)
        ref_engine.shutdown()

        e = _make_engine(cfg, params, num_blocks=10, host_kv_bytes=1 << 20)
        e.start()
        out1 = e.generate(p1, 6)
        e.generate(p2, 6)              # evicts p1's blocks -> tier saves
        saved = next(iter(e.host_tier._blobs.values()))
        assert saved.shape == (2, cfg.n_layers, 4, cfg.n_heads * cfg.d_head)
        out1b = e.generate(p1, 6)      # re-admission: tier consult
        st = e.stats()
        e.shutdown()
        assert out1 == ref1 and out1b == ref1
        assert st["host_tier_hits"] > 0, "re-admission never hit the tier"
        assert st["host_tier_blocks"] > 0

    def test_decode_role_caps_prefill_budget(self):
        """Scheduler policy: a decode-role engine never spends more than
        max_step_tokens/4 on prefill in one step; a prefill-role engine
        runs multiple chunks per step."""
        from ray_tpu.serve.engine import Scheduler, Sequence

        kv = KVBlockManager(num_blocks=128, block_size=4)
        sched = Scheduler(
            kv, max_num_seqs=4, max_step_tokens=64, prefill_chunk=16,
            max_prefills_per_step=4, prefill_budget_cap=16,
        )
        for i in range(4):
            sched.add(Sequence(request_id=f"r{i}", prompt=[1] * 40,
                               max_new_tokens=4))
        out = sched.schedule()
        assert sum(c.num_tokens for c in out.prefills) <= 16, (
            "decode-role cap exceeded"
        )


# ----------------------------------------------------------- fleet policy
class TestDisaggPolicy:
    def _cfg(self):
        return dict(target_ongoing_requests=2.0, target_queue_depth=4.0,
                    ttft_p99_target_s=0.5, downscale_hit_rate=0.2)

    def test_ttft_pressure_scales_prefill_pool_only(self):
        from ray_tpu.serve.fleet import FleetSignals, decide_scale_disagg

        pre = FleetSignals(replicas=1, ongoing=0, queue_depth=0,
                           ttft_p99_s=2.0, hit_rates=[0.9])
        dec = FleetSignals(replicas=2, ongoing=1.0, queue_depth=0,
                           running=2, hit_rates=[0.9, 0.9])
        dp, dd = decide_scale_disagg(pre, dec, **self._cfg())
        assert dp == 1 and dd == 0

    def test_decode_queue_scales_decode_pool_only(self):
        from ray_tpu.serve.fleet import FleetSignals, decide_scale_disagg

        pre = FleetSignals(replicas=1, ongoing=0, queue_depth=0,
                           ttft_p99_s=0.1, hit_rates=[0.9])
        dec = FleetSignals(replicas=2, ongoing=1.0, queue_depth=20,
                           running=2, hit_rates=[0.9, 0.9])
        dp, dd = decide_scale_disagg(pre, dec, **self._cfg())
        assert dp == 0 and dd == 1

    def test_quiet_cold_pools_scale_down(self):
        from ray_tpu.serve.fleet import FleetSignals, decide_scale_disagg

        pre = FleetSignals(replicas=2, ongoing=0, queue_depth=0,
                           ttft_p99_s=None, hit_rates=[0.0, 0.0])
        dec = FleetSignals(replicas=2, ongoing=0.0, queue_depth=0,
                           running=0, hit_rates=[0.0, 0.0])
        dp, dd = decide_scale_disagg(pre, dec, **self._cfg())
        assert dp == -1 and dd == -1

    def test_decode_ttft_tail_never_scales_decode(self):
        """A slow first token is the prefill pool's problem — the decode
        pool must not scale on it."""
        from ray_tpu.serve.fleet import FleetSignals, decide_scale_disagg

        pre = FleetSignals(replicas=1, ongoing=0, queue_depth=0,
                           ttft_p99_s=0.1, hit_rates=[0.9])
        dec = FleetSignals(replicas=1, ongoing=1.0, queue_depth=0,
                           running=1, ttft_p99_s=9.9, hit_rates=[0.9])
        dp, dd = decide_scale_disagg(pre, dec, **self._cfg())
        assert dd == 0

    def test_split_pools(self):
        from ray_tpu.serve.fleet import split_pools

        pre, dec = split_pools(
            ["prefill", None, "decode", "mixed", "decode"]
        )
        assert pre == [0] and dec == [2, 4]


class TestDisaggControllerAutoscale:
    """Controller-side pool-target mechanics (the policy itself is
    TestDisaggPolicy; these drive `_maybe_autoscale` bare, like
    test_serve_fleet's TestControllerAutoscaling)."""

    def _controller(self):
        import threading as _t

        from ray_tpu.serve.controller import ServeController

        ctl = ServeController.__new__(ServeController)
        ctl._lock = _t.RLock()
        ctl._version = 0
        ctl._apps = {}
        return ctl

    def _state(self, autoscaling, replicas=4, prefill=2):
        from ray_tpu.serve.controller import _DeploymentState

        state = _DeploymentState(
            {"name": "d",
             "opts": {"num_replicas": replicas,
                      "prefill_replicas": prefill,
                      "autoscaling_config": autoscaling},
             "cls": b"", "init_args": b""}
        )
        state.replicas = [object() for _ in range(replicas)]
        state.replica_tags = [f"a#d#{i}" for i in range(replicas)]
        for i in range(replicas):
            state.replica_roles[f"a#d#{i}"] = (
                "prefill" if i < prefill else "decode"
            )
        return state

    def _cfg(self, **kw):
        return {**dict(min_replicas=2, max_replicas=4,
                       target_ongoing_requests=2.0, target_queue_depth=2.0,
                       upscale_delay_s=0.0, downscale_delay_s=0.0,
                       ttft_p99_target_s=1.0, downscale_hit_rate=0.2), **kw}

    def test_band_clamp_never_starves_a_pressured_decode_pool(self):
        """Both pools pressured AT the max_replicas ceiling: nothing can
        grow, and the clamp must not steal the decode pool's target to
        fund prefill growth (it used to halve decode under active decode
        queue pressure)."""
        ctl = self._controller()
        state = self._state(self._cfg())
        state.replica_meta["a#d#0"] = {
            "t": 0.0,
            "engine": {"role": "prefill", "ttft_p99_s": 9.0,
                       "queue_depth": 0, "prefix_hit_rate": 0.9},
        }
        state.replica_meta["a#d#2"] = {
            "t": 0.0,
            "engine": {"role": "decode", "queue_depth": 50,
                       "prefix_hit_rate": 0.9},
        }
        for _ in range(3):
            ctl._maybe_autoscale(state)
        assert (state.target_prefill, state.target_replicas) == (2, 4)

    def test_decode_growth_survives_clamp_when_prefill_also_grows(self):
        """One slot left under the ceiling, both pools asking: growth is
        given back from the prefill side first — decode lanes are the
        scarce resource."""
        ctl = self._controller()
        state = self._state(self._cfg(max_replicas=5))
        state.replica_meta["a#d#0"] = {
            "t": 0.0,
            "engine": {"role": "prefill", "ttft_p99_s": 9.0,
                       "queue_depth": 0, "prefix_hit_rate": 0.9},
        }
        state.replica_meta["a#d#2"] = {
            "t": 0.0,
            "engine": {"role": "decode", "queue_depth": 50,
                       "prefix_hit_rate": 0.9},
        }
        ctl._maybe_autoscale(state)
        assert (state.target_prefill, state.target_replicas) == (2, 5)

    def test_pure_rebalance_never_drifts_targets(self):
        """dp=+1/dd=-1 with an unchanged total has NO actuation (roles are
        assigned at replica start; nothing migrates a live replica between
        pools) — repeated ticks must not walk target_prefill away from the
        fleet's real composition (it used to increment every tick,
        unboundedly)."""
        ctl = self._controller()
        state = self._state(self._cfg(max_replicas=8))
        state.replica_meta["a#d#0"] = {
            "t": 0.0,
            "engine": {"role": "prefill", "ttft_p99_s": 9.0,
                       "queue_depth": 0, "prefix_hit_rate": 0.9},
        }
        state.replica_meta["a#d#2"] = {
            "t": 0.0,
            "engine": {"role": "decode", "queue_depth": 0, "running": 0,
                       "prefix_hit_rate": 0.0},
        }
        v0 = ctl._version
        for _ in range(5):
            ctl._maybe_autoscale(state)
        assert (state.target_prefill, state.target_replicas) == (2, 4)
        assert ctl._version == v0, "no-actuation tick published a version"


class TestPoolSplitRedeploy:
    """In-place redeploy with a CHANGED prefill_replicas: a live replica's
    role is fixed at engine start, so role-stale replicas must be drained
    (reconcile then starts correctly-roled replacements) — redeploying
    0->N used to leave every replica role-less forever, silently serving
    colocated while reporting a pool split."""

    def _controller(self):
        import threading as _t

        from ray_tpu.serve.controller import ServeController

        ctl = ServeController.__new__(ServeController)
        ctl._lock = _t.RLock()
        ctl._version = 0
        ctl._apps = {}
        ctl._reconcile = lambda: None  # unit test: no replica starts
        return ctl

    def _spec(self, replicas, prefill):
        return {"name": "d",
                "opts": {"num_replicas": replicas,
                         "prefill_replicas": prefill},
                "cls": b"", "init_args": b""}

    def _deploy(self, ctl, replicas, prefill):
        ctl.deploy_application(
            "a", [self._spec(replicas, prefill)], "/a", "d"
        )
        return ctl._apps["a"]["deployments"]["d"]

    def _seed_live(self, state, roles):
        state.replicas = [object() for _ in roles]
        state.replica_tags = [f"a#d#{i}" for i in range(len(roles))]
        for t, r in zip(state.replica_tags, roles):
            if r:
                state.replica_roles[t] = r

    def test_colocated_to_disagg_drains_roleless(self):
        ctl = self._controller()
        state = self._deploy(ctl, 4, 0)
        self._seed_live(state, [None, None, None, None])
        state = self._deploy(ctl, 4, 2)
        assert state.target_prefill == 2
        assert state.replicas == [], "role-less replicas must be replaced"
        # Replacements get real roles, prefill pool filled first.
        from ray_tpu.serve.controller import ServeController

        assert ServeController._pick_role(ctl, state) == "prefill"

    def test_split_change_drains_only_the_over_pool(self):
        ctl = self._controller()
        state = self._deploy(ctl, 4, 1)
        self._seed_live(state, ["prefill", "decode", "decode", "decode"])
        state = self._deploy(ctl, 4, 2)
        roles = [state.replica_roles.get(t) for t in state.replica_tags]
        assert roles == ["prefill", "decode", "decode"]
        from ray_tpu.serve.controller import ServeController

        assert ServeController._pick_role(ctl, state) == "prefill"

    def test_disagg_to_colocated_drains_roled(self):
        ctl = self._controller()
        state = self._deploy(ctl, 4, 2)
        self._seed_live(
            state, ["prefill", "prefill", "decode", "decode"]
        )
        state = self._deploy(ctl, 4, 0)
        assert state.target_prefill == 0
        assert state.replicas == [] and not state.replica_roles

    def test_split_shrink_spares_correctly_roled_starting_replica(self):
        """Redeploy 2->1 prefill while a decode replica is still STARTING:
        the drain must take the excess prefill replica, not whatever
        drains first — killing the starting decode replica would leave a
        2-prefill fleet that nothing ever corrects (pure rebalances have
        no actuation)."""
        ctl = self._controller()
        state = self._deploy(ctl, 4, 2)
        self._seed_live(state, ["prefill", "prefill", "decode"])
        state.starting = [(object(), "a#d#3", 0.0)]
        state.replica_roles["a#d#3"] = "decode"
        state = self._deploy(ctl, 4, 1)
        assert [(t, state.replica_roles.get(t))
                for t in state.replica_tags] == [
            ("a#d#0", "prefill"), ("a#d#2", "decode")]
        assert [t for _, t, _ in state.starting] == ["a#d#3"], (
            "the correctly-roled starting decode replica was drained"
        )

    def test_unchanged_split_keeps_replicas(self):
        ctl = self._controller()
        state = self._deploy(ctl, 4, 2)
        self._seed_live(
            state, ["prefill", "prefill", "decode", "decode"]
        )
        live = list(state.replicas)
        state = self._deploy(ctl, 4, 2)
        assert state.replicas == live


# ------------------------------------------------------------ serve fleet
@pytest.fixture
def disagg_cluster():
    """Real multiprocess cluster (replicas in separate worker processes —
    the handoff crosses real process boundaries and the arena)."""
    ray_tpu.init(num_cpus=4)
    serve.start(http_options={"host": "127.0.0.1", "port": 0})
    yield
    serve.shutdown()
    ray_tpu.shutdown()


@pytest.fixture(params=["stream", "ring", "off"])
def disagg_cluster_lander(request):
    """disagg_cluster pinned to one native-lander mode. The env must be set
    BEFORE init: workers inherit the driver's environ through the node
    agent's spawn-env template, so this is how the mode reaches the decode
    replica's import path."""
    lander = request.param
    if lander in ("stream", "ring"):
        from ray_tpu import native as native_mod

        if native_mod.load_bulk_lib() is None:
            pytest.skip(
                f"native bulk lander unbuildable: "
                f"{native_mod.bulk_build_error()}"
            )
    old = os.environ.get("RAY_TPU_BULK_NATIVE_LANDER")
    _lander_env(lander)
    ray_tpu.init(num_cpus=4)
    serve.start(http_options={"host": "127.0.0.1", "port": 0})
    yield lander
    serve.shutdown()
    ray_tpu.shutdown()
    if old is None:
        os.environ.pop("RAY_TPU_BULK_NATIVE_LANDER", None)
    else:
        os.environ["RAY_TPU_BULK_NATIVE_LANDER"] = old
    from ray_tpu.core import config as rt_config

    rt_config._reset_cache_for_tests()


def _engine_opts(**kw):
    return {**dict(num_blocks=64, block_size=4, max_num_seqs=4, seed=3), **kw}


def _replica_view(app, dep="LLMDeployment"):
    from ray_tpu.serve.handle import Router

    r = Router.get_or_create(app, dep)
    r._refresh(force=True)
    with r._lock:
        return (list(r._info["replicas"]), list(r._info["replica_tags"]),
                r._replica_roles())


def _reference_tokens(prompt, n, engine_opts):
    import jax.numpy as jnp

    from ray_tpu.models.gpt import GPTConfig
    from ray_tpu.serve.engine import EngineOptions, InferenceEngine

    cfg = GPTConfig(**{**TINY, "dtype": jnp.float32})
    e = InferenceEngine(cfg, options=EngineOptions(**engine_opts))
    e.start()
    out = e.generate(prompt, n)
    e.shutdown()
    return out


@pytest.mark.cluster
class TestDisaggServe:
    def test_two_pool_fleet_handoff_parity(self, disagg_cluster):
        """1 prefill + 1 decode replica: the public handle's generate runs
        the full prefill->export->import->decode orchestration with
        token-exact parity, the roles land where the controller assigned
        them, and the transfer counters prove the KV actually moved."""
        opts = _engine_opts()
        app = serve.LLMDeployment.options(
            num_replicas=2, prefill_replicas=1, max_ongoing_requests=64,
        ).bind(model="gpt2-small",
               model_overrides={**TINY, "dtype": "float32"},
               engine_options=opts)
        serve.run(app, name="disagg", route_prefix="/disagg", timeout_s=600)
        h = serve.get_app_handle("disagg")
        prompt = list(range(1, 19))  # 4 full blocks at bs=4
        N = 12
        ref = _reference_tokens(prompt, N, opts)

        res = h.generate.remote(prompt, N).result(timeout_s=180)
        assert res["tokens"] == ref, "disagg parity broke through serve"

        # Streaming rides the same orchestration (first token from the
        # prefill pool, rest from the decode pool).
        toks = list(
            h.options(stream=True).generate_stream.remote(prompt, N)
        )
        assert toks == ref

        replicas, tags, roles = _replica_view("disagg")
        assert sorted(r for r in roles if r) == ["decode", "prefill"]
        stats = {
            role: ray_tpu.get(
                rep.handle_request.remote("engine_stats", (), {})
            )
            for rep, role in zip(replicas, roles)
        }
        nfull = len(prompt) // 4
        assert stats["prefill"]["blocks_exported"] >= nfull
        assert stats["decode"]["blocks_imported"] == nfull, (
            "second request must reuse the first import"
        )
        assert stats["decode"]["prefix_cache_hits"] >= 2 * nfull
        # Controller view: pool target + per-replica roles are exposed.
        info_roles = sorted(r for r in roles if r)
        assert info_roles == ["decode", "prefill"]
        serve.delete("disagg")

    def test_disagg_request_trace_end_to_end(self, disagg_cluster):
        """Flight-recorder acceptance: ONE x-request-id covers the whole
        disagg path — the router's prefill handoff, the kv export on the
        prefill replica, the kv fetch + import on the decode replica, and
        the decode itself — all merged into the controller timeline in
        causal order, joined into the request's trace forest, and drawn
        as `disagg/<rid>` flow arrows in the merged Perfetto export."""
        import urllib.request

        from ray_tpu.core import api
        from ray_tpu.util import flight as flight_mod
        from ray_tpu.util import tracing

        opts = _engine_opts()
        app = serve.LLMDeployment.options(
            num_replicas=2, prefill_replicas=1, max_ongoing_requests=64,
        ).bind(model="gpt2-small",
               model_overrides={**TINY, "dtype": "float32"},
               engine_options=opts)
        serve.run(app, name="dtrace", route_prefix="/dtrace", timeout_s=600)
        port = serve.http_port()
        body = json.dumps(
            {"prompt": list(range(1, 19)), "max_new_tokens": 6}
        ).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/dtrace", data=body, method="POST"
        )
        resp = urllib.request.urlopen(req, timeout=180)
        rid = resp.headers.get("x-request-id")
        assert rid and len(json.loads(resp.read())["tokens"]) == 6

        backend = api._global_runtime().backend
        want = {"disagg.prefill_handoff", "kv.export", "kv.fetch",
                "kv.import", "disagg.decode"}
        end = time.monotonic() + 30.0
        spans = []
        while time.monotonic() < end:
            spans = [
                e for e in ray_tpu.timeline()
                if e.get("event") == "span" and e.get("trace") == rid
            ]
            if want <= {e["name"] for e in spans}:
                break
            # On-demand pull: the replicas' rings flush via the
            # task_events piggyback when poked.
            backend._request({"type": "flight_pull"})
            time.sleep(0.3)
        names = {e["name"] for e in spans}
        assert want <= names, f"missing spans: {want - names}"

        # Causal order across three processes (router, prefill replica,
        # decode replica). EPS absorbs the RTT-midpoint clock-alignment
        # error — sub-ms on loopback, but the gaps here are also small.
        starts = {n: min(e["ts"] for e in spans if e["name"] == n)
                  for n in want}
        ends = {n: max(e["ts"] + e.get("dur", 0.0) for e in spans
                       if e["name"] == n) for n in want}
        EPS = 0.05
        assert starts["disagg.prefill_handoff"] <= starts["kv.export"] + EPS
        assert starts["kv.export"] <= starts["kv.import"] + EPS
        assert starts["kv.import"] <= starts["kv.fetch"] + EPS  # fetch is
        # part of the import; decode RPC brackets both.
        assert ends["disagg.decode"] + EPS >= ends["kv.import"]
        # The import moved the exported prefix, not nothing.
        imp = max((e for e in spans if e["name"] == "kv.import"),
                  key=lambda e: e["args"]["blocks"])
        assert imp["args"]["blocks"] == len(range(1, 19)) // 4

        # Same rid joins the classic trace forest (/api/traces view).
        t = tracing.trace_payload(ray_tpu.timeline(), trace_id=rid)["trace"]
        assert t is not None and want <= {s["name"] for s in t["spans"]}

        # Merged Perfetto export: this request's disagg flow arrows.
        chrome = flight_mod.merged_chrome_trace(
            ray_tpu.timeline(), trace_id=rid)
        tracing.validate_chrome_trace(chrome)
        assert any(e["ph"] == "s" and e["name"] == f"disagg/{rid}"
                   for e in chrome)
        assert any(e["ph"] == "f" and e["name"] == f"disagg/{rid}"
                   for e in chrome)
        serve.delete("dtrace")

    @pytest.mark.chaos
    def test_sigkill_prefill_replica_mid_handoff(self, disagg_cluster_lander):
        """SIGKILL the prefill replica's worker while its prefill runs:
        the router's fallback recomputes on a decode replica — the caller
        sees the exact colocated tokens, the stream never wedges, and the
        decode replica imported either nothing or a COMPLETE prefix (the
        all-or-nothing import contract), never a partial one. Parametrized
        over every native-lander path (stream/ring/off) — the chaos
        semantics must not depend on which lander lands the spans."""
        opts = _engine_opts(
            num_blocks=129, max_step_tokens=24, prefill_chunk_tokens=8,
            max_num_seqs=4,
        )
        app = serve.LLMDeployment.options(
            num_replicas=2, prefill_replicas=1, max_ongoing_requests=64,
        ).bind(model="gpt2-small",
               model_overrides={**TINY, "dtype": "float32"},
               engine_options=opts)
        serve.run(app, name="chaos", route_prefix="/chaos", timeout_s=600)
        h = serve.get_app_handle("chaos")

        replicas, tags, roles = _replica_view("chaos")
        pre_i = roles.index("prefill")
        dec_i = roles.index("decode")
        pre_hex = replicas[pre_i]._actor_id.hex()
        from ray_tpu.util.state import list_workers

        pid = next(
            w["pid"] for w in list_workers()
            if w.get("actor") == pre_hex
        )

        # 96-token prompt at 8 tokens/step: the prefill runs for many
        # engine steps — a kill right after arrival lands mid-prefill.
        prompt = [(13 * i + 7) % 60 + 1 for i in range(96)]
        N = 8
        ref = _reference_tokens(prompt, N, opts)

        result = {}

        def fire():
            try:
                result["res"] = h.generate.remote(prompt, N).result(
                    timeout_s=240
                )
            except Exception as e:  # noqa: BLE001
                result["err"] = e

        th = threading.Thread(target=fire, daemon=True)
        th.start()
        # Kill once the prefill replica has admitted the request.
        deadline = time.monotonic() + 30
        killed = False
        while time.monotonic() < deadline and not killed:
            try:
                st = ray_tpu.get(
                    replicas[pre_i].handle_request.remote(
                        "engine_stats", (), {}
                    ),
                    timeout=5,
                )
                if st["queue_depth"] + st["running"] > 0 or (
                    st["total_finished"] > 0
                ):
                    os.kill(pid, signal.SIGKILL)
                    killed = True
            except Exception:  # noqa: BLE001 — already dead
                killed = True
            time.sleep(0.02)
        assert killed, "never observed the request on the prefill replica"
        th.join(timeout=240)
        assert not th.is_alive(), "stream wedged after prefill SIGKILL"
        assert "err" not in result, f"request failed: {result.get('err')!r}"
        assert result["res"]["tokens"] == ref, (
            "post-kill recompute diverged from colocated decode"
        )
        # All-or-nothing import: the decode replica holds either no
        # imported blocks or the complete exported prefix.
        st = ray_tpu.get(
            replicas[dec_i].handle_request.remote("engine_stats", (), {})
        )
        assert st["blocks_imported"] in (0, len(prompt) // 4), (
            f"partial KV import after chaos: {st['blocks_imported']}"
        )
        # Flight acceptance: the aborted handoff left a death-kind span
        # (cap-exempt in the ring) on the merged timeline — the partial
        # trace stays readable even though the prefill replica's own ring
        # died unflushed with the SIGKILL.
        end = time.monotonic() + 20
        death = []
        while time.monotonic() < end and not death:
            death = [
                e for e in ray_tpu.timeline()
                if e.get("event") == "span"
                and e.get("name") == "disagg.prefill_abort"
            ]
            time.sleep(0.3)
        assert death, "no disagg.prefill_abort death span after SIGKILL"
        assert death[0]["args"]["kind"] == "death"
        assert death[0]["args"]["error"]
        serve.delete("chaos")

    def test_force_span_pull_rung(self, disagg_cluster):
        """The cross-machine rung on a one-box cluster: with the same-node
        read and whole-object rungs disabled, the import must come through
        `object_sources` + bulk span pulls — and parity must hold."""
        os.environ["RAY_TPU_KV_FORCE_SPAN_PULL"] = "1"
        try:
            opts = _engine_opts()
            app = serve.LLMDeployment.options(
                num_replicas=2, prefill_replicas=1, max_ongoing_requests=64,
            ).bind(model="gpt2-small",
                   model_overrides={**TINY, "dtype": "float32"},
                   engine_options=opts)
            serve.run(app, name="span", route_prefix="/span", timeout_s=600)
            h = serve.get_app_handle("span")
            prompt = list(range(2, 20))
            N = 8
            ref = _reference_tokens(prompt, N, opts)
            res = h.generate.remote(prompt, N).result(timeout_s=180)
            assert res["tokens"] == ref
            replicas, tags, roles = _replica_view("span")
            st = ray_tpu.get(
                replicas[roles.index("decode")].handle_request.remote(
                    "engine_stats", (), {}
                )
            )
            assert st["blocks_imported"] == len(prompt) // 4, (
                "span-pull rung did not deliver the import"
            )
            serve.delete("span")
        finally:
            os.environ.pop("RAY_TPU_KV_FORCE_SPAN_PULL", None)
