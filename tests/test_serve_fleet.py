"""Fleet serving plane (`ray_tpu.serve.fleet` + its wiring).

Three planes, each covered at the policy level (pure, fast) and through
the live stack (2-replica CPU engine fleet in local mode):

  * prefix-affinity routing — the routing key chain IS the kv_manager's
    content-hash chain, so a digest match predicts a prefix-cache hit;
    identical prompts from independent routers converge (rendezvous when
    cold, affinity once warm), stale digests fall back cleanly, and a
    saturated replica is never picked on affinity alone;
  * engine-metrics autoscaling — scale-up on queue/TTFT pressure measured
    AT the engines (no router traffic required), scale-down only when the
    fleet is quiet AND the coldest replica's prefix-hit economics agree;
  * speculative decoding — greedy spec decode is token-for-token identical
    to plain paged decode (the correctness gate), with real acceptance on
    self-repeating generations and drafts funded inside the step budget.
"""

import json
import threading
import time

import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.serve.engine import KVBlockManager
from ray_tpu.serve.fleet import (
    FleetSignals,
    decide_scale,
    pick_replica,
    routing_chain,
)

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


# ------------------------------------------------------------ routing policy
class TestRoutingPolicy:
    def test_routing_chain_matches_kv_digest(self):
        """The deep link between the planes: the router's chain over a
        prompt's leading full blocks must be found in the digest of a
        KV manager that computed that prompt — same hash, same truncation."""
        kv = KVBlockManager(num_blocks=32, block_size=4)
        toks = list(range(17))
        kv.allocate_cached("a", toks, len(toks) + 1)
        kv.register_computed("a", toks, len(toks))
        digest = set(kv.prefix_digest())
        chain = routing_chain(toks, block_size=4)
        assert len(chain) == 4  # (17-1)//4 full blocks
        assert set(chain) <= digest
        # A divergent prompt shares only the common-prefix entries.
        other = toks[:8] + [99] * 9
        chain2 = routing_chain(other, block_size=4)
        assert chain2[:2] == chain[:2] and chain2[2] != chain[2]
        assert set(chain2[:2]) <= digest and chain2[2] not in digest

    def test_affinity_picks_deepest_digest_match(self):
        prompt = list(range(40))
        chain = routing_chain(prompt, block_size=4)
        metas = [
            {"digest": chain[:1], "queue_depth": 0, "block_size": 4},
            {"digest": chain[:5], "queue_depth": 0, "block_size": 4},
            {"digest": [], "queue_depth": 0, "block_size": 4},
        ]
        idx, reason = pick_replica(
            chain, ["r0", "r1", "r2"], metas, {}, spill_threshold=8
        )
        assert (idx, reason) == (1, "affinity")

    def test_cold_prefix_rendezvous_is_deterministic(self):
        """No digest anywhere: two independent routers must still send the
        same prompt to the same replica (the second arrival hits the cache
        the first one warmed) — and different prompts must spread."""
        tags = ["r0", "r1", "r2", "r3"]
        metas = [{"digest": [], "queue_depth": 0, "block_size": 4}] * 4
        picks = set()
        for seed in range(12):
            chain = routing_chain([seed * 7 + t for t in range(20)], 4)
            a = pick_replica(chain, tags, metas, {}, 8)
            b = pick_replica(chain, tags, metas, {3: 2}, 8)  # other load
            assert a[1] == "rendezvous" and a[0] == b[0]
            picks.add(a[0])
        assert len(picks) > 1, "rendezvous mapped every prefix to one replica"

    def test_stale_digest_falls_back_cleanly(self):
        """Telemetry absent (controller hasn't probed yet / replicas just
        restarted): the router must still route deterministically, not
        crash or degrade to random."""
        chain = routing_chain(list(range(20)), 4)
        tags = ["r0", "r1"]
        a = pick_replica(chain, tags, [None, None], {}, 8)
        b = pick_replica(chain, tags, [None, None], {}, 8)
        assert a == b and a[1] == "rendezvous"
        # No routing key AND no telemetry -> power-of-two.
        idx, reason = pick_replica([], tags, [None, None], {}, 8)
        assert reason == "pow2" and idx in (0, 1)

    def test_spill_guard_overrides_affinity(self):
        prompt = list(range(40))
        chain = routing_chain(prompt, 4)
        metas = [
            {"digest": chain, "queue_depth": 50, "block_size": 4},  # drowning
            {"digest": [], "queue_depth": 0, "block_size": 4},
        ]
        idx, reason = pick_replica(
            chain, ["hot", "cold"], metas, {}, spill_threshold=8
        )
        assert idx == 1, "affinity routed into a drowning replica"
        # Whole fleet saturated: load spreading, not affinity.
        metas[1]["queue_depth"] = 60
        idx, reason = pick_replica(chain, ["hot", "cold"], metas, {}, 8)
        assert reason == "spill" and idx == 0  # lower load of the two

    def test_local_outstanding_counts_toward_spill(self):
        chain = routing_chain(list(range(40)), 4)
        metas = [
            {"digest": chain, "queue_depth": 0, "block_size": 4},
            {"digest": [], "queue_depth": 0, "block_size": 4},
        ]
        # The router's own in-flight count pushes the digest-matching
        # replica past the spill threshold.
        idx, _ = pick_replica(chain, ["a", "b"], metas, {0: 8}, 8)
        assert idx == 1


# ---------------------------------------------------------- autoscale policy
class TestAutoscalePolicy:
    def _sig(self, **kw):
        base = dict(replicas=2, ongoing=0.0, queue_depth=0.0,
                    ttft_p99_s=None, hit_rates=[None, None])
        base.update(kw)
        return FleetSignals(**base)

    def _decide(self, sig, **kw):
        base = dict(target_ongoing_requests=2.0, target_queue_depth=4.0,
                    ttft_p99_target_s=1.0, downscale_hit_rate=0.2)
        base.update(kw)
        return decide_scale(sig, **base)

    def test_up_on_queue_pressure(self):
        assert self._decide(self._sig(queue_depth=20.0)) == 1

    def test_up_on_ttft_tail(self):
        assert self._decide(self._sig(ttft_p99_s=3.0)) == 1

    def test_up_on_summed_router_ongoing(self):
        assert self._decide(self._sig(ongoing=10.0)) == 1

    def test_no_down_while_cache_hot(self):
        sig = self._sig(hit_rates=[0.9, 0.8])
        assert self._decide(sig) == 0, "killed a replica serving cache hits"

    def test_down_when_idle_and_cold(self):
        assert self._decide(self._sig(hit_rates=[0.05, 0.9])) == -1
        assert self._decide(self._sig(hit_rates=[None, None])) == -1

    def test_no_down_under_pressure(self):
        sig = self._sig(queue_depth=20.0, hit_rates=[0.0, 0.0])
        assert self._decide(sig) == 1

    def test_no_down_while_generations_in_flight(self):
        """Routers only report on NEW submissions — mid-generation a fleet
        looks router-quiet with empty admission queues, but sequences still
        DECODING must block scale-down (killing a replica drops them)."""
        sig = self._sig(running=3.0, hit_rates=[0.0, 0.0])
        assert self._decide(sig) == 0, "scaled down under in-flight decode"


# ------------------------------------------------- controller metric plumbing
class TestControllerAutoscaling:
    def _controller(self):
        """Bare controller (no actor, no reconcile thread) — the same
        construction test_serve uses for _drain."""
        import threading as _t

        from ray_tpu.serve.controller import ServeController

        ctl = ServeController.__new__(ServeController)
        ctl._lock = _t.RLock()
        ctl._version = 0
        ctl._apps = {}
        return ctl

    def _state(self, autoscaling, replicas=1):
        from ray_tpu.serve.controller import _DeploymentState

        state = _DeploymentState(
            {"name": "d", "opts": {"num_replicas": replicas,
                                   "autoscaling_config": autoscaling},
             "cls": b"", "init_args": b""}
        )
        state.replicas = [object() for _ in range(replicas)]
        state.replica_tags = [f"a#d#{i}" for i in range(replicas)]
        return state

    def test_router_reports_sum_not_blend(self):
        """THE undercount fix: two routers with 10 outstanding each must
        read as ~20, not ~10 (the old code EMA-blended both streams into
        one)."""
        # Autoscaling config with unreachable thresholds: the EMA advances
        # (inside _maybe_autoscale, exactly once per report) without any
        # scale action firing.
        inert = dict(min_replicas=1, max_replicas=1,
                     target_ongoing_requests=1e9, target_queue_depth=1e9,
                     upscale_delay_s=1e9, downscale_delay_s=1e9,
                     ttft_p99_target_s=None, downscale_hit_rate=0.0)
        ctl = self._controller()
        state = self._state(inert)
        ctl._apps["a"] = {"deployments": {"d": state}}
        for _ in range(30):
            ctl.record_request_metrics("a", "d", 10.0, router_id="r1")
            ctl.record_request_metrics("a", "d", 10.0, router_id="r2")
        assert state.ongoing_total(time.monotonic()) == 20.0
        assert state.ongoing_ema > 18.0, (
            f"two routers x10 converged to {state.ongoing_ema:.1f}, not ~20"
        )

    def test_dead_router_expires_from_sum(self):
        ctl = self._controller()
        state = self._state(None)
        ctl._apps["a"] = {"deployments": {"d": state}}
        ctl.record_request_metrics("a", "d", 10.0, router_id="r1")
        ctl.record_request_metrics("a", "d", 10.0, router_id="r2")
        # r2 stops reporting: age its report past the TTL.
        state.router_reports["r2"][1] -= 60.0
        assert state.ongoing_total(time.monotonic()) == 10.0
        assert "r2" not in state.router_reports

    def test_engine_pressure_scales_up_and_cold_idle_scales_down(self):
        """_maybe_autoscale driven purely by replica telemetry — no router
        reports at all (the 'driven by engine metrics' criterion at the
        controller level; the live-fleet variant is below)."""
        cfg = dict(min_replicas=1, max_replicas=3,
                   target_ongoing_requests=2.0, target_queue_depth=2.0,
                   upscale_delay_s=0.0, downscale_delay_s=0.0,
                   ttft_p99_target_s=None, downscale_hit_rate=0.5)
        ctl = self._controller()
        state = self._state(cfg, replicas=1)
        state.replica_meta["a#d#0"] = {
            "t": 0.0, "engine": {"queue_depth": 10, "prefix_hit_rate": 0.0},
        }
        ctl._maybe_autoscale(state)
        assert state.target_replicas == 2, "queue pressure did not scale up"
        state.last_scale_action_t = 0.0
        state.replica_meta["a#d#0"]["engine"] = {
            "queue_depth": 0, "ttft_p99_s": 9.0,
        }
        cfg["ttft_p99_target_s"] = 1.0
        ctl._maybe_autoscale(state)
        assert state.target_replicas == 3, "TTFT tail did not scale up"
        # Idle but HOT cache: held.
        state.last_scale_action_t = 0.0
        state.replica_meta["a#d#0"]["engine"] = {
            "queue_depth": 0, "prefix_hit_rate": 0.9,
        }
        ctl._maybe_autoscale(state)
        assert state.target_replicas == 3, "downscaled a hot-cache replica"
        # Idle and COLD: released.
        state.replica_meta["a#d#0"]["engine"] = {
            "queue_depth": 0, "prefix_hit_rate": 0.0,
        }
        ctl._maybe_autoscale(state)
        assert state.target_replicas == 2, "cold idle replica not released"


def test_metrics_never_boot_a_runtime():
    """Regression: Counter/Gauge records from an un-inited process must be
    dropped, not boot a whole local runtime (one engine-unit-test Gauge.set
    used to leak a runtime into the rest of the pytest session)."""
    from ray_tpu.util.metrics import Counter, Gauge

    Counter("fleet_leak_canary_total", "x").inc(1.0)
    Gauge("fleet_leak_canary", "x").set(2.0)
    assert not ray_tpu.is_initialized(), "a metric record booted the runtime"


# --------------------------------------------------------------- live fleet
@pytest.fixture
def serve_instance():
    ray_tpu.init(local_mode=True, ignore_reinit_error=True)
    serve.start()
    yield
    serve.shutdown()
    ray_tpu.shutdown()


def _fresh_router(app, dep):
    """An independent Router instance (≈ a handle in another process) —
    get_or_create would return the shared one."""
    from ray_tpu.serve.handle import Router

    return Router(app, dep)


class TestFleetSmoke:
    def test_two_replica_affinity_and_retry(self, serve_instance):
        """2-replica CPU engine fleet: (1) identical prompts from two
        independent routers pick the SAME replica while cold (rendezvous);
        (2) after serving the prompt, telemetry makes the pick an AFFINITY
        hit on the warmed replica and the prefix cache actually hits;
        (3) killing the picked replica behind the router's back is healed
        by the one-shot retry instead of surfacing a dead-handle error."""
        app = serve.LLMDeployment.options(num_replicas=2).bind(
            model="gpt2-small",
            model_overrides=TINY,
            engine_options=dict(num_blocks=64, block_size=4, max_num_seqs=4),
        )
        serve.run(app, name="fleet", route_prefix="/fleet", timeout_s=240)
        prompt = [11, 7, 3, 60, 2, 9, 1, 44] * 3  # 24 tokens = 6 blocks

        r1 = _fresh_router("fleet", "LLMDeployment")
        r2 = _fresh_router("fleet", "LLMDeployment")
        i1, _, _ = r1._pick_replica(prompt=prompt)
        r1._done(i1)
        i2, _, _ = r2._pick_replica(prompt=prompt)
        r2._done(i2)
        assert i1 == i2, "cold identical prompts diverged across routers"

        # Serve the prompt (warms replica i1's prefix cache), then wait for
        # the digest to travel replica -> controller -> router snapshot.
        assert len(
            r1.call("generate", (prompt,), {"max_new_tokens": 4}).result(
                timeout_s=120
            )["tokens"]
        ) == 4
        deadline = time.monotonic() + 20.0
        warmed = None
        while time.monotonic() < deadline:
            r2._refresh(force=True)
            metas = r2._info.get("replica_meta") or []
            if i1 < len(metas) and metas[i1] and metas[i1].get("digest"):
                warmed = metas[i1]
                break
            time.sleep(0.25)
        assert warmed, "hot-prefix digest never reached the router snapshot"
        i3, _, _ = r2._pick_replica(prompt=prompt)
        r2._done(i3)
        assert i3 == i1, "warm prompt routed away from its cache"

        # Prefix cache really hits on the warmed replica through the full
        # data plane (second identical prompt, same replica).
        stats0 = r2.call("engine_stats", (), {}).result(timeout_s=60)
        r2.call("generate", (prompt,), {"max_new_tokens": 4}).result(
            timeout_s=120
        )
        # engine_stats routes without a prompt; ask every replica and take
        # the max-hit one (the warmed replica's counter must have grown).
        hits = []
        with r2._lock:
            replicas = list(r2._info["replicas"])
        for h in replicas:
            hits.append(
                ray_tpu.get(
                    h.handle_request.remote("engine_stats", (), {})
                )["prefix_cache_hits"]
            )
        assert max(hits) >= 5, f"no prefix hits recorded on any replica: {hits}"

        # --- router retry: kill the routed replica behind the router.
        with r2._lock:
            dead = r2._info["replicas"][i1]
            live_idx = 1 - i1
        ray_tpu.kill(dead)
        # Bias the router so power-of-two/load would still pick the dead
        # one — the call must succeed anyway via forced-refresh retry.
        r2._outstanding[live_idx] = 50
        out = r2.call("generate", (prompt,), {"max_new_tokens": 3}).result(
            timeout_s=120
        )
        assert len(out["tokens"]) == 3, "retry did not heal the dead replica"
        serve.delete("fleet")

    def test_autoscaler_live_scale_up_and_down(self, serve_instance, tmp_path):
        """End-to-end: a deployment whose replicas report synthetic engine
        pressure through the REAL telemetry path (replica.telemetry ->
        reconcile -> _maybe_autoscale) scales up with zero request traffic,
        then back down when the signal goes idle+cold."""
        sig = tmp_path / "sig.json"
        sig.write_text(json.dumps({"queue_depth": 10, "prefix_hit_rate": 0.0}))

        @serve.deployment(
            autoscaling_config=dict(
                min_replicas=1, max_replicas=2, target_ongoing_requests=2.0,
                target_queue_depth=2.0, upscale_delay_s=0.0,
                downscale_delay_s=0.0, downscale_hit_rate=0.5,
            )
        )
        class FakeEngine:
            def __init__(self, path):
                self._path = path

            def fleet_state(self):
                return json.loads(open(self._path).read())

            def __call__(self, x):
                return x

        serve.run(FakeEngine.bind(str(sig)), name="fake", route_prefix="/fake",
                  timeout_s=60)

        def replica_count():
            st = serve.status()["applications"]["fake"]["deployments"]
            return st["FakeEngine"]["replica_states"]["RUNNING"]

        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline and replica_count() < 2:
            time.sleep(0.3)
        assert replica_count() == 2, "engine queue pressure did not scale up"

        sig.write_text(json.dumps({"queue_depth": 0, "prefix_hit_rate": 0.0}))
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline and replica_count() > 1:
            time.sleep(0.3)
        assert replica_count() == 1, "idle cold deployment did not scale down"
        serve.delete("fake")


# ------------------------------------------------------ speculative decoding
@pytest.fixture(scope="module")
def tiny_engine_parts():
    import jax

    from ray_tpu.models.gpt import GPTConfig, init_params

    cfg = GPTConfig(**{**TINY, "dtype": jax.numpy.float32})
    params = init_params(jax.random.PRNGKey(3), cfg)
    params = jax.tree_util.tree_map(lambda a: a * 3.0, params)
    return cfg, params


def _run_engine(cfg, params, prompt, n, **opts):
    from ray_tpu.serve.engine import EngineOptions, InferenceEngine

    eng = InferenceEngine(
        cfg,
        params=params,
        options=EngineOptions(
            **{**dict(num_blocks=64, block_size=4, max_num_seqs=4), **opts}
        ),
    )
    rid = eng.submit(prompt, max_new_tokens=n)
    res = {}
    t = threading.Thread(
        target=lambda: res.setdefault("t", list(eng.stream(rid)))
    )
    t.start()
    steps = 0
    while eng.scheduler.has_work() and steps < 500:
        eng.step()
        steps += 1
    t.join(10)
    assert steps < 500, "engine did not drain"
    eng.block_manager.check_invariants()
    return res["t"], eng, steps


class TestSpeculativeDecoding:
    def test_greedy_token_parity(self, tiny_engine_parts):
        """ACCEPTANCE GATE: greedy spec-decode output identical to
        non-speculative paged decode, across draft lengths."""
        import jax

        cfg, params = tiny_engine_parts
        for seed in (0, 5, 9):
            prompt = [int(t) for t in jax.random.randint(
                jax.random.PRNGKey(seed), (14,), 0, 64)]
            base, _, _ = _run_engine(cfg, params, prompt, 24)
            assert len(set(base)) > 3, "degenerate decode proves nothing"
            for k in (2, 4):
                spec, eng, _ = _run_engine(
                    cfg, params, prompt, 24, spec_tokens=k
                )
                assert spec == base, (
                    f"spec k={k} seed={seed} diverged from greedy decode"
                )

    def test_acceptance_and_fewer_steps_on_repetition(self, tiny_engine_parts):
        """A self-repeating greedy generation must get real draft
        acceptance — and finish in FEWER engine steps than one-token
        decode (that is the whole point)."""
        cfg, params = tiny_engine_parts
        prompt = [7, 3, 11, 60, 2, 9, 1, 7, 3, 11, 60, 2]
        base, _, base_steps = _run_engine(cfg, params, prompt, 32)
        spec, eng, spec_steps = _run_engine(
            cfg, params, prompt, 32, spec_tokens=4
        )
        assert spec == base
        assert eng.total_spec_proposed > 0
        assert eng.total_spec_accepted > 0, "no draft ever accepted"
        assert spec_steps < base_steps, (
            f"spec decode took {spec_steps} steps vs {base_steps} baseline"
        )
        st = eng.stats()
        assert 0.0 < st["spec_acceptance_rate"] <= 1.0

    def test_drafts_funded_inside_step_budget(self, tiny_engine_parts):
        """Scheduler invariant: decode lanes + funded drafts + prefill
        chunks never exceed max_step_tokens, and drafts show up in the
        work order accounting."""
        from ray_tpu.serve.engine import EngineOptions, InferenceEngine

        cfg, params = tiny_engine_parts
        eng = InferenceEngine(
            cfg, params=params,
            options=EngineOptions(
                num_blocks=64, block_size=4, max_num_seqs=4,
                max_step_tokens=12, prefill_chunk_tokens=8, spec_tokens=4,
            ),
        )
        rep = [5, 6, 7, 8]
        for i in range(3):
            eng.submit(rep * 4, max_new_tokens=20, request_id=f"r{i}")
        plans = []
        schedule = eng.scheduler.schedule

        def planned():      # every work order the engine's own loop ran
            plans.append(schedule())
            return plans[-1]

        eng.scheduler.schedule = planned
        steps = 0
        while eng.scheduler.has_work() and steps < 500:
            eng.step()
            steps += 1
        assert steps < 500
        for out in plans:
            assert out.step_tokens <= 12, (
                f"budget blown: {out.step_tokens} > 12"
            )
            for rid, d in out.drafts.items():
                assert 1 <= len(d) <= 4
        assert any(out.drafts for out in plans), (
            "identical lanes never produced a funded draft")
        eng.block_manager.check_invariants()

    def test_eos_mid_draft_stops_cleanly(self, tiny_engine_parts):
        """eos inside an accepted span must truncate the emission at the
        stop token (no trailing draft tokens leak to the stream)."""
        cfg, params = tiny_engine_parts
        prompt = [7, 3, 11, 60, 2, 9, 1, 7, 3, 11, 60, 2]
        base, _, _ = _run_engine(cfg, params, prompt, 32)
        eos = base[len(base) // 2]  # a token greedy decode provably emits

        def run(**opts):
            from ray_tpu.serve.engine import EngineOptions, InferenceEngine

            eng = InferenceEngine(
                cfg, params=params,
                options=EngineOptions(
                    num_blocks=64, block_size=4, max_num_seqs=4, **opts
                ),
            )
            rid = eng.submit(prompt, max_new_tokens=32, eos_token=eos)
            out = eng.stream(rid)
            res = {}
            t = threading.Thread(
                target=lambda: res.setdefault("t", list(out))
            )
            t.start()
            n = 0
            while eng.scheduler.has_work() and n < 500:
                eng.step()
                n += 1
            t.join(10)
            eng.block_manager.check_invariants()
            return res["t"], out.finish_reason

        # Both paths must agree on tokens AND the eos finish.
        b_toks, b_reason = run()
        s_toks, s_reason = run(spec_tokens=4)
        assert s_toks == b_toks and s_reason == b_reason == "eos"
        assert s_toks[-1] == eos and s_toks.count(eos) == 1

    def test_spec_requires_greedy(self, tiny_engine_parts):
        from ray_tpu.serve.engine import EngineOptions, InferenceEngine

        cfg, params = tiny_engine_parts
        with pytest.raises(ValueError, match="temperature"):
            InferenceEngine(
                cfg, params=params,
                options=EngineOptions(spec_tokens=4, temperature=0.7),
            )


class TestNGramProposer:
    def test_prompt_lookup_and_incremental_index(self):
        from ray_tpu.serve.engine.spec import NGramProposer

        p = NGramProposer(k=4, n=2)
        prompt = [1, 2, 3, 4, 5, 1, 2]
        out = []
        # Follows the earlier (1, 2) occurrence.
        assert p.propose("r", prompt, out, 4) == [3, 4, 5, 1]
        out += [3, 4]
        # Incremental: appended OUTPUT tokens extend the retained history
        # (the proposer never re-reads the prompt).
        assert p.propose("r", prompt, out, 4) == [5, 1, 2, 3]
        assert p.propose("r", prompt, out, 2) == [5, 1]   # budget clamp
        # Preemption fold (output -> prompt, token list unchanged) keeps
        # the retained history valid.
        assert p.propose("r", prompt + out, [], 4) == [5, 1, 2, 3]
        assert p.propose("x", [9, 8, 7], [], 4) == []     # no repeat
        p.forget("r")
        assert len(p) == 1  # only "x" left
