"""Serve generation bench: static router-batching vs the continuous-batching
engine, under CONTINUOUS load.

Two serving modes over the same GPT config, both riding the full data plane
(HTTP proxy → router → replica):

  * static  — the r5 path: `@serve.batch` forms a fixed batch in the router
    and the replica decodes it TO COMPLETION with `make_generate` (one
    dispatch per batch). Every request in a batch pays the LONGEST
    generation in it; arrivals during a decode wait out the whole batch.
  * engine  — `serve.LLMDeployment`: iteration-level scheduler + paged KV
    cache (`ray_tpu/serve/engine/`). Short requests join mid-decode and
    exit at their own stop condition.

Continuous load: Poisson arrivals (seeded), mixed output lengths (short
with probability 1-p_long, long otherwise). The headline numbers are
USEFUL tokens/s (requested tokens only — the static path burns decode
steps on tokens nobody asked for) and the SHORT-request p99, which the
static path couples to the long-request duration.

Run (CPU, records BENCH_SERVE_engine.json):
    JAX_PLATFORMS=cpu python scripts/serve_bench.py --mode both \
        --out BENCH_SERVE_engine.json
Single mode: --mode engine | --mode static. The r5 TPU batch bench is
`--model gpt2-large --tpu --mode static`.

Two further workloads compare the engine against ITSELF at equal KV budget:

  * --workload prefix (records BENCH_SERVE_prefix.json): shared system
    prompt + varied tails under Poisson arrivals, prefix caching on vs off
    — the mixed-arrival re-bench of VERDICT open item 5.
  * --workload longprompt: long prompts interleaved with short ones,
    chunked vs monolithic prefill — measures how much a monolithic prefill
    stalls the short-request tail.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TINY = dict(
    vocab_size=512,
    n_layers=4,
    d_model=128,
    n_heads=4,
    d_head=32,
    d_mlp=512,
    max_seq=512,
    attn_impl="ref",
    remat=False,
    pos="rotary",
    rotary_dim=32,
    norm="rmsnorm",
    activation="swiglu",
)


def build_static_app(serve, model_kwargs, batch, new_tokens, tpu):
    """Ingress with router-side batching on __call__: proxy → router batcher
    → one `make_generate` dispatch per formed batch."""
    actor_opts = {"num_tpus": 1} if tpu else {}

    @serve.deployment(
        max_ongoing_requests=256,
        ray_actor_options=actor_opts,
        replica_startup_timeout_s=2400,
    )
    class GPTStatic:
        def __init__(self):
            import jax
            import numpy as np

            from ray_tpu.models.gpt import GPTConfig, init_params, make_generate

            self.jax, self.np = jax, np
            kw = dict(model_kwargs)
            if isinstance(kw.get("dtype"), str):
                kw["dtype"] = getattr(jax.numpy, kw["dtype"])
            cfg = GPTConfig(**kw)
            self.cfg = cfg
            self.params = init_params(jax.random.PRNGKey(0), cfg)
            self.gen = jax.jit(make_generate(cfg, new_tokens))
            self.rng = jax.random.PRNGKey(0)

        def platform(self):
            return self.jax.devices()[0].platform

        @serve.batch(max_batch_size=batch, batch_wait_timeout_s=0.02)
        def __call__(self, requests):
            jnp = self.jax.numpy
            np = self.np
            bodies = [r.json() for r in requests]
            P = len(bodies[0]["prompt"])
            arr = np.zeros((batch, P), np.int32)
            for i, b in enumerate(bodies):
                arr[i] = np.asarray(b["prompt"], np.int32)
            self.rng, key = self.jax.random.split(self.rng)
            out = np.asarray(self.gen(self.params, jnp.asarray(arr), key))
            # Fixed-shape decode: everyone rides to new_tokens; deliver the
            # requested prefix. The waste is the point being measured.
            return [
                {"tokens": out[i, : int(b.get("max_new_tokens", new_tokens))].tolist()}
                for i, b in enumerate(bodies)
            ]

    return GPTStatic.bind()


def build_engine_app(serve, model_kwargs, max_num_seqs, tpu,
                     engine_overrides=None, deploy_overrides=None):
    opts = dict(num_blocks=129, block_size=16, max_num_seqs=max_num_seqs)
    opts.update(engine_overrides or {})
    actor_opts = {"max_concurrency": 16, **({"num_tpus": 1} if tpu else {})}
    return serve.LLMDeployment.options(
        max_ongoing_requests=256, ray_actor_options=actor_opts,
        replica_startup_timeout_s=2400, **(deploy_overrides or {})
    ).bind(
        model="gpt2-small",
        model_overrides=model_kwargs,
        engine_options=opts,
    )


def run_load(base_url, reqs, rate, seed):
    """Poisson open-loop client: one thread per request, launched on the
    arrival clock (not closed-loop — stragglers must not throttle offered
    load). Returns per-request (kind, latency_s) + wall time."""
    import numpy as np
    import requests as rq

    rng = np.random.default_rng(seed)
    inter = rng.exponential(1.0 / rate, size=len(reqs))
    results = [None] * len(reqs)
    errors = []
    threads = []

    def fire(i, body):
        t0 = time.perf_counter()
        try:
            r = rq.post(base_url, json=body, timeout=600)
            out = r.json()
            if r.status_code != 200 or len(out.get("tokens", ())) != body["max_new_tokens"]:
                raise RuntimeError(f"bad response {r.status_code}: {out}")
            results[i] = time.perf_counter() - t0
        except Exception as e:  # noqa: BLE001
            errors.append((i, e))

    t_start = time.perf_counter()
    for i, body in enumerate(reqs):
        time.sleep(inter[i])
        th = threading.Thread(target=fire, args=(i, body), daemon=True)
        th.start()
        threads.append(th)
    for th in threads:
        th.join()
    wall = time.perf_counter() - t_start
    if errors:
        raise RuntimeError(
            f"{len(errors)}/{len(reqs)} requests failed; first: "
            f"req {errors[0][0]}: {errors[0][1]!r}"
        )
    return results, wall


def rows_platform(rows):
    """The platform the replicas of a report's rows ran on, as each replica's
    own `jax.devices()[0]` named it (never the --tpu flag)."""
    return "+".join(sorted({r["platform"] for r in rows.values()}))


def percentile(xs, p):
    """Rounded percentile, or None for an empty bucket (e.g. --p-long 0/1)."""
    if not xs:
        return None
    xs = sorted(xs)
    return round(xs[min(len(xs) - 1, int(len(xs) * p))], 3)


def bench_mode(mode, args, model_kwargs):
    import numpy as np

    import ray_tpu
    from ray_tpu import serve

    serve.start(http_options={"host": "127.0.0.1", "port": 0})
    app = (
        build_static_app(serve, model_kwargs, args.batch, args.long, args.tpu)
        if mode == "static"
        else build_engine_app(serve, model_kwargs, args.batch, args.tpu)
    )
    serve.run(app, name=f"bench_{mode}", route_prefix=f"/{mode}",
              timeout_s=2400)
    base = f"http://127.0.0.1:{serve.http_port()}/{mode}"

    rng = np.random.default_rng(args.seed)
    prompts = rng.integers(
        1, model_kwargs["vocab_size"], (args.requests, args.prompt_len)
    ).tolist()
    kinds = rng.random(args.requests) < args.p_long
    reqs = [
        {
            "prompt": prompts[i],
            "max_new_tokens": args.long if kinds[i] else args.short,
        }
        for i in range(args.requests)
    ]

    # Warm every shape bucket the run will hit (XLA compiles) with a burst
    # at full batch width, mixed lengths, OUTSIDE the timed window.
    warm = [
        {"prompt": prompts[0], "max_new_tokens": args.long if i % 2 else args.short}
        for i in range(args.batch)
    ]
    run_load(base, warm, rate=1000.0, seed=0)

    lats, wall = run_load(base, reqs, args.rate, args.seed + 1)
    useful = sum(r["max_new_tokens"] for r in reqs)
    short_l = [l for l, k in zip(lats, kinds) if not k]
    long_l = [l for l, k in zip(lats, kinds) if k]
    out = {
        "mode": mode,
        "requests": args.requests,
        "wall_s": round(wall, 2),
        "useful_tokens_per_s": round(useful / wall, 1),
        "device_tokens_per_s": round(
            (args.requests * args.long if mode == "static" else useful) / wall, 1
        ),
        "short": {
            "n": len(short_l),
            "new_tokens": args.short,
            "p50_s": percentile(short_l, 0.50),
            "p99_s": percentile(short_l, 0.99),
        },
        "long": {
            "n": len(long_l),
            "new_tokens": args.long,
            "p50_s": percentile(long_l, 0.50),
            "p99_s": percentile(long_l, 0.99),
        },
    }
    h = serve.get_app_handle(f"bench_{mode}")
    if mode == "engine":
        out["engine_stats"] = h.engine_stats.remote().result(timeout_s=30)
        out["platform"] = out["engine_stats"]["platform"]
    else:
        out["platform"] = h.platform.remote().result(timeout_s=30)
    serve.delete(f"bench_{mode}")
    return out


def _summarize(lats, kinds, reqs, wall, args):
    useful = sum(r["max_new_tokens"] for r in reqs)
    short_l = [l for l, k in zip(lats, kinds) if not k]
    long_l = [l for l, k in zip(lats, kinds) if k]
    return {
        "requests": len(reqs),
        "wall_s": round(wall, 2),
        "useful_tokens_per_s": round(useful / wall, 1),
        "short": {
            "n": len(short_l),
            "new_tokens": args.short,
            "p50_s": percentile(short_l, 0.50),
            "p99_s": percentile(short_l, 0.99),
        },
        "long": {
            "n": len(long_l),
            "new_tokens": args.long,
            "p50_s": percentile(long_l, 0.50),
            "p99_s": percentile(long_l, 0.99),
        },
    }


def _bench_engine_config(label, args, model_kwargs, engine_overrides, reqs,
                         kinds, warm):
    """One engine app under one EngineOptions config, Poisson load."""
    from ray_tpu import serve

    serve.start(http_options={"host": "127.0.0.1", "port": 0})
    app = build_engine_app(
        serve, model_kwargs, args.batch, args.tpu, engine_overrides
    )
    serve.run(app, name=f"bench_{label}", route_prefix=f"/{label}",
              timeout_s=2400)
    base = f"http://127.0.0.1:{serve.http_port()}/{label}"
    # Warm every shape bucket (XLA compiles) outside the timed window; for
    # cache-on configs this also steadies the prefix cache — the scenario
    # being measured is the steady state, not the first-ever request.
    run_load(base, warm, rate=1000.0, seed=0)
    lats, wall = run_load(base, reqs, args.rate, args.seed + 1)
    out = _summarize(lats, kinds, reqs, wall, args)
    out["engine_options"] = dict(engine_overrides)
    h = serve.get_app_handle(f"bench_{label}")
    stats = h.engine_stats.remote().result(timeout_s=30)
    out["engine_stats"] = stats
    out["platform"] = stats["platform"]
    out["ttft_p50_s"] = stats.get("ttft_p50_s")
    serve.delete(f"bench_{label}")
    print(json.dumps({label: out}), flush=True)
    return out


def bench_prefix(args, model_kwargs):
    """Shared-prefix Poisson workload (VERDICT open item 5's mixed-arrival
    re-bench): one common system prompt + per-request varied tails, mixed
    output lengths, engine-vs-engine with prefix caching on vs off at EQUAL
    KV budget."""
    import numpy as np

    rng = np.random.default_rng(args.seed)
    V = model_kwargs["vocab_size"]
    system = rng.integers(1, V, args.prefix_len).tolist()
    kinds = rng.random(args.requests) < args.p_long
    reqs = [
        {
            "prompt": system + rng.integers(1, V, args.tail_len).tolist(),
            "max_new_tokens": args.long if kinds[i] else args.short,
        }
        for i in range(args.requests)
    ]
    warm = [
        {"prompt": system + rng.integers(1, V, args.tail_len).tolist(),
         "max_new_tokens": args.long if i % 2 else args.short}
        for i in range(args.batch)
    ]
    rows = {}
    for label, overrides in (
        ("cache_on", {"enable_prefix_caching": True}),
        ("cache_off", {"enable_prefix_caching": False}),
    ):
        rows[label] = _bench_engine_config(
            label, args, model_kwargs, overrides, reqs, kinds, warm
        )
    on, off = rows["cache_on"], rows["cache_off"]
    comparison = {
        "useful_tokens_per_s_ratio": round(
            on["useful_tokens_per_s"] / off["useful_tokens_per_s"], 2
        ),
    }
    if on["ttft_p50_s"] and off["ttft_p50_s"]:
        comparison["ttft_p50_ratio_off_over_on"] = round(
            off["ttft_p50_s"] / on["ttft_p50_s"], 2
        )
    return {
        "metric": "serve_shared_prefix_cache_on_vs_off",
        "config": {
            "model": args.model,
            "rate_req_s": args.rate,
            "prefix_len": args.prefix_len,
            "tail_len": args.tail_len,
            "short": args.short,
            "long": args.long,
            "p_long": args.p_long,
            "batch": args.batch,
            "kv_budget_blocks": 129,
            "platform": rows_platform(rows),
        },
        "results": rows,
        "comparison": comparison,
    }


def bench_longprompt(args, model_kwargs):
    """Long-prompt interference: long prompts (``--prefix-len`` tokens,
    unshared) arrive alongside short ones; chunked prefill (small chunk)
    vs monolithic (chunk >= prompt) at equal KV budget. The number to watch
    is the SHORT-request tail — monolithic prefills stall every decode
    stream for the whole long prompt."""
    import numpy as np

    rng = np.random.default_rng(args.seed)
    V = model_kwargs["vocab_size"]
    kinds = rng.random(args.requests) < args.p_long  # long = long PROMPT
    reqs = [
        {
            "prompt": rng.integers(
                1, V, args.prefix_len if kinds[i] else args.tail_len
            ).tolist(),
            "max_new_tokens": args.short,
        }
        for i in range(args.requests)
    ]
    warm = [
        {"prompt": rng.integers(
            1, V, args.prefix_len if i % 2 else args.tail_len).tolist(),
         "max_new_tokens": args.short}
        for i in range(args.batch)
    ]
    budget = 1 << (args.prefix_len - 1).bit_length()
    rows = {}
    for label, overrides in (
        ("chunked", {"prefill_chunk_tokens": 32,
                     "max_step_tokens": 64,
                     "enable_prefix_caching": False}),
        ("monolithic", {"prefill_chunk_tokens": budget,
                        "max_step_tokens": budget + args.batch + 1,
                        "enable_prefix_caching": False}),
    ):
        rows[label] = _bench_engine_config(
            label, args, model_kwargs, overrides, reqs, kinds, warm
        )
    ch, mono = rows["chunked"], rows["monolithic"]
    comparison = {}
    if ch["short"]["p99_s"] and mono["short"]["p99_s"]:
        comparison["short_p99_ratio_mono_over_chunked"] = round(
            mono["short"]["p99_s"] / ch["short"]["p99_s"], 2
        )
    return {
        "metric": "serve_longprompt_chunked_vs_monolithic_prefill",
        "config": {
            "model": args.model,
            "rate_req_s": args.rate,
            "long_prompt_len": args.prefix_len,
            "short_prompt_len": args.tail_len,
            "new_tokens": args.short,
            "p_long_prompt": args.p_long,
            "batch": args.batch,
            "platform": rows_platform(rows),
        },
        "results": rows,
        "comparison": comparison,
    }


def _replica_stats(app_name, deployment="LLMDeployment"):
    """Per-replica engine stats straight off the routable replica set (the
    driver-side router's snapshot), raw latency windows included."""
    import ray_tpu
    from ray_tpu.serve.handle import Router

    r = Router.get_or_create(app_name, deployment)
    r._refresh(force=True)
    with r._lock:
        replicas = list(r._info["replicas"])
        tags = list(r._info["replica_tags"])
    out = {}
    for tag, h in zip(tags, replicas):
        out[tag] = ray_tpu.get(
            h.handle_request.remote("engine_stats", (), {"include_raw": True})
        )
    return out


def _bench_fleet_config(label, args, model_kwargs, reqs, kinds, warm,
                        replicas, engine_overrides, deploy_overrides,
                        rate=None):
    """One multi-replica engine app under one routing/spec config."""
    from ray_tpu import serve

    serve.start(http_options={"host": "127.0.0.1", "port": 0})
    app = build_engine_app(
        serve, model_kwargs, args.batch, args.tpu, engine_overrides,
        deploy_overrides,
    )
    name = f"bench_{label}"
    serve.run(app, name=name, route_prefix=f"/{label}", timeout_s=2400)
    base = f"http://127.0.0.1:{serve.http_port()}/{label}"
    run_load(base, warm, rate=1000.0, seed=0)
    lats, wall = run_load(base, reqs, rate or args.rate, args.seed + 1)
    out = _summarize(lats, kinds, reqs, wall, args)
    per_replica = _replica_stats(name)
    ttfts, pre_ttfts, hits, misses, host_hits = [], [], 0, 0, 0
    spec_prop = spec_acc = 0
    for tag, st in per_replica.items():
        t_recent = st.pop("ttft_recent", [])
        ttfts += t_recent
        if st.get("role") == "prefill":
            # Disagg: the REAL first token is emitted by the prefill pool;
            # the decode pool's internal "first token" is token #2.
            pre_ttfts += t_recent
        st.pop("tpot_recent", None)
        hits += st["prefix_cache_hits"]
        misses += st["prefix_cache_misses"]
        host_hits += st.get("host_tier_hits", 0)
        spec_prop += st["spec_proposed"]
        spec_acc += st["spec_accepted"]
    ttfts = pre_ttfts or ttfts
    out["replicas"] = replicas
    out["platform"] = rows_platform(per_replica)
    out["engine_options"] = dict(engine_overrides)
    out["per_replica"] = {
        t: {
            k: st[k]
            for k in ("role", "total_tokens", "total_finished",
                      "prefix_cache_hits", "prefix_cache_misses",
                      "host_tier_hits", "blocks_imported", "blocks_exported",
                      "spec_acceptance_rate", "ttft_p50_s")
            if k in st
        }
        for t, st in per_replica.items()
    }
    out["ttft_p50_s"] = percentile(ttfts, 0.50)   # pooled across replicas
    out["ttft_p99_s"] = percentile(ttfts, 0.99)
    out["prefix_hit_rate"] = (
        round(hits / (hits + misses), 4) if hits + misses else None
    )
    out["host_tier_hits"] = host_hits
    out["spec_acceptance_rate"] = (
        round(spec_acc / spec_prop, 4) if spec_prop else None
    )
    serve.delete(name)
    # The next config must route fresh, not through this app's cached router.
    from ray_tpu.serve.handle import Router

    with Router._routers_lock:
        Router._routers.pop((name, "LLMDeployment"), None)
    print(json.dumps({label: out}), flush=True)
    return out


def bench_fleet(args, model_kwargs):
    """Fleet-level shared-prefix Poisson mix (the BENCH_SERVE_prefix
    scenario lifted to a multi-replica fleet): G prefix groups of shared
    system prompts + varied tails, mixed output lengths, at EQUAL total KV
    budget per config. Two comparisons:

      * prefix-affinity routing vs power-of-two — aggregate prefix-hit
        rate and pooled TTFT p50/p99 (affinity concentrates each group on
        one replica's cache; pow2 smears it over all of them);
      * speculative decoding on vs off (repetitive decode-heavy mix) —
        useful tokens/s at the measured draft acceptance rate.
    """
    import numpy as np

    rng = np.random.default_rng(args.seed)
    V = model_kwargs["vocab_size"]
    groups = [
        rng.integers(1, V, args.prefix_len).tolist()
        for _ in range(args.prefix_groups)
    ]
    kinds = rng.random(args.requests) < args.p_long
    gidx = rng.integers(0, len(groups), args.requests)
    reqs = [
        {
            "prompt": groups[gidx[i]] + rng.integers(1, V, args.tail_len).tolist(),
            "max_new_tokens": args.long if kinds[i] else args.short,
        }
        for i in range(args.requests)
    ]
    warm = [
        {"prompt": rng.integers(1, V, args.tail_len).tolist(),
         "max_new_tokens": args.long if i % 2 else args.short}
        for i in range(args.batch)
    ]
    per_replica_blocks = max(args.kv_blocks // args.replicas, 2)
    rows = {}
    for label, affinity in (("affinity", True), ("pow2", False)):
        rows[label] = _bench_fleet_config(
            label, args, model_kwargs, reqs, kinds, warm, args.replicas,
            dict(num_blocks=per_replica_blocks, block_size=16),
            dict(num_replicas=args.replicas,
                 prefix_affinity_routing=affinity),
        )

    # Spec decode: single replica, SATURATED (burst arrivals — the number
    # being measured is decode throughput, not arrival spread) with short
    # repetitive prompts (prompt lookup drafts need self-similar context;
    # short tables keep the step decode-dispatch-bound, which is the cost
    # speculative verify amortizes).
    pattern = rng.integers(1, V, 8).tolist()
    spec_plen = min(32, args.prefix_len)
    rep_prompt = (pattern * ((spec_plen // 8) + 1))[:spec_plen]
    spec_reqs = [
        {"prompt": list(rep_prompt), "max_new_tokens": args.long}
        for _ in range(args.requests)
    ]
    spec_kinds = [True] * len(spec_reqs)
    spec_warm = [
        {"prompt": list(rep_prompt), "max_new_tokens": args.long}
        for _ in range(args.batch)
    ]
    for label, k in (("spec_off", 0), ("spec_on", 4)):
        rows[label] = _bench_fleet_config(
            label, args, model_kwargs, spec_reqs, spec_kinds, spec_warm, 1,
            dict(num_blocks=args.kv_blocks, block_size=16, spec_tokens=k),
            dict(num_replicas=1),
            rate=1000.0,
        )

    aff, p2 = rows["affinity"], rows["pow2"]
    son, soff = rows["spec_on"], rows["spec_off"]
    comparison = {
        "prefix_hit_rate_affinity": aff["prefix_hit_rate"],
        "prefix_hit_rate_pow2": p2["prefix_hit_rate"],
        "ttft_p50_ratio_pow2_over_affinity": (
            round(p2["ttft_p50_s"] / aff["ttft_p50_s"], 2)
            if aff["ttft_p50_s"] and p2["ttft_p50_s"] else None
        ),
        "ttft_p99_ratio_pow2_over_affinity": (
            round(p2["ttft_p99_s"] / aff["ttft_p99_s"], 2)
            if aff["ttft_p99_s"] and p2["ttft_p99_s"] else None
        ),
        "spec_tokens_per_s_ratio": round(
            son["useful_tokens_per_s"] / soff["useful_tokens_per_s"], 2
        ),
        "spec_acceptance_rate": son["spec_acceptance_rate"],
    }
    return {
        "metric": "serve_fleet_affinity_autoscale_spec",
        "config": {
            "model": args.model,
            "replicas": args.replicas,
            "prefix_groups": args.prefix_groups,
            "rate_req_s": args.rate,
            "prefix_len": args.prefix_len,
            "tail_len": args.tail_len,
            "short": args.short,
            "long": args.long,
            "p_long": args.p_long,
            "batch": args.batch,
            "kv_blocks_total": args.kv_blocks,
            "platform": rows_platform(rows),
        },
        "results": rows,
        "comparison": comparison,
    }


def bench_disagg(args, model_kwargs):
    """Disaggregated prefill/decode vs the colocated fleet (ROADMAP item 1
    workload: Poisson mix with LONG shared system prompts, equal total KV
    budget, equal replica count), each at a moderate AND a saturating
    arrival rate. Two headline properties:

      * cross-replica prefix hit rate — colocated affinity concentrates
        each prefix group on ONE replica's cache (per-replica 0.65 in
        BENCH_SERVE_fleet.json); disagg makes the cache cluster-wide: the
        prefill pool computes each prefix once and every decode replica
        IMPORTS it over the bulk plane instead of recomputing, so the
        aggregate hit rate should rise well above the per-replica number;
      * p50 TTFT vs decode load — in the colocated fleet, saturating
        decode lanes contend with every long prefill, inflating TTFT; a
        disaggregated prefill pool keeps computing first tokens at its own
        pace, so TTFT stays ~flat as the decode side saturates.
    """
    import numpy as np

    rng = np.random.default_rng(args.seed)
    V = model_kwargs["vocab_size"]
    groups = [
        rng.integers(1, V, args.prefix_len).tolist()
        for _ in range(args.prefix_groups)
    ]
    kinds = rng.random(args.requests) < args.p_long
    gidx = rng.integers(0, len(groups), args.requests)
    reqs = [
        {
            "prompt": groups[gidx[i]] + rng.integers(1, V, args.tail_len).tolist(),
            "max_new_tokens": args.long if kinds[i] else args.short,
        }
        for i in range(args.requests)
    ]
    warm = [
        {"prompt": rng.integers(1, V, args.tail_len).tolist(),
         "max_new_tokens": args.long if i % 2 else args.short}
        for i in range(args.batch)
    ]
    per_replica_blocks = max(args.kv_blocks // args.replicas, 2)
    engine = dict(num_blocks=per_replica_blocks, block_size=16)
    rates = {"moderate": args.rate, "saturated": args.rate * args.rate_mult}
    rows = {}
    for mode, deploy in (
        ("colocated", dict(num_replicas=args.replicas)),
        ("disagg", dict(num_replicas=args.replicas, prefill_replicas=1)),
    ):
        for rname, rate in rates.items():
            rows[f"{mode}_{rname}"] = _bench_fleet_config(
                f"{mode}_{rname}", args, model_kwargs, reqs, kinds, warm,
                args.replicas, engine, deploy, rate=rate,
            )

    def ratio(a, b):
        return round(a / b, 2) if a and b else None

    co_lo, co_hi = rows["colocated_moderate"], rows["colocated_saturated"]
    di_lo, di_hi = rows["disagg_moderate"], rows["disagg_saturated"]
    comparison = {
        # Fleet-wide cache: aggregate hit rate under the saturating mix.
        "prefix_hit_rate_disagg": di_hi["prefix_hit_rate"],
        "prefix_hit_rate_colocated": co_hi["prefix_hit_rate"],
        "prefix_hit_rate_fleet_baseline": 0.65,  # BENCH_SERVE_fleet.json
        # TTFT flatness: how much the p50 inflates when decode saturates.
        "ttft_p50_inflation_colocated": ratio(
            co_hi["ttft_p50_s"], co_lo["ttft_p50_s"]
        ),
        "ttft_p50_inflation_disagg": ratio(
            di_hi["ttft_p50_s"], di_lo["ttft_p50_s"]
        ),
        # The tail is the honest flatness signal on a shared-CPU host (the
        # p50 moderate baselines are sub-hundred-ms, so tiny absolute
        # shifts read as huge p50 ratios): a disaggregated prefill pool's
        # p99 barely moves as decode saturates.
        "ttft_p99_inflation_colocated": ratio(
            co_hi["ttft_p99_s"], co_lo["ttft_p99_s"]
        ),
        "ttft_p99_inflation_disagg": ratio(
            di_hi["ttft_p99_s"], di_lo["ttft_p99_s"]
        ),
        "ttft_p50_ratio_colocated_over_disagg_saturated": ratio(
            co_hi["ttft_p50_s"], di_hi["ttft_p50_s"]
        ),
        "ttft_p99_ratio_colocated_over_disagg_saturated": ratio(
            co_hi["ttft_p99_s"], di_hi["ttft_p99_s"]
        ),
        "kv_blocks_imported": sum(
            r.get("blocks_imported", 0)
            for r in di_hi["per_replica"].values()
        ),
    }
    return {
        "metric": "serve_disagg_vs_colocated_fleet",
        "config": {
            "model": args.model,
            "replicas": args.replicas,
            "prefill_replicas": 1,
            "prefix_groups": args.prefix_groups,
            "rate_req_s": args.rate,
            "rate_saturated_req_s": rates["saturated"],
            "prefix_len": args.prefix_len,
            "tail_len": args.tail_len,
            "short": args.short,
            "long": args.long,
            "p_long": args.p_long,
            "batch": args.batch,
            "kv_blocks_total": args.kv_blocks,
            "platform": rows_platform(rows),
        },
        "results": rows,
        "comparison": comparison,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["static", "engine", "both"],
                    default="both")
    ap.add_argument("--workload",
                    choices=["mixed", "prefix", "longprompt", "fleet",
                             "disagg"],
                    default="mixed",
                    help="mixed: static-vs-engine continuous load (r5); "
                         "prefix: shared-system-prompt Poisson load, prefix "
                         "cache on vs off; longprompt: chunked vs monolithic "
                         "prefill under long-prompt interference; fleet: "
                         "multi-replica shared-prefix mix — affinity vs "
                         "pow2 routing + spec decode on vs off; disagg: "
                         "prefill/decode pools + cluster-wide KV vs the "
                         "colocated fleet at moderate AND saturating rates")
    ap.add_argument("--rate-mult", type=float, default=4.0,
                    help="disagg workload: saturating rate = rate * this")
    ap.add_argument("--replicas", type=int, default=2,
                    help="fleet workload: replicas per deployment")
    ap.add_argument("--prefix-groups", type=int, default=4,
                    help="fleet workload: distinct shared system prompts")
    ap.add_argument("--kv-blocks", type=int, default=130,
                    help="fleet workload: TOTAL KV blocks split across "
                         "replicas (equal-budget comparisons)")
    ap.add_argument("--prefix-len", type=int, default=96,
                    help="shared system-prompt length (prefix workload) / "
                         "long prompt length (longprompt workload)")
    ap.add_argument("--tail-len", type=int, default=8,
                    help="per-request varied tail length (prefix workload) / "
                         "short prompt length (longprompt workload)")
    ap.add_argument("--model", choices=["tiny", "gpt2-large"], default="tiny")
    ap.add_argument("--tpu", action="store_true",
                    help="TPU replica (flash attention, num_tpus=1)")
    ap.add_argument("--requests", type=int, default=48)
    ap.add_argument("--rate", type=float, default=4.0,
                    help="Poisson arrival rate, req/s")
    ap.add_argument("--batch", type=int, default=8,
                    help="static batch size / engine max_num_seqs")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--short", type=int, default=4)
    ap.add_argument("--long", type=int, default=48)
    ap.add_argument("--p-long", type=float, default=0.3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None,
                    help="write the comparison JSON here as well")
    args = ap.parse_args()

    if args.model == "tiny":
        model_kwargs = dict(TINY)
        if not args.tpu:
            os.environ.setdefault("JAX_PLATFORMS", "cpu")
            model_kwargs["dtype"] = "float32"
    else:
        model_kwargs = dict(
            vocab_size=50304, n_layers=36, d_model=1280, n_heads=20,
            d_mlp=5120, max_seq=args.prompt_len + args.long,
            attn_impl="flash" if args.tpu else "ref", remat=False,
        )

    import ray_tpu

    ray_tpu.init()
    if args.workload != "mixed":
        bench = {
            "prefix": bench_prefix,
            "longprompt": bench_longprompt,
            "fleet": bench_fleet,
            "disagg": bench_disagg,
        }[args.workload]
        report = bench(args, model_kwargs)
        print(json.dumps(report), flush=True)
        if args.out:
            with open(args.out, "w") as f:
                json.dump(report, f, indent=2)
        from ray_tpu import serve

        serve.shutdown()
        ray_tpu.shutdown()
        return

    modes = ["static", "engine"] if args.mode == "both" else [args.mode]
    results = {}
    for mode in modes:
        results[mode] = bench_mode(mode, args, model_kwargs)
        print(json.dumps(results[mode]), flush=True)

    report = {
        "metric": "serve_continuous_load_engine_vs_static",
        "config": {
            "model": args.model,
            "rate_req_s": args.rate,
            "prompt_len": args.prompt_len,
            "short": args.short,
            "long": args.long,
            "p_long": args.p_long,
            "batch": args.batch,
            "platform": rows_platform(results),
        },
        "results": results,
    }
    if "static" in results and "engine" in results:
        report["comparison"] = {
            "useful_tokens_per_s_ratio": round(
                results["engine"]["useful_tokens_per_s"]
                / results["static"]["useful_tokens_per_s"],
                2,
            ),
        }
        sp = results["static"]["short"]["p99_s"]
        ep = results["engine"]["short"]["p99_s"]
        if sp and ep:
            report["comparison"]["short_p99_ratio"] = round(ep / sp, 3)
    print(json.dumps(report), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2)
    from ray_tpu import serve

    serve.shutdown()
    ray_tpu.shutdown()


if __name__ == "__main__":
    main()
