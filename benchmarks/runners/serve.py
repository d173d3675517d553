"""Runner kind `requests`: `serve.run(LLMDeployment)` on one chip, reached
as a caller reaches it, through `handle.options(stream=True).generate_stream`
(the HTTP route of `http_proxy.py` streams only when the ingress `__call__`
is a generator, and `LLMDeployment.__call__` is not: it answers with all
tokens at once, so time to first token cannot be seen through it).

From the program the runner takes public entry points only: the replica's
constructor, `generate`, `generate_stream` and `engine_stats`, the engine's
`submit` and `stream`, the `EngineOptions` fields, and the spans the program
records itself (every `engine.*` and `serve.*` span of the window with its
args, read once from `ray_tpu.timeline()` after `flight.flush()`). Sizes, the
program model, the reference and the bytes come from the configuration's
architecture module (`benchmarks/arch/`). Warm-up and the correctness check
are real requests. The replica is `LLMDeployment`'s own
class with probe methods added (`bench_*`) for what only the process that
holds the chip can do: weights made under one `jax.jit`, JAX's compile
events, the device's memory peak, the profiler. The one private name left
is that class's, `_LLMReplica` (PERF.md, Open questions)."""

from __future__ import annotations

import copy
import dataclasses
import math
import os
import shutil
import threading
import time

from .. import harness, readers, stats, traffic
from ray_tpu.serve.engine.deployment import LLMDeployment, _LLMReplica


class BenchReplica(_LLMReplica):
    def __init__(self, model, model_overrides, engine_options, seed, t0_wall):
        import jax

        from ray_tpu.models.gpt import CONFIGS, init_params

        self._phases = {}
        self._devices = jax.devices()            # attaches to the chip
        self._phases["attach_s"] = time.time() - t0_wall
        self._compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        cfg = CONFIGS[model](**model_overrides)
        t = time.perf_counter()
        self._params = jax.jit(lambda key: init_params(key, cfg))(jax.random.PRNGKey(seed))
        jax.block_until_ready(self._params)
        self._phases["weights_s"] = time.perf_counter() - t
        t = time.perf_counter()
        super().__init__(model, model_overrides, engine_options, params=self._params)
        self._phases["engine_s"] = time.perf_counter() - t

    def _on_event(self, event, duration, **kw):
        if "backend_compile" in event or "cache_retrieval" in event:
            self._compiles += 1

    # ------------------------------------------------------------- set-up
    def bench_check_tokens(self, arch, dims, seed, prompt_len, new_tokens):
        """A seeded prompt through `generate` (chunked prefill, then decode
        through the paged cache), greedy; against the full forward pass of the
        plain reference of the architecture module `arch` over the prompt and
        the tokens that came back. With
        random weights the largest logit changes on rounding, so the tokens
        are not compared with the reference's own choice: each is held to the
        reference's logits, and the error is how far below the reference's
        largest logit the chosen token's logit lies, over the largest logit
        in size. Returns (that error, tokens that are the reference's argmax)."""
        import jax.numpy as jnp
        import numpy as np

        t = time.perf_counter()
        rng = np.random.default_rng([seed, 1])    # not the window's own stream
        prompt = rng.integers(1, dims["vocab_size"], prompt_len).tolist()
        got = self.generate(prompt, new_tokens)["tokens"]
        if len(got) != new_tokens:
            raise RuntimeError(f"check request: asked {new_tokens} tokens, got {got}")
        full = jnp.asarray(prompt + got[:-1], jnp.int32)
        logits = harness.arch(arch).make_logits(dims)
        want = np.asarray(logits(self._params, full))[prompt_len - 1:]
        chosen = want[np.arange(new_tokens), got]
        err = float((want.max(-1) - chosen).max() / np.abs(want).max())
        self._phases["reference_s"] = time.perf_counter() - t
        return err, int((want.argmax(-1) == np.asarray(got)).sum())

    def bench_warm(self, waves, vocab):
        """Replay `traffic.warm_plan`: every wave submitted at once through
        the engine's `submit` and drained through `stream`."""
        import numpy as np

        t = time.perf_counter()
        rng = np.random.default_rng(0)
        c0 = self._compiles
        for wave in waves:
            rids = [self.engine.submit(rng.integers(1, vocab, length).tolist(), new)
                    for length, new in wave]
            for rid, (_, new) in zip(rids, wave):
                got = list(self.engine.stream(rid))
                if len(got) != new:
                    raise RuntimeError(f"warm-up request: asked {new} tokens, got {got}")
        self._phases["warm_s"] = time.perf_counter() - t
        return self._compiles - c0

    def bench_info(self):
        d = self._devices[0]
        return {"phases": dict(self._phases),
                "device": {"platform": d.platform, "kind": d.device_kind,
                           "count": len(self._devices)}}

    # ------------------------------------------------------------- window
    def _totals(self):
        """Every integer `engine_stats()` gives, under its own name, with
        the names the checks and older metric files read."""
        s = {k: v for k, v in self.engine_stats().items() if type(v) is int}
        return {**s, "compiles": self._compiles, "engine_tokens": s["total_tokens"],
                "engine_finished": s["total_finished"],
                "preemptions": s["total_preemptions"],
                "prefix_hits": s["prefix_cache_hits"]}

    def bench_window_start(self):
        self._c0 = self._totals()
        return time.time()

    def bench_window_end(self):
        now = self._totals()
        peak = max(((d.memory_stats() or {}).get("peak_bytes_in_use") or 0)
                   for d in self._devices)
        return {**{k: now[k] - self._c0[k] for k in now}, "memory_peak_bytes": peak}

    def bench_trace_start(self, trace_dir):
        import jax

        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 1          # idle gaps are named by host frames
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        return time.time()

    def bench_trace_stop(self, trace_dir):
        import jax

        from benchmarks import trace as trace_mod

        jax.profiler.stop_trace()
        reduced = trace_mod.reduce_trace(trace_mod.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        return reduced


# ------------------------------------------------------------------ client
class _Client:
    """Open loop: every request is sent at its due time from a thread of its
    own, whatever the server is doing, and timed from when it was DUE."""

    def __init__(self, handle, reqs, trace_ids: bool):
        self.handle, self.reqs, self.trace_ids = handle, reqs, trace_ids
        self.rec = [dict(due=r.due_s, asked=r.max_new_tokens, sent=None,
                         stamps=[], done=False, error=None) for r in reqs]
        self.closed = False
        self.t0 = None

    def _one(self, i):
        rec, req = self.rec[i], self.reqs[i]
        try:
            if self.trace_ids:
                from ray_tpu.util import tracing

                tracing.set_trace_id(tracing.new_trace_id())
            rec["sent"] = time.perf_counter() - self.t0
            gen = self.handle.options(stream=True).generate_stream.remote(
                req.prompt, req.max_new_tokens)
            for _tok in gen:
                rec["stamps"].append(time.perf_counter() - self.t0)
            rec["done"] = True
        except Exception as e:  # noqa: BLE001 — counted as failed unless closed
            if not self.closed:
                rec["error"] = repr(e)

    def run(self, seconds: float, drain_s: float = 0.0, first_token_grace_s: float = 0.0):
        self.t0 = time.perf_counter()
        threads = []
        for i, req in enumerate(self.reqs):
            wait = self.t0 + req.due_s - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            th = threading.Thread(target=self._one, args=(i,), daemon=True)
            th.start()
            threads.append(th)
        left = self.t0 + seconds - time.perf_counter()
        if left > 0:
            time.sleep(left)
        end = time.perf_counter() + drain_s
        for th in threads:
            th.join(max(0.0, end - time.perf_counter()))
        # a request due inside the window may get its first token just after it
        end = time.perf_counter() + first_token_grace_s
        while time.perf_counter() < end and any(
                r["sent"] is not None and not r["stamps"] and not r["error"]
                for r in self.rec):
            time.sleep(0.02)

    def series(self, seconds: float) -> dict:
        """What reached the clients inside the window."""
        ttft, itl, late, tokens = [], [], [], []
        for r in self.rec:
            if r["sent"] is None:
                continue
            late.append(1e3 * (r["sent"] - r["due"]))
            # due in the window and not answered when the grace ended: inf
            ttft.append(1e3 * (r["stamps"][0] - r["due"]) if r["stamps"] else math.inf)
            stamps = [s for s in r["stamps"] if s <= seconds]
            itl += [1e3 * (b - a) for a, b in zip(stamps, stamps[1:])]
            tokens += stamps
        return {"ttft_ms": ttft, "itl_ms": itl, "late_ms": late,
                "token_t_s": sorted(tokens)}

    def verdict(self):
        done = [r for r in self.rec if r["done"]]
        wrong = [r for r in done if len(r["stamps"]) != r["asked"]]
        over = [r for r in self.rec if len(r["stamps"]) > r["asked"]]
        errors = [r["error"] for r in self.rec if r["error"]]
        return {"completed": len(done), "failed": len(wrong) + len(over) + len(errors),
                "errors": errors[:3]}


def _spans(ray, t_from, t_to):
    """Every `engine.*` and `serve.*` span the program recorded that starts
    between the two instants, with its args, from the controller's timeline
    (which keeps its newest 10,000 events)."""
    from ray_tpu.util import flight

    flight.flush()                  # this process's `serve.handle` spans
    time.sleep(1.0)                 # the workers' span flush
    try:
        events = ray.timeline()
    except Exception:  # noqa: BLE001 — readers with nothing to read
        return []
    return [ev for ev in events
            if ev.get("event") == "span" and t_from <= ev.get("ts", 0) <= t_to
            and ev.get("name", "").startswith(("engine.", "serve."))]


def run(ctx: dict) -> dict:
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.deployment import Deployment
    from ray_tpu.serve.engine import EngineOptions

    cell, config, mix = ctx["cell"], ctx["config"], ctx["traffic"]
    rehearse, seconds = ctx["rehearse"], ctx["seconds"]
    part = copy.deepcopy(config["runners"]["requests"])
    if rehearse:
        pre = dict(config["rehearsal"]["requests"])
        part["engine_options"] = pre.pop("engine_options")
        part["token_check"] = pre.pop("token_check")
        mix = {**mix, **pre}
    arch = harness.arch(config["arch"])
    dims = arch.dims(config, rehearse)
    model, overrides = arch.program(config, dims)
    opts = dataclasses.asdict(EngineOptions(**part["engine_options"]))
    actor_options = dict(part["ray_actor_options"])
    if not rehearse:
        actor_options["num_tpus"] = cell["chips"]
    trace_dir = os.path.join(harness.OUT, "trace", cell["name"])

    t = time.perf_counter()
    serve.start()
    bench_llm = Deployment(BenchReplica, LLMDeployment.name,
                           copy.deepcopy(LLMDeployment.opts))
    handle = serve.run(
        bench_llm.options(ray_actor_options=actor_options,
                          replica_startup_timeout_s=900).bind(
            model=model, model_overrides=overrides,
            engine_options=part["engine_options"],
            seed=harness.key_seed(ctx["seed"]), t0_wall=ctx["t0_wall"]),
        name="bench", route_prefix="/bench", timeout_s=900)
    call = lambda m, *a, timeout_s=900: getattr(handle, m).remote(*a).result(timeout_s=timeout_s)
    deploy_s = time.perf_counter() - t
    info = call("bench_info")
    if not rehearse and info["device"]["platform"] != "tpu":
        raise RuntimeError(f"replica is on {info['device']}, not the TPU")
    check = part["token_check"]
    token_err, token_agree = call("bench_check_tokens", config["arch"], dims, ctx["seed"],
                                  check["prompt_len"], check["new_tokens"])
    rates = ctx.get("sweep") or [mix["arrivals"]["rate_rps"]]
    top = {**mix, "arrivals": {**mix["arrivals"], "rate_rps": max(rates)}}
    waves = traffic.warm_plan(top, seconds, opts["block_size"], opts["max_num_seqs"],
                              opts["prefill_chunk_tokens"])
    programs = call("bench_warm", waves, dims["vocab_size"])
    # the caller's own path (handle, router, streaming refs), once
    hello = _Client(handle, [traffic.Request(0.0, list(range(1, waves[0][0][0] + 1)), 2)],
                    trace_ids=False)
    hello.run(0.0, drain_s=60.0)
    if hello.verdict()["failed"] or not hello.rec[0]["done"]:
        raise RuntimeError(f"warm-up request through the handle: {hello.rec[0]}")
    phases = {**call("bench_info")["phases"], "deploy_s": deploy_s}

    sweep = []
    for rate in rates:
        m = {**mix, "arrivals": {**mix["arrivals"], "rate_rps": rate}}
        reqs = traffic.requests(m, ctx["seed"], seconds, dims["vocab_size"])
        client = _Client(handle, reqs, trace_ids=ctx["trace"])
        probes = ctx["trace"] or bool(ctx.get("sweep"))
        reduced, tracer = [None], None
        if ctx["trace"] and not rehearse and not ctx.get("sweep"):
            tr = mix["trace"]

            def traced():
                time.sleep(min(tr["after_s"], max(0.0, seconds - tr["seconds"] - 1)))
                call("bench_trace_start", trace_dir)
                time.sleep(tr["seconds"])
                reduced[0] = call("bench_trace_stop", trace_dir)

            tracer = threading.Thread(target=traced, daemon=True)
        w0 = call("bench_window_start")
        phases["setup_s"] = time.time() - ctx["t0_wall"]
        if tracer:
            tracer.start()
        client.run(seconds, drain_s=60.0 if ctx.get("sweep") else 0.0,
                   first_token_grace_s=mix.get("first_token_grace_s", 0.0))
        verdict = client.verdict()      # before the engine's count, so that
        counters = call("bench_window_end")   # it can only be the larger one
        client.closed = True
        if tracer:
            tracer.join(300)
        series = client.series(seconds)
        window = {"t0": w0, "seconds": seconds}
        # a second past the window: the spans of a request due at its end
        spans = _spans(ray_tpu, w0, w0 + seconds + 1.0) if probes else []
        if ctx.get("sweep"):
            seen = {"window": window, "spans": spans}
            step = {"span": "engine.step", "over": "count"}
            done_by = lambda f: sum(1 for r in client.rec if r["done"] and r["stamps"][-1] <= f * seconds)
            sent_by = lambda f: sum(1 for r in client.rec if r["due"] <= f * seconds)
            fin = [x for x in series["ttft_ms"] if math.isfinite(x)]
            sweep.append({
                "rate_rps": rate, "requests": len(reqs),
                "in_flight_at": {f: sent_by(f) - done_by(f) for f in (0.33, 0.67, 1.0)},
                "tokens_in_window": len(series["token_t_s"]),
                "tok_s": len(series["token_t_s"]) / seconds,
                "ttft_p50_ms": stats.percentile(fin, 50) if fin else None,
                "itl_p90_ms": stats.percentile(series["itl_ms"], 90) if series["itl_ms"] else None,
                "lanes_mean": readers.span_sum(
                    seen, {**step, "arg": "decodes", "where": "decodes"}),
                "engine_step_ms": readers.span_sum(seen, {**step, "arg": "dur", "scale": 1e3}),
                "queue_depth_max": readers.span_percentile(
                    seen, {**step, "arg": "queue_depth", "q": 100}),
                "failed": verdict["failed"], "phases": dict(phases),
            })
    client_tokens = len(series["token_t_s"])
    sent = sum(1 for r in client.rec if r["sent"] is not None)
    block_bytes = arch.kv_block_bytes(dims, opts["block_size"])
    checks = {
        "token_err": token_err, "token_argmax_agree": [token_agree, check["new_tokens"]],
        "tokens_match_reference": token_err <= part["token_tolerance"],
        "every_response_exact": verdict["failed"] == 0,
        "errors": verdict["errors"],
        "engine_tokens": counters["engine_tokens"], "client_tokens": client_tokens,
        "counters_agree": (counters["engine_finished"] >= verdict["completed"]
                           and counters["engine_tokens"] >= client_tokens),
        "no_compile_in_window": counters["compiles"] == 0,
        "warmed_programs": programs,
    }
    try:
        serve.shutdown()
    except Exception:  # noqa: BLE001 — requests cut at the window's close
        pass
    peak = counters.pop("memory_peak_bytes")
    return {
        "phases": phases, "series": series, "counters": counters,
        "spans": spans, "window": window,
        "facts": {"arch": config["arch"], "model": dims,
                  "kv_pool_bytes": opts["num_blocks"] * block_bytes,
                  "engine_options": opts},
        "trace": reduced[0], "checks": checks, "sweep": sweep,
        "attempted": sent, "failed": verdict["failed"],
        "device": {**info["device"], "memory_peak_bytes": peak},
    }
