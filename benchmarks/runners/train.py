"""Runner kind `train`: one `JaxTrainer.fit()` whose single worker holds the
cell's chips. Everything that touches JAX is in `_train_loop`, which runs in
that worker."""

from __future__ import annotations

import os
import time

from .. import harness


def _train_loop(config):
    import math
    import shutil

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu import train
    from ray_tpu.data.streaming.ingest import StreamingIngest
    from ray_tpu.models.gpt import (
        CONFIGS, init_params, make_train_step, param_shardings,
    )
    from ray_tpu.train.jax_trainer import jax_utils

    from benchmarks import trace as trace_mod
    from benchmarks.traffic import TokenBatches

    wall0 = config["t0_wall"]
    phases = {}
    devices = jax.devices()                      # attaches to the chips
    phases["attach_s"] = time.time() - wall0
    rehearse = config["rehearse"]
    if not rehearse and devices[0].platform != "tpu":
        raise RuntimeError(f"train worker is on {devices[0].platform!r}, not the TPU")
    if config["chips"] and len(devices) != config["chips"]:
        raise RuntimeError(f"granted {config['chips']} chips, JAX sees {len(devices)}")

    arch = harness.arch(config["arch"])
    m, mix, part = config["dims"], config["traffic"], config["part"]
    seq, batch = mix["seq"], mix["batch_per_chip"] * len(devices)
    mesh = jax_utils.get_mesh(**part["mesh"])
    model, overrides = config["program"]
    cfg = CONFIGS[model](
        **{**overrides, "max_seq": seq}, attn_impl=part["attn_impl"], remat=True,
        remat_policy=part["remat_policy"],
    )
    shardings = param_shardings(cfg, mesh)
    t = time.perf_counter()
    params = jax.jit(lambda key: init_params(key, cfg), out_shardings=shardings)(
        jax.random.PRNGKey(harness.key_seed(config["seed"])))
    jax.block_until_ready(params)
    phases["weights_s"] = time.perf_counter() - t

    ingest = StreamingIngest(
        TokenBatches(config["seed"], batch, seq, cfg.vocab_size, mix["epoch_batches"]),
        batch_size=batch, epochs=None)
    rows = NamedSharding(mesh, P(("dp", "fsdp"), None))
    put = lambda b: {"tokens": jax.device_put(b["tokens"], rows)}
    first = ingest.next_batch()

    # The plain reference on the whole first batch, one sequence a device.
    t = time.perf_counter()
    ref = jax.jit(jax.vmap(arch.make_loss(m), in_axes=(None, 0)))
    n_dev = len(devices)
    ref_sum = 0.0
    for i in range(0, batch, n_dev):
        chunk = first["tokens"][i:i + n_dev]
        pad = n_dev - len(chunk)
        if pad:
            chunk = np.concatenate([chunk, chunk[:pad]])
        got = np.asarray(ref(params, jax.device_put(chunk, rows)))
        ref_sum += float(got[: n_dev - pad].sum())
    ref_loss = ref_sum / (batch * seq)
    del ref
    phases["reference_s"] = time.perf_counter() - t

    o = part["optimizer"]
    opt = optax.adamw(o["lr"], weight_decay=o["weight_decay"],
                      mu_dtype=getattr(jnp, o["mu_dtype"]))
    state = (params, opt.init(params))
    t = time.perf_counter()
    step = (
        jax.jit(make_train_step(cfg, opt, mesh=mesh), donate_argnums=(0,))
        .lower(state, put(first)).compile()
    )
    phases["compile_s"] = time.perf_counter() - t
    hlo = step.as_text()
    mosaic = {
        k: sum(1 for ln in hlo.splitlines() if "tpu_custom_call" in ln and k in ln)
        for k in arch.kernel_costs(m, batch, seq, len(devices))
    }
    mem = step.memory_analysis()
    del hlo

    t = time.perf_counter()
    warm_losses = []
    for i in range(mix["warmup_steps"]):
        b = first if i == 0 else ingest.next_batch()
        state, metrics = step(state, put(b))
        warm_losses.append(float(metrics["loss"]))
    phases["warm_s"] = time.perf_counter() - t

    # ------------------------------------------------------ measured window
    seconds, do_trace = config["seconds"], config["trace"]
    tr = mix["trace"]
    trace_dir = config["trace_dir"]
    annotate = jax.profiler.TraceAnnotation
    step_ends, report_s, losses, reported = [], [], [], []
    traced = None
    starve0 = ingest.starve_s
    phases["setup_s"] = time.time() - wall0
    t_start = time.perf_counter()
    i = 0
    while True:
        if do_trace and i == tr["from_step"]:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            traced = "on"
        with annotate("bench.next_batch"):
            b = put(ingest.next_batch())
        with annotate("bench.dispatch"):
            state, metrics = step(state, b)
        with annotate("bench.loss_read"):
            loss = float(metrics["loss"])        # the fence: the host needs it
        end = time.perf_counter()
        step_ends.append(end - t_start)
        losses.append(loss)
        reported.append(metrics)                 # read after the window
        with annotate("bench.report"):
            train.report({"step": i, "loss": loss})
        report_s.append(time.perf_counter() - end)
        i += 1
        if traced == "on" and i == tr["from_step"] + tr["steps"]:
            jax.profiler.stop_trace()
            traced = "done"
        if end - t_start >= seconds and traced != "on":
            break
    window_s = time.perf_counter() - t_start
    starve_s = ingest.starve_s - starve0
    ingest.shutdown()

    reduced = None
    if traced == "done" and not rehearse:
        reduced = trace_mod.reduce_trace(trace_mod.find_xplane(trace_dir))
    shutil.rmtree(trace_dir, ignore_errors=True)

    g = mix["group_steps"]
    n_groups = len(losses) // g
    group_loss = [sum(losses[k * g:(k + 1) * g]) / g for k in range(n_groups)]
    checks = {
        "first_loss": warm_losses[0], "reference_loss": ref_loss,
        "loss_matches_reference": abs(warm_losses[0] - ref_loss) <= part["loss_tolerance"],
        "losses_finite": all(math.isfinite(x) for x in warm_losses + losses),
        "loss_falls": n_groups >= 2 and group_loss[-1] < group_loss[0],
        "mosaic_calls": mosaic,
        "three_flash_kernels": rehearse or all(v >= 1 for v in mosaic.values()),
    }
    peak = max(((d.memory_stats() or {}).get("peak_bytes_in_use") or 0) for d in devices)
    train.report({"final": True, "obs": {
        "phases": phases,
        "series": {**{k: [float(r[k]) for r in reported] for k in reported[0]},
                   "step_end_s": step_ends, "report_s": report_s,
                   "step_s": [b - a for a, b in zip([0.0] + step_ends, step_ends)]},
        "counters": {"starve_s": starve_s, "window_s": window_s,
                     "steps": len(step_ends)},
        "facts": {
            "tokens_per_step": batch * seq, "seq": seq, "chips": len(devices),
            "group_steps": g, "arch": config["arch"], "model": m, "batch": batch,
            "compiled_bytes": {"arguments": mem.argument_size_in_bytes,
                               "temp": mem.temp_size_in_bytes} if mem else None,
        },
        "trace": reduced,
        "checks": checks,
        "attempted": len(step_ends),
        "failed": 0,
        "device": {"platform": devices[0].platform, "kind": devices[0].device_kind,
                   "count": len(devices), "memory_peak_bytes": peak},
    }})


def run(ctx: dict) -> dict:
    """Parent side: fit, and hand back the worker's observations."""
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    cell, config, mix = ctx["cell"], ctx["config"], ctx["traffic"]
    chips = 0 if ctx["rehearse"] else cell["chips"]
    part = dict(config["runners"]["train"])
    if ctx["rehearse"]:
        mix = {**mix, **config["rehearsal"]["train"]}
    name = cell["name"]
    arch = harness.arch(config["arch"])
    dims = arch.dims(config, ctx["rehearse"])
    result = JaxTrainer(
        _train_loop,
        train_loop_config=dict(
            t0_wall=ctx["t0_wall"], seed=ctx["seed"], seconds=ctx["seconds"],
            trace=ctx["trace"], rehearse=ctx["rehearse"], chips=chips,
            arch=config["arch"], dims=dims, program=arch.program(config, dims),
            traffic=mix, part=part,
            trace_dir=os.path.join(harness.OUT, "trace", name),
        ),
        scaling_config=ScalingConfig(
            num_workers=1, resources_per_worker={"TPU": chips} if chips else {}),
        run_config=RunConfig(name=name, storage_path=os.path.join(harness.OUT, "train")),
    ).fit()
    if result.error is not None:
        raise RuntimeError(f"trainer failed: {result.error}")
    final = result.metrics_history[-1]
    if not final.get("final"):
        raise RuntimeError(f"trainer ended without its final report: {final}")
    return final["obs"]
