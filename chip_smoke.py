"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives both hot paths once, through the entry points a user calls, at the
published widths of gpt2-large (36 L, d=1280, 20x64 heads, MLP 5120, vocab
50304) with seeded random weights:

  1. trainer — `JaxTrainer(...).fit()` in one TPU worker that owns every
     local chip: gpt2-large, S=1024, flash attention + remat_policy="attn",
     adamw with bf16 first moments (the `gpt2-large.train` cell's shape) on
     an fsdp mesh over `jax.devices()`, a few steps on one fixed batch;
  2. server — `serve.run(LLMDeployment...)` on one chip, requests of mixed
     prompt lengths over the HTTP proxy, some in flight together;
  3. reference — in a plain `num_tpus=1` task: each Pallas kernel against
     its XLA reference at the trainer's shape (gated), and the dense
     `prefill`/`decode_step` path for one of the server's prompts (reported,
     not gated: bf16 near-ties may flip a greedy token).

This process starts the runtime and stays off JAX's backends (asserted at
exit); each phase runs in a runtime-scheduled TPU worker, and the next one
starts only when no process holds a chip any more. Anything that fails, and
any device that is not a TPU, ends the run with a non-zero exit code and no
result line. On success stdout ends with two JSON lines: the full report
(versions, cache directory, per phase its checks, set-up and steady seconds;
also written to `chiprun_out/chip_smoke/report.json`), and last the verdict,
`{"ok": true, "device": {"platform", "kind", "count"}}` with exactly those
keys and the device as JAX reports it in the trainer's worker.

Run it through the chip tool: `chiprun -- python3 chip_smoke.py`.
"""

from __future__ import annotations

import glob
import json
import os
import signal
import sys
import threading
import time
import urllib.request
import uuid

MODEL = "gpt2-large"
SEQ = 1024
BATCH_PER_CHIP = 13  # the train cell's batch; 14 is the largest that compiles (PR 21)
TRAIN_STEPS = 8
# (prompt tokens, new tokens): one prefill chunk, two, and five (chunk = 64).
REQUESTS = ((5, 16), (70, 24), (300, 8))
ENGINE_OPTIONS = dict(num_blocks=512, block_size=16, max_num_seqs=8)
_RUN_TAG = "CHIP_SMOKE_RUN"


# ----------------------------------------------------------------- phase 1
def _train_loop(config):
    """Runs in the TPU worker (`train_loop_per_worker`)."""
    import re
    import time

    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu import train
    from ray_tpu.models.gpt import (
        CONFIGS, init_params, make_train_step, param_shardings,
    )
    from ray_tpu.ops.attention import flash_kernels_in
    from ray_tpu.train.jax_trainer import jax_utils

    t0 = time.perf_counter()
    devices = jax.devices()
    mesh = jax_utils.get_mesh(fsdp=-1)
    cfg = CONFIGS[config["model"]](
        max_seq=config["seq"], attn_impl="flash", remat=True,
        remat_policy="attn", **config["model_overrides"],
    )
    shardings = param_shardings(cfg, mesh)
    params = jax.jit(
        lambda key: init_params(key, cfg), out_shardings=shardings
    )(jax.random.PRNGKey(0))
    opt = optax.adamw(3e-4, weight_decay=0.1, mu_dtype=jnp.bfloat16)
    state = (params, opt.init(params))  # moments inherit the param shardings
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (config["batch"], config["seq"] + 1), 0,
        cfg.vocab_size,
    )
    batch = {
        "tokens": jax.device_put(
            tokens, NamedSharding(mesh, P(("dp", "fsdp"), None))
        )
    }
    step = (
        jax.jit(make_train_step(cfg, opt, mesh=mesh), donate_argnums=(0,))
        .lower(state, batch)
        .compile()
    )
    hlo = step.as_text()
    mem = step.memory_analysis()
    # q as one device's forward kernel sees it: [batch*heads / shards, S, Dh].
    fwd_call = next(
        (line for line in hlo.splitlines()
         if "tpu_custom_call" in line and "flash_fwd" in line),
        "",
    )
    q_shard = re.search(r"operand_layout_constraints=\{(\w+\[[\d,]*\])", fwd_call)
    w = params["w_qkv"]
    info = {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "local_device_count": jax.local_device_count(),
        "device_count": len(devices),
        "jax": jax.__version__,
        "jaxlib": __import__("jaxlib").__version__,
        "libtpu": __import__("libtpu").__version__,
        "compile_cache": jax.config.jax_compilation_cache_dir,
        "flash_kernels": flash_kernels_in(hlo),
        "mosaic_calls": hlo.count("tpu_custom_call"),
        "flash_q_per_device": q_shard and q_shard.group(1),
        "param_shard_fraction": w.addressable_shards[0].data.size / w.size,
        "compiled_bytes_per_device": {
            "arguments": mem.argument_size_in_bytes,
            "temp": mem.temp_size_in_bytes,
        },
    }
    for i in range(config["steps"]):
        t_step = time.perf_counter()
        state, metrics = step(state, batch)
        loss = float(metrics["loss"])  # the host needs it: fences the step
        now = time.perf_counter()
        report = {"step": i, "loss": loss, "step_s": now - t_step}
        if i == 0:
            report.update(info, setup_s=now - t0)
        if i == config["steps"] - 1:
            report["peak_hbm_bytes"] = [
                (d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices
            ]
        train.report(report)


def trainer_phase(chips: int, batch: int, model_overrides=None, seq: int = SEQ,
                  steps: int = TRAIN_STEPS) -> dict:
    """One `JaxTrainer.fit()` in a worker granted `chips` TPU chips (0: a CPU
    worker, for debugging the smoke itself). Raises unless every step
    reported a finite loss and the last is below the first."""
    import math

    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    t0 = time.perf_counter()
    result = JaxTrainer(
        _train_loop,
        train_loop_config=dict(
            model=MODEL, model_overrides=model_overrides or {}, seq=seq,
            batch=batch, steps=steps,
        ),
        scaling_config=ScalingConfig(
            num_workers=1,
            resources_per_worker={"TPU": chips} if chips else {},
        ),
        run_config=RunConfig(name="chip_smoke", storage_path=_out_dir()),
    ).fit()
    if result.error is not None:
        raise RuntimeError(f"trainer phase failed: {result.error}")
    reports = result.metrics_history
    losses = [r["loss"] for r in reports]
    if len(losses) != steps or not all(math.isfinite(x) for x in losses):
        raise RuntimeError(f"want {steps} finite losses, got {losses}")
    if not losses[-1] < losses[0]:
        raise RuntimeError(f"loss did not fall: {losses}")
    out = {k: v for k, v in reports[0].items() if k not in ("step", "loss", "step_s")}
    out.update(
        batch=batch, seq=seq, steps=steps,
        losses=[round(x, 4) for x in losses],
        steady_s=round(sum(r["step_s"] for r in reports[1:]), 3),
        setup_s=round(out["setup_s"], 1),
        wall_s=round(time.perf_counter() - t0, 1),
        peak_hbm_bytes=reports[-1]["peak_hbm_bytes"],
    )
    return out


# ----------------------------------------------------------------- phase 2
def _post(url: str, body: dict) -> dict:
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=900) as resp:
        return json.loads(resp.read())


def _wave(url: str, bodies: list) -> list:
    """POST all bodies at once (one thread each) so they are in flight
    together; returns the responses in order, raising the first error."""
    results = [None] * len(bodies)

    def fire(i):
        try:
            results[i] = _post(url, bodies[i])
        except Exception as e:  # noqa: BLE001 — re-raised below, on the caller
            results[i] = e

    threads = [threading.Thread(target=fire, args=(i,)) for i in range(len(bodies))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for r in results:
        if isinstance(r, Exception):
            raise r
    return results


def _prompts(vocab: int, requests) -> list:
    import numpy as np

    rng = np.random.default_rng(0)
    return [
        {"prompt": rng.integers(1, vocab, n).tolist(), "max_new_tokens": new}
        for n, new in requests
    ]


def server_phase(chips: int, model_overrides=None, requests=REQUESTS,
                 engine_options=None) -> dict:
    """`serve.run(LLMDeployment)` on one chip (0: CPU, for debugging) and two
    waves of the same mixed-length requests over the HTTP proxy, bracketed by
    the shortest prompt sent alone. Raises unless every response carries
    exactly the tokens asked for and the two solo runs agree token for
    token; the repeated wave's agreement is reported (its prompts hit the
    prefix cache and share decode steps, so bf16 near-ties may differ)."""
    from ray_tpu import serve
    from ray_tpu.models.gpt import CONFIGS

    overrides = dict(model_overrides or {})
    vocab = CONFIGS[MODEL](**overrides).vocab_size
    bodies = _prompts(vocab, requests)
    actor_options = {"max_concurrency": 16}
    if chips:
        actor_options["num_tpus"] = 1

    t0 = time.perf_counter()
    serve.start(http_options={"host": "127.0.0.1", "port": 0})
    serve.run(
        serve.LLMDeployment.options(
            ray_actor_options=actor_options, replica_startup_timeout_s=900,
        ).bind(
            model=MODEL, model_overrides=overrides,
            engine_options=dict(engine_options or ENGINE_OPTIONS),
        ),
        name="smoke", route_prefix="/smoke", timeout_s=900,
    )
    url = f"http://127.0.0.1:{serve.http_port()}/smoke"
    t1 = time.perf_counter()
    solo_first = _post(url, bodies[0])
    wave1 = _wave(url, bodies)
    t2 = time.perf_counter()
    wave2 = _wave(url, bodies)
    solo_last = _post(url, bodies[0])
    t3 = time.perf_counter()
    stats = serve.get_app_handle("smoke").engine_stats.remote().result(timeout_s=60)
    # The runtime is at its widest here (controller, worker template, Serve
    # controller, proxy, replica): only the replica may be on the chip.
    holders = chip_holders()
    serve.shutdown()
    if chips and len(holders) != 1:
        raise RuntimeError(f"want the replica alone on the chip, found {holders}")

    responses = [solo_first, *wave1, *wave2, solo_last]
    asked = [bodies[0], *bodies, *bodies, bodies[0]]
    for body, resp in zip(asked, responses):
        if len(resp["tokens"]) != body["max_new_tokens"]:
            raise RuntimeError(
                f"asked for {body['max_new_tokens']} tokens after a "
                f"{len(body['prompt'])}-token prompt, got {resp}"
            )
    if solo_first["tokens"] != solo_last["tokens"]:
        raise RuntimeError(
            f"same prompt, different tokens: {solo_first['tokens']} vs "
            f"{solo_last['tokens']}"
        )
    want_tokens = sum(b["max_new_tokens"] for b in asked)
    if (stats["total_finished"], stats["total_tokens"]) != (len(asked), want_tokens):
        raise RuntimeError(f"engine_stats disagree with the requests: {stats}")
    return {
        "platform": stats["platform"],
        "device_kind": stats["device_kind"],
        "requests": len(asked),
        "prompt_lens": [len(b["prompt"]) for b in bodies],
        "new_tokens": [b["max_new_tokens"] for b in bodies],
        "solo_repeat_identical": True,
        "wave_repeat_agreement": [
            _agree(a["tokens"], b["tokens"]) for a, b in zip(wave1, wave2)
        ],
        "engine_stats": {
            k: stats[k] for k in (
                "total_finished", "total_tokens", "total_preemptions",
                "prefix_cache_hits", "prefix_cache_misses",
            )
        },
        "deploy_s": round(t1 - t0, 1),
        "setup_s": round(t2 - t0, 1),   # deploy + first pass (compiles)
        "steady_s": round(t3 - t2, 2),  # second pass
        "wave1_tokens": [r["tokens"] for r in wave1],
    }


def _agree(a: list, b: list) -> str:
    """'n/m': length of the common prefix over the length asked for."""
    n = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y), min(len(a), len(b)))
    return f"{n}/{len(a)}"


# ----------------------------------------------------------------- phase 3
def _kernel_errors(cfg, seq: int) -> dict:
    """Max abs error (over the reference's largest magnitude, if above 1) of
    each Pallas kernel against its XLA reference at the trainer's shape
    (heads, S, Dh, bf16; batch 2). Forward with and without
    lse, both backward kernels, and the RMSNorm kernel (which no gpt2 step
    selects) on full and ragged row blocks."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import attention as A
    from ray_tpu.ops import norms

    def err(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.max(jnp.abs(a - b)) / jnp.maximum(1.0, jnp.max(jnp.abs(b))))

    H, D = cfg.n_heads, cfg.d_head
    scale = D ** -0.5
    q, k, v, g = (
        jax.random.normal(key, (2, H, seq, D), jnp.bfloat16)
        for key in jax.random.split(jax.random.PRNGKey(2), 4)
    )
    ref = A.attention_reference(q, k, v, True, scale)
    logits = jnp.einsum("bhsd,bhtd->bhst", q, k, preferred_element_type=jnp.float32)
    causal = jnp.arange(seq)[:, None] >= jnp.arange(seq)[None, :]
    ref_lse = jax.nn.logsumexp(jnp.where(causal, logits * scale, -1e30), -1)
    out = jax.jit(
        lambda q, k, v: A._flash_fwd_pallas(q, k, v, True, scale, 1024, 1024)
    )(q, k, v)
    out2, lse = jax.jit(
        lambda q, k, v: A._flash_fwd_pallas(
            q, k, v, True, scale, 1024, 1024, return_lse=True)
    )(q, k, v)

    def grads(attn):
        def loss(q, k, v):
            return jnp.sum(attn(q, k, v).astype(jnp.float32) * g.astype(jnp.float32))

        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)

    got = grads(lambda q, k, v: A.flash_attention(q, k, v, True, scale, 1024, 1024))
    want = grads(lambda q, k, v: A.attention_reference(q, k, v, True, scale))
    errors = {
        "flash_fwd": err(out, ref),
        "flash_fwd_lse": max(
            err(out2, ref), err(lse[:, 0, :seq], ref_lse.reshape(2 * H, seq))
        ),
        "flash_bwd_dq": err(got[0], want[0]),
        "flash_bwd_dkv": max(err(got[1], want[1]), err(got[2], want[2])),
    }
    w = jax.random.normal(jax.random.PRNGKey(3), (cfg.d_model,), jnp.bfloat16)
    for rows in (2 * seq, 300):  # 300: ragged last row block
        x = jax.random.normal(jax.random.PRNGKey(4), (rows, cfg.d_model), jnp.bfloat16)
        errors[f"rmsnorm_{rows}"] = err(
            jax.jit(lambda x, w: norms._rmsnorm_pallas(x, w, 1e-6))(x, w),
            norms._rmsnorm_ref(x, w, 1e-6),
        )
    return errors


def _reference_task(model_overrides: dict, seq: int, prompt: list,
                    max_new_tokens: int, seed: int) -> dict:
    """Runs in a `num_tpus=1` task: kernel numerics, then greedy tokens from
    the dense `prefill`/`decode_step` path with the engine's weights (same
    seed)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.gpt import CONFIGS, init_params, make_generate

    overrides = dict(model_overrides)
    if isinstance(overrides.get("dtype"), str):
        overrides["dtype"] = getattr(jnp, overrides["dtype"])
    cfg = CONFIGS[MODEL](**overrides, remat=False)
    platform = jax.devices()[0].platform
    kernel_errors = _kernel_errors(cfg, seq) if platform == "tpu" else None
    params = init_params(jax.random.PRNGKey(seed), cfg)
    gen = jax.jit(make_generate(cfg, max_new_tokens))
    tokens = gen(params, jnp.asarray([prompt], jnp.int32), jax.random.PRNGKey(0))
    return {
        "tokens": [int(t) for t in tokens[0]],
        "platform": platform,
        "kernel_max_err": kernel_errors,
    }


# bf16 against bf16: the kernels measured 0.016 on the chip (PR 21).
KERNEL_TOLERANCE = 0.05


def reference_phase(chips: int, engine_tokens: list, model_overrides=None,
                    requests=REQUESTS, seq: int = SEQ) -> dict:
    """The repo's references, on the chip: every Pallas kernel against its
    XLA reference (gated), and `engine_tokens` — what the server answered to
    the second request — against the dense path (reported)."""
    import ray_tpu
    from ray_tpu.models.gpt import CONFIGS

    overrides = dict(model_overrides or {})
    body = _prompts(CONFIGS[MODEL](**overrides).vocab_size, requests)[1]
    t0 = time.perf_counter()
    task = ray_tpu.remote(_reference_task).options(num_tpus=1 if chips else None)
    got = ray_tpu.get(
        task.remote(overrides, seq, body["prompt"], body["max_new_tokens"], 0),
        timeout=900,
    )
    errors = got["kernel_max_err"]
    if chips and not all(e < KERNEL_TOLERANCE for e in errors.values()):
        raise RuntimeError(f"kernel disagrees with its reference: {errors}")
    return {
        "platform": got["platform"],
        "kernel_max_err": errors,
        "prompt_len": len(body["prompt"]),
        "greedy_agreement": _agree(engine_tokens, got["tokens"]),
        "wall_s": round(time.perf_counter() - t0, 1),
    }


# -------------------------------------------------------------- the parent
def _out_dir() -> str:
    path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "chiprun_out", "chip_smoke"
    )
    os.makedirs(path, exist_ok=True)
    return path


def chip_holders() -> dict:
    """{pid: [device nodes]} for every process holding a TPU device node."""
    out = {}
    for fd in glob.glob("/proc/[0-9]*/fd/*"):
        try:
            target = os.readlink(fd)
        except OSError:
            continue
        if target.startswith("/dev/accel") or (
            target.startswith("/dev/vfio/") and target[10:].isdigit()
        ):
            out.setdefault(int(fd.split("/")[2]), []).append(target)
    return out


def wait_chip_free(timeout_s: float = 120.0) -> float:
    """Block until no process holds a chip; returns the seconds it took."""
    t0 = time.monotonic()
    while True:
        holders = chip_holders()
        if not holders:
            return time.monotonic() - t0
        if time.monotonic() - t0 > timeout_s:
            raise RuntimeError(f"chip still held after {timeout_s}s: {holders}")
        time.sleep(0.2)


def _started_processes(tag: str) -> list:
    """Live pids (other than this one) that inherited this run's tag."""
    needle = f"{_RUN_TAG}={tag}".encode()
    pids = []
    for path in glob.glob("/proc/[0-9]*/environ"):
        pid = int(path.split("/")[2])
        if pid == os.getpid():
            continue
        try:
            with open(path, "rb") as f:
                if needle in f.read().split(b"\0"):
                    pids.append(pid)
        except OSError:
            continue
    return pids


def _stop_started_processes(tag: str, grace_s: float = 20.0) -> int:
    """Wait for the runtime's processes to exit; kill what remains. Returns
    how many had to be killed."""
    deadline = time.monotonic() + grace_s
    while _started_processes(tag) and time.monotonic() < deadline:
        time.sleep(0.2)
    left = _started_processes(tag)
    for pid in left:
        try:
            os.kill(pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
    return len(left)


def _dump_logs():
    """On failure: the tail of every log of this run's runtime session."""
    for path in sorted(glob.glob(f"/tmp/ray_tpu/session_*_{os.getpid()}/*.log")):
        with open(path, "rb") as f:
            f.seek(max(0, os.path.getsize(path) - 6000))
            tail = f.read().decode(errors="replace")
        if tail.strip():
            print(f"----- {path}\n{tail}", file=sys.stderr)


def main() -> int:
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.lower().split(","):
        print(
            f"chip_smoke: JAX_PLATFORMS={platforms!r} keeps JAX off the TPU "
            "chip, and this check is only worth anything on the chip",
            file=sys.stderr,
        )
        return 1

    import ray_tpu
    from ray_tpu.util.accelerators import tpu as tpu_util

    chips = tpu_util.detect_num_chips()
    if chips == 0:
        print(
            "chip_smoke: no TPU chip on this machine (no /dev/accel* or "
            "/dev/vfio/<n> device node)",
            file=sys.stderr,
        )
        return 1
    holders = chip_holders()
    if holders:
        print(f"chip_smoke: the chip is already held: {holders}", file=sys.stderr)
        return 1

    tag = uuid.uuid4().hex
    os.environ[_RUN_TAG] = tag
    os.environ["RAY_TPU_LOG_TO_DRIVER"] = "0"  # stdout carries the result
    cache_dir = tpu_util.place_compile_cache()
    t0 = time.perf_counter()
    summary = {}
    try:
        ray_tpu.init()
        advertised = ray_tpu.cluster_resources().get("TPU", 0)
        if advertised != chips:
            raise RuntimeError(
                f"runtime advertises TPU={advertised}, machine exposes {chips}"
            )
        holders = chip_holders()
        if holders:
            raise RuntimeError(f"a runtime process attached to the chip: {holders}")
        summary["trainer"] = trainer_phase(chips, BATCH_PER_CHIP * chips)
        summary["trainer"]["release_s"] = round(wait_chip_free(), 2)
        summary["server"] = server_phase(chips)
        summary["server"]["release_s"] = round(wait_chip_free(), 2)
        summary["reference"] = reference_phase(
            chips, summary["server"].pop("wave1_tokens")[1]
        )
    except BaseException:
        _dump_logs()
        raise
    finally:
        ray_tpu.shutdown()
        killed = _stop_started_processes(tag)

    trainer = summary["trainer"]
    problems = []
    for phase, got in summary.items():
        if got["platform"] != "tpu":
            problems.append(f"{phase} ran on {got['platform']!r}, not the TPU")
    if trainer["local_device_count"] != chips:
        problems.append(
            f"trainer worker saw {trainer['local_device_count']} devices, "
            f"granted {chips}"
        )
    if not all(trainer["flash_kernels"].values()):
        problems.append(
            f"compiled train step lacks Mosaic flash kernels: "
            f"{trainer['flash_kernels']}"
        )
    if trainer["compile_cache"] != cache_dir:
        problems.append(
            f"worker cached compiles in {trainer['compile_cache']!r}, "
            f"expected {cache_dir!r}"
        )
    if "jax" in sys.modules:
        from jax._src import xla_bridge

        if xla_bridge.backends_are_initialized():
            problems.append("the parent process initialized a JAX backend")
    holders = chip_holders()
    if holders:
        problems.append(f"chip still held at exit: {holders}")
    if problems:
        print("chip_smoke FAILED:\n  " + "\n  ".join(problems), file=sys.stderr)
        print(json.dumps(summary), file=sys.stderr)
        return 1

    # The device as the trainer's worker read it off `jax.devices()`.
    verdict = {
        "ok": True,
        "device": {
            "platform": trainer["platform"],
            "kind": trainer["device_kind"],
            "count": trainer["device_count"],
        },
    }
    report = json.dumps({
        **verdict,
        "versions": {k: trainer.pop(k) for k in ("jax", "jaxlib", "libtpu")},
        "compile_cache": cache_dir,
        "wall_s": round(time.perf_counter() - t0, 1),
        "processes_killed_at_exit": killed,
        **summary,
    })
    with open(os.path.join(_out_dir(), "report.json"), "w") as f:
        f.write(report + "\n")
    print(report)
    print(json.dumps(verdict), flush=True)  # the last line: exactly these keys
    return 0


if __name__ == "__main__":
    sys.exit(main())
