"""Multi-host SPMD gang validation — one mesh spanning processes.

This is the executable proof of the framework's core promise: N host
processes, each owning a subset of devices, joined by
`jax.distributed.initialize` into ONE global mesh, running ONE compiled
train step whose collectives cross the process boundary.

Reference analog: the torch process-group path this replaces is e2e-tested
in the reference (`python/ray/train/torch/config.py:106,148` via
`python/ray/train/_internal/backend_executor.py:124`); here the gang is a
union `jax.sharding.Mesh` instead of a NCCL communicator.

`run_gang_step()` is deliberately process-count agnostic: the SAME function
runs single-process (8 local devices) or multi-process (2×4), and must
produce the same loss — that equivalence is what the tests assert.

Run as a module to join a gang from a fresh interpreter:

    python -m ray_tpu.train.gang_check <process_id> <num_processes> \
        <coordinator host:port> <devices_per_process>
"""

from __future__ import annotations

from typing import Dict


def run_gang_step() -> Dict[str, float]:
    """Build a dp×fsdp mesh over ALL global devices (local + remote), run a
    shard_map psum and one GPT train step, return scalars for comparison.

    Must be called after `jax.distributed.initialize` when spanning
    processes (`jax_utils.maybe_init_distributed`), or directly in a
    single-process run.
    """
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.models import GPTConfig, init_params, make_train_step, param_shardings
    from ray_tpu.parallel import MeshSpec, shard_fn

    n = jax.device_count()
    if n % 2:
        raise ValueError(f"gang check needs an even device count, got {n}")
    mesh = MeshSpec(dp=2, fsdp=n // 2).build(jax.devices())
    data_axes = ("dp", "fsdp")

    # 1) shard_map allreduce across the union mesh: device i holds value i,
    # the psum must see every process's shard (28.0 for n=8).
    per_dev = jax.jit(
        lambda: jnp.arange(float(n)),
        out_shardings=NamedSharding(mesh, P(data_axes)),
    )()
    total = jax.jit(
        shard_fn(
            lambda x: jax.lax.psum(jnp.sum(x), data_axes),
            mesh,
            in_specs=P(data_axes),
            out_specs=P(),
        )
    )(per_dev)
    psum = float(total)

    # 2) one GPT train step sharded dp×fsdp. Params/opt/batch are all
    # materialized INSIDE jit with explicit out_shardings — the standard
    # multi-host idiom (each process computes only its addressable shards).
    cfg = GPTConfig(
        vocab_size=512,
        n_layers=2,
        d_model=128,
        n_heads=4,
        d_head=32,
        d_mlp=256,
        max_seq=128,
        pos="rotary",
        rotary_dim=32,
        attn_impl="ref",
        remat=True,
    )
    shardings = param_shardings(cfg, mesh)
    params = jax.jit(
        lambda k: init_params(k, cfg), out_shardings=shardings
    )(jax.random.PRNGKey(0))
    opt = optax.adamw(1e-3)
    opt_state = jax.jit(opt.init)(params)

    B = 2 * n
    tokens = jax.jit(
        lambda k: jax.random.randint(k, (B, cfg.max_seq + 1), 0, cfg.vocab_size),
        out_shardings=NamedSharding(mesh, P(data_axes, None)),
    )(jax.random.PRNGKey(1))

    step = jax.jit(make_train_step(cfg, opt), donate_argnums=(0,))
    state, metrics = step((params, opt_state), {"tokens": tokens})
    # Loss and grad_norm are fully replicated → every process can read them.
    loss = float(metrics["loss"])
    grad_norm = float(metrics["grad_norm"])
    assert loss == loss and loss > 0, f"bad gang loss {loss}"
    assert grad_norm > 0, "gang gradients are zero"
    return {
        "loss": loss,
        "grad_norm": grad_norm,
        "psum": psum,
        "n_global": float(n),
        "n_local": float(jax.local_device_count()),
    }


def spawn_gang(
    nprocs: int = 2,
    devices_per_proc: int = 4,
    timeout: float = 420.0,
    _bind_attempts: int = 3,
):
    """Spawn `nprocs` fresh interpreters that join one jax.distributed gang
    and each run `run_gang_step`; returns the parsed per-process results.

    Shared by `tests/test_multihost_gang.py` and
    `__graft_entry__._dryrun_multiprocess_gang` so the CLI protocol lives in
    one place. Stdout goes to temp files (not pipes) so a chatty worker can
    never wedge the gang on a full pipe, and every worker is killed on any
    failure path — a surviving sibling would otherwise sit in a collective
    waiting for its dead peer.

    Coordinator-port TOCTOU (ADVICE r5 #5): the port is picked bind-then-
    close, and another process can take it before worker 0's
    jax.distributed coordinator binds it. The socket is held open with
    SO_REUSEADDR until just before the workers launch (shrinks the window
    to microseconds), and a rendezvous failure that looks like a lost
    bind race retries the whole gang on a fresh port.
    """
    import json
    import os
    import socket
    import subprocess
    import sys
    import tempfile
    import time

    s = socket.socket()
    s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    s.bind(("127.0.0.1", 0))
    coord = f"127.0.0.1:{s.getsockname()[1]}"
    repo = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

    procs = []
    logs = []
    try:
        # Hold the reservation until the last instant: the coordinator
        # child binds with SO_REUSEADDR-compatible semantics only after
        # this close, so the race window is the exec latency, not the
        # whole test-collection interval.
        s.close()
        for pid in range(nprocs):
            log = tempfile.TemporaryFile(mode="w+")
            logs.append(log)
            procs.append(
                subprocess.Popen(
                    [sys.executable, "-m", "ray_tpu.train.gang_check",
                     str(pid), str(nprocs), coord, str(devices_per_proc)],
                    stdout=log, stderr=subprocess.STDOUT, cwd=repo,
                )
            )
        deadline = time.monotonic() + timeout
        for p in procs:
            left = deadline - time.monotonic()
            p.wait(timeout=max(left, 1.0))
        outs = []
        for pid, (p, log) in enumerate(zip(procs, logs)):
            log.seek(0)
            out = log.read()
            if p.returncode != 0:
                lowered = out.lower()
                if _bind_attempts > 1 and (
                    "address already in use" in lowered
                    or "errno 98" in lowered
                    or "failed to bind" in lowered
                    or "bind address" in lowered
                ):
                    # Lost the coordinator-port race: kill the gang (the
                    # finally-block below) and retry on a fresh port.
                    for q in procs:
                        if q.poll() is None:
                            q.kill()
                    return spawn_gang(
                        nprocs, devices_per_proc, timeout,
                        _bind_attempts=_bind_attempts - 1,
                    )
                raise RuntimeError(f"gang worker {pid} failed:\n{out[-4000:]}")
            lines = [l for l in out.splitlines() if l.startswith("GANG_RESULT ")]
            if not lines:
                raise RuntimeError(f"no GANG_RESULT from worker {pid}:\n{out[-4000:]}")
            outs.append(json.loads(lines[-1][len("GANG_RESULT "):]))
        return outs
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for p in procs:
            if p.poll() is None:
                p.wait(timeout=10)
        for log in logs:
            log.close()


def _main() -> None:
    import json
    import os
    import sys

    pid, nprocs, coord, local = (
        int(sys.argv[1]),
        int(sys.argv[2]),
        sys.argv[3],
        int(sys.argv[4]),
    )
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={local}"
    os.environ["RAY_TPU_JAX_COORDINATOR"] = coord
    os.environ["RAY_TPU_JAX_NUM_PROCESSES"] = str(nprocs)
    os.environ["RAY_TPU_JAX_PROCESS_ID"] = str(pid)

    from ray_tpu.train.jax_trainer import jax_utils

    assert jax_utils.maybe_init_distributed(), "coordinator env missing"
    out = run_gang_step()
    print("GANG_RESULT " + json.dumps(out), flush=True)


if __name__ == "__main__":
    _main()
