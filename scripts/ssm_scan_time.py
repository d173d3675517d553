"""Time ONE layer's selective scan of `jamba2-3b` on the chip at the cell's own
shapes, outside the engine, the plain `lax.scan` form beside the kernel:
`python3 -m scripts.ssm_scan_time [--shapes 1:256,1:64,1:1,16:1,64:1]
[--reps 20]`.

`--shapes B:S` is `B` lanes of `S` tokens: a prefill chunk is 1:256 (the
configuration's `prefill_chunk_tokens`), a decode step B:1. Each shape runs
`ops/ssm.py`'s `selective_scan` twice, `kernel=False` (the plain form: one
trip of a `lax.scan` a token) and `kernel=True` (`ssm_scan`, the time loop
inside the kernel), under the profiler; the times are DEVICE times from the
trace (`benchmarks/trace.py`): the whole jitted call (the kernel with the
re-tiling of its operands around it) and the kernel's own events by name.

The roofline share is `benchmarks.arch.jamba.kernel_costs` through
`benchmarks.peaks.roofline_seconds` over the time of the WHOLE call
(`ssm_scan_roofline`), not of the kernel's own events (`kernel_us`): where the
operands are small the compiler keeps them in VMEM between the fusion that
makes them and the kernel (memory space `S(1)` in the compiled text), so the
kernel's events leave out the HBM traffic its bytes stand for and read over
100% of a bound they never touched (64 lanes of one token: 14 us for 46 MB;
my chip run, PR 40); the call's time has every byte in it. The scan's
bound is its BYTES (the step, the input and the output a token a channel, the
state once): its arithmetic, 7 operations a token a channel a state, runs on
the vector unit and would take 0.75 us of the MXU's peak where its bytes take
20 us of HBM's, so `roofline_seconds` picks the memory bound at every shape
here; the MXU's peak is no ceiling of a kernel that issues no matrix product,
and the vector unit's own peak is in no table of `benchmarks/peaks.py`. A
share well under 100% therefore says the kernel is bound by the vector unit's
issue rate (16 states x 6 operations and an exponential a token a vreg of
channels), not that HBM idles for want of a better schedule.

A chip run or nothing: on the CPU (`--rehearse`) it prints shapes only."""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default="1:256,1:64,1:1,16:1,64:1")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp

    from benchmarks import harness, peaks, trace
    from ray_tpu.ops import ssm

    config = harness.load_json(harness.ROOT, "benchmarks/configs/jamba2-3b.json")
    arch = harness.arch(config["arch"])
    m = arch.dims(config, a.rehearse)
    Di, N = m["expand"] * m["d_model"], m["d_state"]
    dev = jax.devices()[0]
    if not a.rehearse and dev.platform != "tpu":
        print("a chip run or nothing: no TPU here (--rehearse prints shapes)", file=sys.stderr)
        return 1
    out_dir = os.path.join(harness.OUT, "trace", "ssm_scan_time")
    rows = []
    for shape in a.shapes.split(","):
        B, S = (int(v) for v in shape.split(":"))
        k = jax.random.split(jax.random.PRNGKey(B * 1000 + S), 5)
        delta = jax.nn.softplus(jax.random.normal(k[0], (B, S, Di)) - 4.0)
        x = jax.random.normal(k[1], (B, S, Di), jnp.bfloat16)
        A = -jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32)[:, None], (N, Di))
        Bm, Cm = jax.random.normal(k[2], (B, S, N)), jax.random.normal(k[3], (B, S, N))
        s0 = jax.random.normal(k[4], (B, *ssm.state_shape(Di, N)))
        valid = jnp.ones((B, S), bool)
        cost = arch.kernel_costs(m, B, S, 1)["ssm_scan"]
        row = {"lanes": B, "tokens": S, "flops": cost["flops"], "bytes": cost["bytes"]}
        if a.rehearse:
            rows.append(row)
            continue
        least, bound = peaks.roofline_seconds(cost, dev.device_kind)
        row.update(least_us=least * 1e6, bound=bound)

        def ssm_scan_plain(*args):
            return ssm.selective_scan(*args, kernel=False)

        def ssm_scan_kernel(*args):
            return ssm.selective_scan(*args, kernel=True)

        args = (delta, x, A, Bm, Cm, s0, valid)
        got = {}
        for name, fn in (("plain", ssm_scan_plain), ("kernel", ssm_scan_kernel)):
            jit = jax.jit(fn)
            got[name] = jax.block_until_ready(jit(*args))       # compile, warm
            shutil.rmtree(out_dir, ignore_errors=True)
            jax.profiler.start_trace(out_dir)
            for _ in range(a.reps):
                y = jit(*args)
            jax.block_until_ready(y)
            jax.profiler.stop_trace()
            t = trace.reduce_trace(trace.find_xplane(out_dir))
            calls = [s for nm, xs in t["module_s"].items() if fn.__name__ in nm for s in xs]
            row[f"{name}_call_us"] = 1e6 * sum(calls) / max(len(calls), 1)
            if name == "kernel":
                names = [n for n in t["op_self_s"] if n.startswith("ssm_scan")]
                took = sum(t["op_self_s"][n] for n in names)
                count = sum(t["op_count"][n] for n in names)
                row["kernel_us"] = 1e6 * took / max(count, 1)
                row["kernel_calls"] = count
                row["ssm_scan_roofline"] = 100.0 * least * len(calls) / sum(calls) \
                    if calls else None
        shutil.rmtree(out_dir, ignore_errors=True)
        row["max_abs_diff"] = [float(jnp.abs(p - q).max())
                               for p, q in zip(got["plain"], got["kernel"])]
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({"device": {"platform": dev.platform, "kind": dev.device_kind},
                      "d_inner": Di, "d_state": N, "reps": a.reps, "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
