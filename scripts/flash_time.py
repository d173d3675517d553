"""Time the three flash kernels alone on the chip, at the train cells' shapes:
`python3 -m scripts.flash_time [--shapes 13x20:1024:64,2x16:2048:256]
[--tiles 128,256,512,0] [--parent .parent] [--reps 20]`.

`--shapes BxH:S:D` is one device's call of a layer (`gpt2-large.train`: 13
sequences x 20 heads of 64 at 1,024 tokens; `gptj-6b.train-fsdp4`: 2 x 16 of
256 at 2,048), causal, bf16, default blocks. Each shape is timed in every
form: RULE (the kernels as the program runs them: the sub-tile that
`ops.attention._sub_tile` gives the head width), one form for each of
`--tiles` (that sub-tile forced; 0: the block itself, one pair a grid step),
and, with `--parent DIR`, the kernels of the checkout unpacked there (`git
archive <commit> | tar -x -C DIR`): how the rule was set (PERF.md §6, PR 45).

Every form is timed FROM AND TO [B, S, H·Dh], q, k, v, o, dO, dq, dk and dv
as the projections lay them, split into heads and transposed to `attend`'s
[B, H, S, Dh] as `models/gpt.py` `_block` does: the kernels of this checkout
read and write that form (`ops.attention.heads_a_step`; XLA cancels the
transposes), a checkout from before PR 49 pays copies around each call. A
form's calls run back to back under the profiler, `--reps` of the forward and
of the backward. A kernel's time ALONE is the MEDIAN device duration of its
events in the trace (what `flash_*_roofline` reads, by the same names), given
as microseconds a head and as a share of `benchmarks/peaks.py`'s roofline for
the call; `laid` is every device operation of the forward program, and of the
backward program, over its calls: the kernels TOGETHER with the relayouts
they need (microseconds a head; `ops`: the operations by family, which names
the copies); `apart` is how far the form's o, dq, dk and dv lie from the plain
reference's (float32, the first two heads; largest absolute difference). A
chip run or nothing: on the CPU (`--rehearse`) it runs each form once in
interpret mode at two heads and prints no time."""

from __future__ import annotations

import argparse
import importlib.util
import json
import statistics
import sys
import tempfile


def _load(path: str):
    spec = importlib.util.spec_from_file_location("parent_attention", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _device_us(trace_dir: str) -> tuple:
    """(median device microseconds of each flash kernel's events, summed
    microseconds of every device operation by family)."""
    import collections

    from jax.profiler import ProfileData

    from benchmarks.trace import find_xplane, op_family
    from ray_tpu.ops.attention import FLASH_KERNELS

    took = {name: [] for name in FLASH_KERNELS}
    ops = collections.Counter()
    for plane in ProfileData.from_file(find_xplane(trace_dir)).planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                family = op_family(ev.name)
                ops[family] += ev.duration_ns * 1e-3
                for name in took:  # no kernel's name is a prefix of another's
                    if family.startswith(name):
                        took[name].append(ev.duration_ns * 1e-3)
                        break
    return {name: statistics.median(v) if v else None for name, v in took.items()}, ops


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default="13x20:1024:64,2x16:2048:256")
    ap.add_argument("--tiles", default="128,256,512,0")
    ap.add_argument("--parent", default="")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp

    from benchmarks import peaks
    from ray_tpu.ops import attention

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not a.rehearse:
        raise SystemExit(f"{dev.platform}: a kernel's time is a chip run's (--rehearse runs no clock)")
    forms = [("rule", attention, None)]
    forms += [(f"tile-{int(t) or 'block'}", attention, int(t)) for t in a.tiles.split(",") if t]
    if a.parent:
        forms.append(("parent", _load(f"{a.parent}/ray_tpu/ops/attention.py"), None))
    costs = {"flash_fwd": peaks.flash_fwd_cost, "flash_bwd_dq": peaks.flash_bwd_dq_cost,
             "flash_bwd_dkv": peaks.flash_bwd_dkv_cost}
    rule = attention._sub_tile
    for shape in a.shapes.split(","):
        batch_heads, seq, dh = shape.split(":")
        (batch, heads), seq, dh = (int(x) for x in batch_heads.split("x")), int(seq), int(dh)
        if a.rehearse:
            batch, heads = 1, 2
        bh = batch * heads

        def split(x):  # the projections' [B, S, H·Dh] as `_block` hands it to `attend`
            return x.reshape(batch, seq, heads, dh).transpose(0, 2, 1, 3)

        def merge(x):
            return x.transpose(0, 2, 1, 3).reshape(batch, seq, heads * dh)

        keys = jax.random.split(jax.random.PRNGKey(0), 4)
        q, k, v, g = (jax.random.normal(kk, (batch, seq, heads * dh), jnp.bfloat16)
                      for kk in keys)
        scale = dh ** -0.5
        f32 = [split(x)[:1, :2].astype(jnp.float32) for x in (q, k, v, g)]
        ref, vjp = jax.vjp(lambda q, k, v: attention.attention_reference(q, k, v, True, scale),
                           *f32[:3])
        want = (ref, *vjp(f32[3]))
        for form, mod, tile in forms:
            if tile is not None:
                attention._sub_tile = lambda kernel, d, t=tile: t or 1 << 30

            def fwd(q, k, v, m=mod):
                o, lse = m._flash_fwd_pallas(split(q), split(k), split(v), True, scale,
                                             1024, 1024, interpret=a.rehearse, return_lse=True)
                return merge(o), lse

            def bwd(q, k, v, o, lse, g, m=mod):
                return tuple(merge(x) for x in m._flash_bwd_pallas(
                    split(q), split(k), split(v), split(o), lse, split(g), True, scale,
                    1024, 1024, interpret=a.rehearse))

            fwd, bwd = jax.jit(fwd), jax.jit(bwd)
            line = {"shape": [batch, heads, seq, dh], "form": form,
                    "sub_tile": mod is attention and [
                        attention._sub_tile(kernel, dh) for kernel in attention.FLASH_KERNELS]}
            o, lse = fwd(q, k, v)
            got = (o, *bwd(q, k, v, o, lse, g))    # compiled here, outside the trace
            attention._sub_tile = rule
            line["apart"] = [float(jnp.abs(split(x)[:1, :2].astype(jnp.float32) - y).max())
                             for x, y in zip(got, want)]
            if not a.rehearse:
                laid = {}
                for name, run in (("fwd", lambda: fwd(q, k, v)),
                                  ("bwd", lambda: bwd(q, k, v, o, lse, g))):
                    with tempfile.TemporaryDirectory() as tmp:
                        jax.profiler.start_trace(tmp)
                        jax.block_until_ready([run() for _ in range(a.reps)])
                        jax.profiler.stop_trace()
                        took, ops = _device_us(tmp)
                    laid[f"{name}_us_a_head"] = sum(ops.values()) / a.reps / bh
                    laid[f"{name}_ops"] = {f: round(us / a.reps / bh, 3)
                                           for f, us in ops.most_common()}
                    for kernel, us in took.items():
                        if us is None:
                            continue
                        least, bound = peaks.roofline_seconds(costs[kernel](bh, seq, dh),
                                                              dev.device_kind)
                        line[kernel] = {"us_a_head": us / bh,
                                        "roofline_pct": 100 * least * 1e6 / us, "bound": bound}
                line["laid"] = laid
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
