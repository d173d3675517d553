"""Compile-only rehearsal of the three paged programs, without the chip:
`JAX_PLATFORMS=cpu python3 -m scripts.paged_rehearse --model gpt2-large
--num-blocks 1024 --block-size 16 [--lanes 8] [--width 16] [--chunk 64]
[--spec 4] [--n-layers N] [--config <benchmark configuration>]
[--decode-lanes 1,2,4,8,16]` from the root of a checkout (a model whose
layers form several KV groups gets one block table a group; a latent model's
pool is one array).

Compiles `decode_step_paged`, `prefill_paged` and `verify_step_paged` of
`models/gpt.py` for one described (not attached) `v5e:2x2` device, each
with the sampling epilogue the engine puts behind it and jitted and donated
exactly as the engine does (`serve/engine/engine.py: _paged_jits`: the
programs it dispatches), and prints for each the GiB of arguments, temporaries and
output, and every operation of the compiled program whose result is at
least half of one layer's pool (K or V), with its layout, and the bytes its
`copy-start` / `copy-done` pairs move by loop depth (`loop_copy_bytes`: what a
chunk program's key loop moved between HBM and the compiler's scoped memory on
every key tile before PR 41, PERF.md §5; 0 inside a layer's loops with the
chunk kernel, which has no key loop), and ONE number a program,
`param_relayout_MiB` (`param_relayout_bytes`): the bytes the program moves of
its own parameters other than the pool before it computes with them (`copy` /
`transpose` / `copy-start` outside fusions; 1,152 for every paged program of
`ouro-2.6b` over the public tree, 0 over the tree the engine holds since
PR 50, `models/gpt.py` `hold_served`, which is the tree compiled here; a
latent model's prefetched `lead_w_uq` / `lead_w_dq` / `lead_w_ukv` read 109). In a healthy
paged program the pool enters in the layout the device keeps, is the layer
scan's carry, and only the in-place row update names it (`fusion(scatter)`
or `dynamic-update-slice` with the pool's own shape, aliased to the
argument); the `convert`s are the per-step bf16 copy of the weights, and at
many lanes x blocks the gathered history itself grows past the threshold.
A pool-sized `copy` between two different layouts is a relayout the device
pays in every layer of every step (PERF.md §6, PR 25).

The programs are compiled with the kernels the chip runs (the Pallas norms,
the grouped experts' two kernels), not the plain paths of this process' backend.
`--decode-lanes` compiles the decode program again at each of those lane
buckets and prints its temporaries; for an expert model also, from shapes,
the grouped form's hidden rows between its two kernels, `[T x 128, F]` with
`T = lanes x top_k // 128 + experts held` (one layer's at a time; the chip's
compiler keeps them out of the temporaries it counts: PERF.md §6, PR 39).

Each program's paged attention kernels are counted (`kernels`: the chunk's
`paged_chunk_attn`, the decode step's `paged_decode_attn`; once a layer kind
where it stands in a layer scan's body). `--fusion bitcast_add_fusion` prints
the decode program's fusions of that name, with operands, bytes and body: what
an operation a trace names IS (ROADMAP S13).

`--digest` compiles nothing: it prints a hash of each program AS LOWERED
(StableHLO; the serialized bodies of the Pallas calls left out: they carry
the checkout's path and the call sites' line numbers, which is also why a
parent's compile cache misses for a change that moved a line of `gpt.py`). Run
with the same flags from the roots of two checkouts (`PYTHONPATH=$PWD python3
<this file> --digest ...` in a checkout whose script lacks the flag): equal
digests say the change left that model's served programs alone.

Nothing runs: a compile that passes is not a chip run, and no time, rate or
share comes from here. The last line of stdout is one JSON object."""

from __future__ import annotations

import argparse
import hashlib
import json
import math
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
                "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
                "u64": 8}
# `%name = bf16[36,1024,16,1280]{3,2,1,0:T(8,128)(2,1)} opcode(...)`; a tuple
# result (`(bf16[..], ..)`) is walked shape by shape.
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.+?)\s+([\w\-]+)\(")
_SHAPE = re.compile(r"(\w+)\[([\d,]*)\](\{[^}]*\})?")


_PASSES_ALONG = ("parameter", "tuple", "get-tuple-element", "bitcast", "while")


def _array_bytes(dtype: str, dims: str) -> int:
    """Bytes of an array the text writes as `dtype[dims]`."""
    return _DTYPE_BYTES.get(dtype, 0) * math.prod(
        int(d) for d in filter(None, dims.split(",")))


def big_ops(hlo_text: str, min_bytes: int):
    """[(opcode, name, shape-with-layout, bytes)] of every instruction of the
    compiled program with a result array of at least `min_bytes`. A fusion
    is one operation, named `fusion(<opcode of its root>)`; what only passes
    arrays along (parameters, tuples, the loop itself) is left out."""
    roots, inside, out = {}, None, []
    lines = hlo_text.splitlines()
    for line in lines:           # first pass: each fused computation's root
        if line.startswith("%fused_computation"):
            inside = line.split()[0].lstrip("%")
        elif line.startswith("}"):
            inside = None
        elif inside and line.lstrip().startswith("ROOT"):
            m = _INSTR.match(line)
            roots[inside] = m.group(3) if m else "?"
    inside = None
    for line in lines:
        if line.startswith("%fused_computation"):
            inside = True
        elif line.startswith("}"):
            inside = None
        m = None if inside else _INSTR.match(line)
        if not m or m.group(3) in _PASSES_ALONG:
            continue
        name, result, opcode = m.groups()
        if opcode == "fusion":
            called = re.search(r"calls=%([\w.\-]+)", line)
            opcode = f"fusion({roots.get(called.group(1), '?') if called else '?'})"
        for dtype, dims, layout in _SHAPE.findall(result):
            n = _array_bytes(dtype, dims)
            if n >= min_bytes:
                out.append((opcode, name, f"{dtype}[{dims}]{layout}", n))
    return out


_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_CALLED = re.compile(r"(body|condition|calls|to_apply|true_computation|"
                     r"false_computation)=%?([\w.\-]+)|branch_computations=\{([^}]*)\}")


def loop_copy_bytes(hlo_text: str) -> dict:
    """{loop depth: bytes} that the `copy-start` / `copy-done` pairs of a
    compiled program move, by how many `while` bodies enclose them (0: outside
    any loop; 1: the layer scan of a one-pass model; one deeper: a loop inside
    a layer, which in a chunk program is the key loop of `_paged_layers`).
    Static text: each pair once, whatever the loops' trip counts. The bytes
    are those of the pair's first result, the array that is moved."""
    calls, copies, name, entry = {}, {}, None, None
    for line in hlo_text.splitlines():
        if name is None:
            m = _COMPUTATION.match(line)
            if m and "=" not in line.split("{")[0].split("(")[0]:
                name = m.group(1)
                calls[name], copies[name] = [], 0
                if line.startswith("ENTRY"):
                    entry = name
            continue
        if line.startswith("}"):
            name = None
            continue
        m = _INSTR.match(line)
        if m and m.group(3) == "copy-start":
            copies[name] += _array_bytes(*_SHAPE.search(m.group(2)).groups()[:2])
        for kind, one, many in _CALLED.findall(line):
            for callee in ([one] if one else re.findall(r"%?([\w.\-]+)", many)):
                calls[name].append((callee, kind == "body"))
    by_depth, seen = {}, set()

    def walk(comp, depth):
        if (comp, depth) in seen or comp not in calls:
            return
        seen.add((comp, depth))
        if copies[comp]:
            by_depth[depth] = by_depth.get(depth, 0) + copies[comp]
        for callee, is_body in calls[comp]:
            walk(callee, depth + is_body)

    walk(entry, 0)
    return dict(sorted(by_depth.items()))


_MOVES = ("copy", "transpose", "copy-start")


def param_relayouts(hlo_text: str, min_bytes: int = 2**20):
    """[(parameter, opcode, result with layout, bytes)] of what a compiled
    program moves of its own PARAMETERS before it computes with them: the
    `copy` / `transpose` / `copy-start` instructions of the entry computation,
    outside fusions, whose operand is a program parameter (straight or
    through bitcasts) other than the pool (`kv`): a weight held in a form the
    program does not read as it lies is rewritten here, whole, once a call
    (PERF.md §6, PR 50: `ouro-2.6b`'s fused q/k/v stack, 1,152 MiB in every
    paged program), and a `copy-start` is a stack the compiler prefetches into
    its scoped memory. A result under `min_bytes` is left out: a norm's vector
    or a table on its way there is kilobytes."""
    inside, source, moved = False, {}, []
    for line in hlo_text.splitlines():
        if line.startswith("ENTRY"):
            inside = True
        elif line.startswith("}"):
            inside = False
        m = _INSTR.match(line) if inside else None
        if not m:
            continue
        name, result, opcode = m.groups()
        operand = re.match(r"%?([\w.\-]+)", line[m.end():])
        operand = operand and source.get(operand.group(1))
        if opcode == "parameter":
            source[name] = name
        elif opcode == "bitcast" and operand:
            source[name] = operand
        elif opcode in _MOVES and operand and not operand.startswith("kv_"):
            dtype, dims, layout = _SHAPE.search(result).groups()
            n = _array_bytes(dtype, dims)
            if n >= min_bytes:
                moved.append((operand, opcode, f"{dtype}[{dims}]{layout}", n))
    return moved


def param_relayout_bytes(hlo_text: str, min_bytes: int = 2**20) -> int:
    """The bytes of `param_relayouts`: one number a program."""
    return sum(n for *_, n in param_relayouts(hlo_text, min_bytes))


def kernels_in(hlo_text: str) -> dict:
    """{name: calls} of the paged attention kernels (`ops/paged_attention.py`:
    the chunk's, the decode step's) in a compiled program's text, by the name
    its `pallas_call` was given: which programs hold which, and how often."""
    from ray_tpu.ops import paged_attention

    calls = [line for line in hlo_text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    return {name: sum(f"/{name}/" in line or f"/{name}\"" in line for line in calls)
            for name in (paged_attention.PAGED_CHUNK_KERNEL,
                         paged_attention.PAGED_DECODE_KERNEL)}


def fusions_named(hlo_text: str, name: str):
    """[(instruction, its line, the called computation's body)] of every
    fusion `name` or `name.<n>` of a compiled program: what a trace's
    operation of that name IS (its operands with their shapes, the product or
    copy inside it). Metadata and backend configuration are cut out."""
    def clean(line):
        line = re.sub(r', metadata=\{[^}]*\}', "", line)
        return re.sub(r', backend_config=\{.*$', "", line)

    bodies, inside = {}, None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            inside = m.group(1)
            bodies[inside] = []
        elif line.startswith("}"):
            inside = None
        elif inside:
            bodies[inside].append(clean(line))
    out = []
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m and re.fullmatch(re.escape(name) + r"(\.\d+)?", m.group(1)):
            called = re.search(r"calls=%([\w.\-]+)", line)
            out.append((m.group(1), clean(line).strip(),
                        bodies.get(called.group(1), []) if called else []))
    return out


def rehearse(cfg, device, num_blocks: int, block_size: int, lanes: int = 8,
             width: int = 16, chunk: int = 64, spec: int = 4,
             init: bool = False, decode_lanes=(), digest: bool = False,
             fusion: str = "") -> dict:
    """Compile the three paged programs of `cfg` for `device` (a described
    device of `jax.experimental.topologies`) at one shape bucket each; with
    `init`, also `init_params` under one jit (what making the weights in a
    stated dtype keeps beside them); the decode program again at each lane
    bucket of `decode_lanes`; with `fusion`, print the decode program's fusions
    of that name (`fusions_named`)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.models.gpt import hold_served, init_paged_cache, init_params, kv_layout
    from ray_tpu.serve.engine.engine import _paged_jits, init_sampler

    one_chip = SingleDeviceSharding(device)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
            tree)

    # the tree in the form the engine holds it in (`hold_served`)
    params = on_chip(jax.eval_shape(
        lambda k: hold_served(init_params(k, cfg))[0], jax.random.PRNGKey(0)))
    stateful = int(bool(kv_layout(cfg).state))   # a state slot a lane beside the pool
    kv = on_chip(jax.eval_shape(
        lambda: init_paged_cache(cfg, num_blocks, block_size, lanes * stateful)))
    # the last ids of `lanes` slots and the sampler's constants, as the
    # engine carries them beside the pool
    last, sampling = on_chip(jax.eval_shape(lambda: init_sampler(lanes, 0, 0.0)))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    prefill, decode, verify = _paged_jits()
    groups = len(kv_layout(cfg).windows)    # one block table a KV group
    table = (width,) if groups == 1 else (groups, width)

    def decode_at(n):
        return lambda: decode.lower(
            params, i32(4 + stateful, n), i32(n, *table), kv, last, sampling, cfg)

    programs = {
        "decode_step_paged": decode_at(lanes),
        "prefill_paged": lambda: prefill.lower(
            params, i32(1, chunk), i32(3 + stateful), i32(*table), kv, last, sampling,
            cfg),
        "verify_step_paged": lambda: verify.lower(
            params, i32(lanes, spec + 1), i32(lanes), i32(lanes),
            i32(lanes, *table), kv, cfg),
    }
    for n in decode_lanes:
        programs[f"decode_step_paged@{n}"] = decode_at(n)
    if init:
        programs["init_params"] = lambda: jax.jit(
            lambda k: init_params(k, cfg)).lower(
                jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip))
    layer_pool = kv["k"].size // kv["k"].shape[0] * kv["k"].dtype.itemsize
    report = {
        "n_layers": cfg.n_layers,
        "pool_shape": list(kv["k"].shape), "pool_dtype": str(kv["k"].dtype),
        "pool_GiB": len(set(kv) & {"k", "v"}) * kv["k"].shape[0] * layer_pool / 2**30,
        "state_GiB": sum(a.size * a.dtype.itemsize
                         for a in kv.get("state", {}).values()) / 2**30,
        "weights_GiB": sum(a.size * a.dtype.itemsize for a in params.values()) / 2**30,
        "layer_pool_MiB": layer_pool / 2**20,
        "lanes": lanes, "width": width, "chunk": chunk, "spec": spec,
        "decode_lanes": list(decode_lanes), "programs": {},
    }
    for name, lower in programs.items():
        try:
            began = time.perf_counter()
            lowered = lower()
            lower_s = time.perf_counter() - began   # what every set-up pays a program anew
            if digest:
                text = re.sub(r'\\22body\\22: \\22[^\\]*\\22', "BODY", lowered.as_text())
                report["programs"][name] = hashlib.sha256(text.encode()).hexdigest()[:16]
                print(f"{name}: {report['programs'][name]} "
                      f"(traced and lowered in {lower_s:.2f} s)", flush=True)
                continue
            compiled = lowered.compile()
        except Exception as e:  # noqa: BLE001 — a refusal is the answer
            report["programs"][name] = {"refused": str(e).splitlines()[0][:300]}
            continue
        mem = compiled.memory_analysis()
        text = compiled.as_text()
        ops = big_ops(text, layer_pool // 2)
        moved = param_relayouts(text)
        copies = loop_copy_bytes(text)
        # a loop inside a layer: under the layer scan, itself under the pass
        # scan of a looped model
        in_layer = sum(b for d, b in copies.items() if d > 1 + (cfg.ut_steps > 1))
        report["programs"][name] = {
            "loop_copy_MiB": {str(d): b / 2**20 for d, b in copies.items()},
            "key_loop_copy_MiB": in_layer / 2**20,
            "arguments_GiB": mem.argument_size_in_bytes / 2**30,
            "temp_GiB": mem.temp_size_in_bytes / 2**30,
            "output_GiB": mem.output_size_in_bytes / 2**30,
            "alias_GiB": mem.alias_size_in_bytes / 2**30,
            "pool_sized_ops": [
                {"op": op, "name": n, "result": shape, "MiB": b / 2**20}
                for op, n, shape, b in ops
            ],
            "kernels": kernels_in(text),
            "lower_s": lower_s,
            "param_relayout_MiB": sum(b for *_, b in moved) / 2**20,
        }
        print(f"{name}: args {mem.argument_size_in_bytes / 2**30:.2f} GiB, "
              f"temp {mem.temp_size_in_bytes / 2**30:.4f}, "
              f"output {mem.output_size_in_bytes / 2**30:.2f}, "
              f"aliased {mem.alias_size_in_bytes / 2**30:.2f}; "
              f"{len(ops)} operation(s) of >= {layer_pool / 2**21:.1f} MiB; "
              f"copy-start/copy-done pairs inside a loop of a layer (a chunk's key "
              f"loop) move {in_layer / 2**20:.1f} MiB a trip, by loop depth "
              + (", ".join(f"{d}: {b / 2**20:.1f}" for d, b in copies.items()) or "none"),
              flush=True)
        for op, n, shape, b in ops:
            print(f"    {op:28s} {shape}  {b / 2**20:.1f} MiB  %{n}", flush=True)
        for param, op, shape, b in moved:
            print(f"    {op + ' of a parameter':28s} {shape}  {b / 2**20:.1f} MiB  %{param}",
                  flush=True)
        print(f"    attention kernels: {report['programs'][name]['kernels']}; "
              f"traced and lowered in {lower_s:.2f} s; param_relayout_MiB "
              f"{report['programs'][name]['param_relayout_MiB']:.1f}", flush=True)
        if fusion and name == "decode_step_paged":
            for instr, line, body in fusions_named(text, fusion):
                operands = sum(     # a stacked weight counts whole: a layer reads its slice
                    _array_bytes(d, dims) for b in body if " parameter(" in b
                    for d, dims, _ in _SHAPE.findall(b.split(" parameter(")[0]))
                print(f"  %{instr}: parameters {operands / 2**20:.2f} MiB\n    {line}")
                print("\n".join("      " + b.strip() for b in body), flush=True)
    return report


def config_program(name: str):
    """(model preset, overrides) of the benchmark configuration
    `benchmarks/configs/<name>.json`: its own program model, as its cells run it."""
    from benchmarks import harness

    config = harness.load_json(harness.ROOT, f"benchmarks/configs/{name}.json")
    arch = harness.arch(config["arch"])
    return arch.program(config, arch.dims(config, False))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--model", default="gpt2-large")
    ap.add_argument("--num-blocks", type=int, default=1024)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--lanes", type=int, default=8)
    ap.add_argument("--width", type=int, default=16, help="blocks in a table")
    ap.add_argument("--chunk", type=int, default=64, help="prefill chunk tokens")
    ap.add_argument("--spec", type=int, default=4, help="draft tokens verified")
    ap.add_argument("--n-layers", type=int, default=None,
                    help="cut the preset's depth (a 6 B model on one chip)")
    ap.add_argument("--decode-lanes", default="",
                    help="lane buckets to compile the decode program at besides --lanes")
    ap.add_argument("--digest", action="store_true",
                    help="hash each program as lowered and compile nothing: equal in "
                         "two checkouts = the served programs did not change")
    ap.add_argument("--fusion", default="",
                    help="print the decode program's fusions of this name (and "
                         "name.<n>): operands, bytes, body")
    ap.add_argument("--config", default=None,
                    help="a benchmark configuration (benchmarks/configs/<name>.json): "
                         "its program model and overrides instead of --model")
    a = ap.parse_args()
    from jax.experimental import topologies

    from ray_tpu.models.gpt import CONFIGS
    from ray_tpu.ops import attention

    # The programs pick their kernels by `jax.default_backend()`, which is the
    # CPU here: steer them to the TPU branch (the Pallas norms, the grouped
    # experts' kernels), in this script and nowhere else, so that what the
    # chip's compiler refuses of a kernel (VMEM, tiling) shows here.
    attention._on_tpu = lambda: True
    overrides = {}
    if a.config:
        a.model, overrides = config_program(a.config)
    if a.n_layers is not None:
        overrides["n_layers"] = a.n_layers
    cfg = CONFIGS[a.model](**overrides, remat=False, remat_policy=None)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    report = rehearse(cfg, topo.devices[0], a.num_blocks, a.block_size,
                      a.lanes, a.width, a.chunk, a.spec, init=True,
                      decode_lanes=[int(n) for n in a.decode_lanes.split(",") if n],
                      digest=a.digest, fusion=a.fusion)
    if cfg.mlp_type == "moe":   # what the grouped form's hidden rows take, by shape
        from ray_tpu.ops.moe import GROUP_ROWS

        held = cfg.moe_held[1] if cfg.moe_held else cfg.moe_experts
        for n in sorted({a.lanes, *report["decode_lanes"]}):
            tiles = n * cfg.moe_top_k // GROUP_ROWS + held
            print(f"decode at {n} lanes: hidden rows [{tiles} x {GROUP_ROWS}, {cfg.d_mlp}] "
                  f"{tiles * GROUP_ROWS * cfg.d_mlp * 2 / 2**20:.1f} MiB")
    print(json.dumps({"model": a.model, **report}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
