"""Time the paged programs of a benchmark configuration on the chip, one
shape bucket at a time, outside the engine: `python3 -m scripts.paged_attn_time
[--config smallthinker-21b-a3b] [--prefill 8:0,32:0,128:0,256:0,256:6144]
[--decode 4:256:9000/300/500/200] [--tile-keys 1024,4096] [--sampled]`.

`--prefill W:offset` is one 512-token chunk (the configuration's
`prefill_chunk_tokens`) at `pos_offset` over a table of W blocks;
`--decode B:W:p1/p2/..` is one decode step of a bucket of B lanes over tables
of W blocks, the real lanes at the positions given and the rest padding
lanes. Every KV group's table holds all W blocks, so the masks alone decide
what a query sees. Weights and pool are the cell's own sizes (`init_params`
on the device, `engine_options`), the programs jitted and donated as the
engine's are. `--tile-keys` times each shape once for every value of
`models.gpt._ATTN_TILE_KEYS` (a program without that constant runs each
shape once): how the tile of `_paged_layers`' key loop was chosen (PERF.md
§6, PR 29). `--sampled` times each shape a second time as the program the
engine dispatches (`serve/engine/engine.py: _paged_jits`: the same function
with the sampler behind it, ids for logits, the last ids carried beside the
pool), on the same input ids (an expert model routes by them): what the
epilogue costs a shape (PERF.md §6, PR 33).

Milliseconds a call, mean over `--reps` calls dispatched back to back and
waited for once. A chip run or nothing: on the CPU (`--rehearse`, the
configuration's tiny preset) it prints shapes only."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="smallthinker-21b-a3b")
    ap.add_argument("--prefill", default="8:0,32:0,128:0,256:0")
    ap.add_argument("--decode", default="")
    ap.add_argument("--tile-keys", default="")
    ap.add_argument("--sampled", action="store_true")
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import harness
    from ray_tpu.models import gpt

    config = harness.load_json(harness.ROOT, f"benchmarks/configs/{a.config}.json")
    arch = harness.arch(config["arch"])
    m = arch.dims(config, a.rehearse)
    part = config["rehearsal" if a.rehearse else "runners"]["requests"]
    opts = part["engine_options"]
    NB, BS = opts["num_blocks"], opts["block_size"]
    chunk = opts.get("prefill_chunk_tokens", 64)
    name, overrides = arch.program(config, m)
    cfg = gpt.CONFIGS[name](**overrides)
    G = len(gpt.kv_layout(cfg).windows)
    dev = jax.devices()[0]
    on_chip = dev.platform == "tpu"
    reps = a.reps if on_chip else 1
    params = jax.jit(lambda k: gpt.init_params(k, cfg))(jax.random.PRNGKey(0))
    kv = gpt.init_paged_cache(cfg, NB, BS)

    def table(W):
        """[G, W] (or [W]): group g holds blocks 1 + g*W .. of its own."""
        t = 1 + np.arange(G * W, dtype=np.int32).reshape(G, W)
        if t.max() >= NB:
            raise SystemExit(f"{G} tables of {W} blocks do not fit {NB} blocks")
        return t if G > 1 else t[0]

    tiles = [int(t) for t in a.tile_keys.split(",") if t]
    if not hasattr(gpt, "_ATTN_TILE_KEYS"):
        tiles = []
    rows = []

    def timed(row, fn, args):
        nonlocal kv
        out, kv = fn(params, *args, kv, cfg)
        jax.block_until_ready(out)
        t = time.perf_counter()
        for _ in range(reps):
            out, kv = fn(params, *args, kv, cfg)
        jax.block_until_ready(out)
        if on_chip:
            row["ms"] = 1e3 * (time.perf_counter() - t) / reps
        rows.append(row)
        print(json.dumps(row), flush=True)

    if a.sampled:
        from ray_tpu.serve.engine import engine as engine_module

        slots = opts["max_num_seqs"]
        last, sampling = engine_module.init_sampler(slots, 0, 0.0)

    for tile in tiles or [None]:
        if tile is not None:
            gpt._ATTN_TILE_KEYS = tile
        if a.sampled:
            engine_module._JITS = None      # traced anew under this tile

            def carrying(jit):      # `timed`'s signature, the last ids carried here
                def call(params, *rest):
                    nonlocal last
                    out, kv, last = jit(params, *rest[:-1], last, sampling, rest[-1])
                    return out, kv
                return call

            sampled_prefill, sampled_decode, _ = map(
                carrying, engine_module._paged_jits())
        # The constant is read while tracing and is no part of a program's
        # key; jit keeps traces by function, so each value gets its own.
        prefill = jax.jit(lambda *args: gpt.prefill_paged(*args),
                          static_argnums=(6,), donate_argnums=(5,))
        decode = jax.jit(lambda *args: gpt.decode_step_paged(*args),
                         static_argnums=(5,), donate_argnums=(4,))
        for spec in filter(None, a.prefill.split(",")):
            W, offset = (int(x) for x in spec.split(":"))
            n = min(chunk, W * BS - offset)
            args = (jnp.zeros((1, chunk), jnp.int32).at[0, :n].set(7), jnp.int32(n),
                    jnp.int32(offset), jnp.asarray(table(W)))
            row = {"program": "prefill_paged", "tile_keys": tile, "chunk": chunk,
                   "W": W, "keys": W * BS, "offset": offset}
            timed(dict(row), prefill, args)
            if a.sampled:
                meta = jnp.asarray([n, offset, 0], jnp.int32)
                timed({**row, "sampled": True}, sampled_prefill,
                      (args[0], meta, args[3]))
        for spec in filter(None, a.decode.split(",")):
            B, W, poss = spec.split(":")
            B, W = int(B), int(W)
            pos = [int(p) for p in poss.split("/")]
            positions = np.zeros((B,), np.int32)
            positions[:len(pos)] = pos
            shape = (B, G, W) if G > 1 else (B, W)
            tables = np.zeros(shape, np.int32)
            tables[:len(pos)] = table(W)    # lanes share blocks: reads only matter
            args = (jnp.full((B,), 7, jnp.int32), jnp.asarray(positions),
                    jnp.asarray(tables))
            row = {"program": "decode_step_paged", "tile_keys": tile, "lanes": B,
                   "W": W, "keys": W * BS, "positions": pos}
            timed(dict(row), decode, args)
            if a.sampled:     # rows: slot, position, host id, known: the same ids
                lanes = np.zeros((4, B), np.int32)      # (experts route by them)
                lanes[0], lanes[1] = np.arange(B) % slots, positions
                lanes[2], lanes[3] = 7, 1
                timed({**row, "sampled": True}, sampled_decode,
                      (jnp.asarray(lanes), args[2]))
    report = {"device": {"platform": dev.platform, "kind": dev.device_kind},
              "config": a.config, "n_layers": cfg.n_layers, "block_size": BS,
              "rows": rows}
    out = os.path.join(harness.ROOT, "chiprun_out", "paged_attn_time")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"{int(time.time())}.json"), "w") as f:
        json.dump(report, f)
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
