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
`ops.paged_attention._ATTN_TILE_KEYS`: how the tile of the paged key loop was
chosen (PERF.md §6, PR 29). `--decode-forms gather,kernel` times each decode shape once
in each form of the step's attention: GATHER, every lane's rows gathered at the
table's width (what every decode program ran until PR 44 and what heads of 64
and the CPU still run), and KERNEL (`ops/paged_attention.py`
`paged_decode_attention`: each lane's own blocks through its table; the form
the rule, `paged_attn_form`, gives the shape is the default);
`--group-kib 512,1024,2048` times the kernel once for every value of its DMA
group, `ops.paged_attention._DECODE_GROUP_BYTES` (PERF.md §6, PR 44).
`--sampled` times each shape a second time as the program the
engine dispatches (`serve/engine/engine.py: _paged_jits`: the same function
with the sampler behind it, ids for logits, the last ids carried beside the
pool), on the same input ids (an expert model routes by them): what the
epilogue costs a shape (PERF.md §6, PR 33). `--ids distinct` gives decode
lane b the id 7 + b and a chunk's token j the id 7 + j instead of 7 for all
(an expert model's step reads the experts its tokens choose: alike, a chunk's
tokens would all be rows of the same few experts). `--latent-forms 64,256` (a latent model) times ONE
layer's attention of one chunk over a table of W blocks, outside any program,
in the forms the mathematics allows: ABSORBED (what `_paged_layers` runs off
the chip and ran on it until PR 41: the key up-projection on the query, every
head over the one cached row, a loop over key tiles), EXPANDED (each tile's
rows widened to per-head keys and values first; PERF.md §6, PR 34: a starting
point, not a path of the program), the same gathers, tiles, mask and online
softmax, and KERNEL (the absorbed operands through `ops/paged_attention.py`
`paged_chunk_attention`, the table's rows gathered once: what a chunk program
runs on the chip since PR 41; `--q-rows 512,1024` times it once for every
value of its query tile, `ops.paged_attention._CHUNK_Q_ROWS`).

Milliseconds a call, mean over `--reps` calls dispatched back to back and
waited for once. A chip run or nothing: on the CPU (`--rehearse`, the
configuration's tiny preset) it prints shapes only."""

from __future__ import annotations

import argparse
import itertools
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
    ap.add_argument("--decode-forms", default="")
    ap.add_argument("--group-kib", default="")
    ap.add_argument("--sampled", action="store_true")
    ap.add_argument("--ids", choices=("same", "distinct"), default="same")
    ap.add_argument("--latent-forms", default="")
    ap.add_argument("--q-rows", default="")
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import harness
    from ray_tpu.models import gpt
    from ray_tpu.ops import paged_attention

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
    stateful = bool(gpt.kv_layout(cfg).state)   # a state slot a lane beside the pool
    kv = gpt.init_paged_cache(cfg, NB, BS, opts["max_num_seqs"] * stateful)

    def table(W):
        """[G, W] (or [W]): group g holds blocks 1 + g*W .. of its own."""
        t = 1 + np.arange(G * W, dtype=np.int32).reshape(G, W)
        if t.max() >= NB:
            raise SystemExit(f"{G} tables of {W} blocks do not fit {NB} blocks")
        return t if G > 1 else t[0]

    gathered_logits = {}
    if a.decode_forms:      # rows that are not all alike, so that the forms can differ
        for name in set(kv) & {"k", "v"}:   # one layer's random rows in every layer,
            shape, dtype = kv[name].shape, kv[name].dtype   # and no second pool beside it
            del kv[name]
            kv[name] = jax.jit(lambda: jnp.broadcast_to(
                jax.random.normal(jax.random.PRNGKey(2), shape[1:], dtype), shape))()
    tiles = [int(t) for t in a.tile_keys.split(",") if t]
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
        if "form" in row and not row.get("sampled"):    # the forms agree at the logits
            logits = np.asarray(out[0] if isinstance(out, tuple) else out,
                                np.float32)[:len(row["positions"])]    # the real lanes
            first = gathered_logits.setdefault(json.dumps(row["positions"]), logits)
            row["logits_off_first_form"] = float(np.abs(logits - first).max())
            row["logits_absmax"] = float(np.abs(logits).max())
        rows.append(row)
        print(json.dumps(row), flush=True)

    if a.sampled:
        from ray_tpu.serve.engine import engine as engine_module

        slots = opts["max_num_seqs"]
        last, sampling = engine_module.init_sampler(slots, 0, 0.0)

    for tile in tiles or [None]:
        if tile is not None:
            paged_attention._ATTN_TILE_KEYS = tile
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
        for spec in filter(None, a.prefill.split(",")):
            W, offset = (int(x) for x in spec.split(":"))
            n = min(chunk, W * BS - offset)
            ids = 7 + np.arange(n, dtype=np.int32) * (a.ids == "distinct")
            args = (jnp.zeros((1, chunk), jnp.int32).at[0, :n].set(ids % cfg.vocab_size),
                    jnp.int32(n), jnp.int32(offset), jnp.asarray(table(W)))
            row = {"program": "prefill_paged", "tile_keys": tile, "chunk": chunk,
                   "W": W, "keys": W * BS, "offset": offset}
            timed(dict(row), prefill, args)
            if a.sampled:
                meta = jnp.asarray([n, offset, 0], jnp.int32)
                timed({**row, "sampled": True}, sampled_prefill,
                      (args[0], meta, args[3]))
        rule, own_group = paged_attention.paged_attn_form, paged_attention._DECODE_GROUP_BYTES

        def forced(form):       # the rule, a decode step's form forced
            def answer(tokens, width, block, *rows):
                if tokens != 1:
                    return rule(tokens, width, block, *rows)
                if form == "kernel" and on_chip:
                    return paged_attention.DECODE_KERNEL
                one_tile = paged_attention.paged_attn_tiling(width, block)[1] == 1
                return paged_attention.ONE_SHOT if one_tile else paged_attention.KEY_LOOP
            return answer

        groups = [int(g) << 10 for g in a.group_kib.split(",") if g]
        forms = [(form, group) for form in a.decode_forms.split(",") if form
                 for group in (groups if form == "kernel" and groups else [None])]
        for spec, (form, group) in itertools.product(
                filter(None, a.decode.split(",")), forms or [(None, None)]):
            # both are read while the program is traced: a jit a form
            paged_attention.paged_attn_form = rule if form is None else forced(form)
            paged_attention._DECODE_GROUP_BYTES = group or own_group
            decode = jax.jit(      # (.., tables, state slots or None, kv, cfg)
                lambda p, ids, pos, tables, slots, kv, cfg: gpt.decode_step_paged(
                    p, ids, pos, tables, kv, cfg, slots),
                static_argnums=(6,), donate_argnums=(5,))
            if a.sampled:
                engine_module._JITS = None
                _, sampled_decode, _ = map(carrying, engine_module._paged_jits())
            B, W, poss = spec.split(":")
            B, W = int(B), int(W)
            pos = [int(p) for p in poss.split("/")]
            positions = np.zeros((B,), np.int32)
            positions[:len(pos)] = pos
            shape = (B, G, W) if G > 1 else (B, W)
            tables = np.zeros(shape, np.int32)
            tables[:len(pos)] = table(W)    # lanes share blocks: reads only matter
            ids = 7 + np.arange(B, dtype=np.int32) * (a.ids == "distinct")
            state = ((np.arange(B) < len(pos)) * (1 + np.arange(B) % opts["max_num_seqs"])
                     ).astype(np.int32)
            args = (jnp.asarray(ids), jnp.asarray(positions), jnp.asarray(tables),
                    jnp.asarray(state) if stateful else None)
            row = {"program": "decode_step_paged", "tile_keys": tile, "lanes": B,
                   "W": W, "keys": W * BS, "positions": pos,
                   "form": form or ("kernel" if rule(
                       1, W, BS, *gpt.kv_head_rows(cfg)[1:], cfg.dtype
                   ) == paged_attention.DECODE_KERNEL else "gather"),
                   "group_kib": paged_attention._DECODE_GROUP_BYTES >> 10}
            timed(dict(row), decode, args)
            if a.sampled:     # rows: slot, position, host id, known: the same ids
                lanes = np.zeros((4, B), np.int32)      # (experts route by them)
                lanes[0], lanes[1] = np.arange(B) % slots, positions
                lanes[2], lanes[3] = ids, 1
                timed({**row, "sampled": True}, sampled_decode,
                      (jnp.asarray(lanes), args[2]))
        paged_attention.paged_attn_form, paged_attention._DECODE_GROUP_BYTES = rule, own_group
    for W in (int(w) for w in a.latent_forms.split(",") if w):
        q_rows = [int(r) for r in a.q_rows.split(",") if r]
        for form, ms in latent_forms(cfg, params, kv["k"], table(W), chunk, BS, reps,
                                     on_chip, q_rows).items():
            row = {"program": "latent_attention", "form": form, "chunk": chunk,
                   "W": W, "keys": W * BS}
            if on_chip:
                row["ms"] = ms
            rows.append(row)
            print(json.dumps(row), flush=True)
    report = {"device": {"platform": dev.platform, "kind": dev.device_kind},
              "config": a.config, "n_layers": cfg.n_layers, "block_size": BS,
              "rows": rows}
    out = os.path.join(harness.ROOT, "chiprun_out", "paged_attn_time")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"{int(time.time())}.json"), "w") as f:
        json.dump(report, f)
    print(json.dumps(report))
    return 0


def latent_forms(cfg, params, pool, table, chunk, BS, reps, kernel=False, q_rows=()):
    """{form: ms a call} of one layer's latent attention, `chunk` queries at
    the END of a table of W blocks (every key seen by the last query), in
    tiles of `_ATTN_TILE_KEYS` keys with an online softmax. Queries and
    weights are the model's shapes with layer 0's up-projections. `kernel`
    (the chip): the chunk kernel too, once for each query tile of `q_rows`
    (the module's own if none)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt
    from ray_tpu.ops import paged_attention

    H, R, Dn, Dr = cfg.n_heads, cfg.kv_lora_rank, cfg.d_head, cfg.rotary_dim
    T = min(paged_attention._ATTN_TILE_KEYS, len(table) * BS)
    tiles = len(table) * BS // T
    table = jnp.asarray(table).reshape(tiles, T // BS)
    key = jax.random.PRNGKey(1)
    q = jax.random.normal(key, (H, chunk, Dn + Dr), cfg.dtype)
    w_ukv = params["w_ukv"][0]                              # [R, H, Dn + Dn]
    qpos = (len(table.reshape(-1)) * BS - chunk + jnp.arange(chunk))[None, :, None]
    scale = gpt._latent_scale(cfg)

    def online(pool, scores_and_values):
        def trip(j, carry):
            m, l, acc = carry
            rows = pool[0, table[j]].reshape(T, -1)         # [T, 640]
            kp = (j * T + jnp.arange(T))[None, None, :]
            scores, mix = scores_and_values(rows)
            scores = jnp.where(kp <= qpos, scores * scale, -1e30)
            m_new = jnp.maximum(m, scores.max(-1))
            p = jnp.exp(scores - m_new[..., None])
            fade = jnp.exp(m - m_new)
            return m_new, l * fade + p.sum(-1), acc * fade[..., None] + mix(p)
        return trip

    def run(trip, width):
        m, l, acc = jax.lax.fori_loop(0, tiles, trip, (
            jnp.full((H, chunk), -1e30, jnp.float32), jnp.zeros((H, chunk), jnp.float32),
            jnp.zeros((H, chunk, width), jnp.float32)))
        return (acc / l[..., None]).astype(cfg.dtype)

    @jax.jit
    def absorbed(q, pool, w_ukv):
        w_uk, w_uv = w_ukv[..., :Dn], w_ukv[..., Dn:]
        qa = jnp.concatenate([jnp.einsum("hsd,rhd->hsr", q[..., :Dn], w_uk),
                              q[..., Dn:]], -1)             # [H, S, R + Dr]

        def sv(rows):
            scores = jnp.einsum("hsd,td->hst", qa, rows[:, :R + Dr],
                                preferred_element_type=jnp.float32)
            return scores, lambda p: jnp.einsum(
                "hst,tr->hsr", p.astype(rows.dtype), rows[:, :R],
                preferred_element_type=jnp.float32)

        out = run(online(pool, sv), R)
        return jnp.einsum("hsr,rhd->hsd", out, w_uv)

    @jax.jit
    def expanded(q, pool, w_ukv):
        w_uk, w_uv = w_ukv[..., :Dn], w_ukv[..., Dn:]

        def sv(rows):
            c = rows[:, :R]
            k = jnp.einsum("tr,rhd->htd", c, w_uk)          # [H, T, Dn]
            v = jnp.einsum("tr,rhd->htd", c, w_uv)
            scores = (jnp.einsum("hsd,htd->hst", q[..., :Dn], k,
                                 preferred_element_type=jnp.float32)
                      + jnp.einsum("hsd,td->hst", q[..., Dn:], rows[:, R:R + Dr],
                                   preferred_element_type=jnp.float32))
            return scores, lambda p: jnp.einsum(
                "hst,htd->hsd", p.astype(v.dtype), v, preferred_element_type=jnp.float32)

        return run(online(pool, sv), Dn)

    def by_kernel(q, pool, w_ukv):
        w_uk, w_uv = w_ukv[..., :Dn], w_ukv[..., Dn:]
        qa = jnp.concatenate([jnp.einsum("hsd,rhd->hsr", q[..., :Dn], w_uk),
                              q[..., Dn:]], -1)
        width = pool.shape[-1]
        qa = jnp.pad(qa, ((0, 0), (0, 0), (0, width - qa.shape[-1])))
        out = paged_attention.paged_chunk_attention(
            qa.reshape(1, 1, H * chunk, width),
            pool[0, table.reshape(-1)].reshape(1, tiles * T, width), None,
            jnp.tile(qpos[:, :, 0], (1, H)), jnp.zeros((1,), jnp.int32), tiles,
            paged_attention.NO_WINDOW, tile_keys=T, dv=R, sm_scale=scale)
        return jnp.einsum("hsr,rhd->hsd", out.reshape(H, chunk, R), w_uv)

    forms = [("absorbed", absorbed), ("expanded", expanded)]
    own = paged_attention._CHUNK_Q_ROWS
    for rows in (q_rows or [own]) if kernel else ():
        def at_tile(*args, rows=rows):
            paged_attention._CHUNK_Q_ROWS = rows      # read while tracing
            try:
                return by_kernel(*args)
            finally:
                paged_attention._CHUNK_Q_ROWS = own
        forms.append((f"kernel@{rows}", jax.jit(at_tile)))
    out = {}
    for name, fn in forms:
        jax.block_until_ready(fn(q, pool, w_ukv))
        t = time.perf_counter()
        for _ in range(reps):
            y = fn(q, pool, w_ukv)
        jax.block_until_ready(y)
        out[name] = 1e3 * (time.perf_counter() - t) / reps
    return out


if __name__ == "__main__":
    sys.exit(main())
