"""The readings behind `token_tolerance` of `smallthinker-21b-a3b`
(`benchmarks/configs/smallthinker-21b-a3b.json`), taken on the chip at the
published widths, in one process: `python3 -m scripts.smallthinker_tolerance
[--seeds 3000000001,3000000002] [--parts wrong,float8]`.

Every reading is the number the benchmark itself would print:
`benchmarks.runners.serve.BenchReplica.bench_check_tokens`, the harness's own
function, called on a stand-in that holds what it reads of a replica (the
parameter tree and `generate`), with the cell's own engine options, prompt
length and count of new tokens. For each seed:

- `sound`: the engine's greedy tokens (chunked paged prefill, then paged
  decode) held to the plain float32 reference;
- `wrong`: the same engine held to four WRONG references, which a sound
  program must fail: top-(k-1) routing, no window, a window one block too
  wide, rotary positions on the layers that have none;
- `float8`: the engine serving the weights rounded to float8's mantissa
  (e4m3: three bits; the nearest precision below the bfloat16 the
  configuration states), held to the reference with the weights as they are.

On the CPU (`--rehearse`) the same at the configuration's tiny preset:
control flow only."""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time


class _KeepLogits:
    """An architecture module whose reference keeps the logits it last gave,
    for `--curve`: one forward pass serves every prefix of a check."""

    def __init__(self, module):
        self._module, self.last = module, None

    def __getattr__(self, name):
        return getattr(self._module, name)

    def make_logits(self, dims):
        logits = self._module.make_logits(dims)

        def keep(params, tokens):
            self.last = logits(params, tokens)
            return self.last

        return keep


class _Held:
    """What `bench_check_tokens` reads of a replica."""

    def __init__(self, params, generate):
        self._params, self._phases, self._generate, self.tokens = params, {}, generate, None

    def generate(self, prompt, new_tokens):
        self.tokens = self._generate(prompt, new_tokens)
        return {"tokens": self.tokens}


def main(argv=None) -> int:
    return readings(
        "smallthinker-21b-a3b",
        lambda m, opts: {
            "top_k_minus_one": {"top_k": m["top_k"] - 1},
            "window_off": {"window_layout": [0] * m["n_layers"]},
            "window_one_block_wide": {"window": m["window"] + opts.block_size},
            "rope_on_nope_layers": {"rope_layout": [1] * m["n_layers"]},
        },
        lambda stats: {"window_blocks_released": stats["window_blocks_released"]},
        argv, __doc__)


def readings(config_name, wrongs_of, row_of, argv, doc, faults=None) -> int:
    """The readings of one configuration (`benchmarks/configs/<config_name>.json`):
    `wrongs_of(dims, engine options)` names the wrong references as changes to
    the dims, `row_of(engine.stats())` adds what the sound engine counted.
    `faults`: {name: context manager under which the PROGRAM is wrong}; the
    engine built and run inside it is held to the right reference."""
    ap = argparse.ArgumentParser(description=doc.split("\n\n")[0])
    ap.add_argument("--seeds", default="3000000001")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--parts", default="wrong,float8",
                    help="what to read beside the sound engine's own token error: "
                         "wrong (every wrong reference) or their names, float8, "
                         "faults (every faulty program) or their names")
    ap.add_argument("--init-gains", default="",
                    help="name=gain,...: entries of the preset's init_gains to "
                         "replace (how a preset's gains were chosen; shapes unmoved)")
    ap.add_argument("--check", default="",
                    help="prompt_len:new_tokens in place of the configuration's "
                         "token_check (how a cell's check was sized)")
    ap.add_argument("--curve", default="",
                    help="n1,n2,...: beside each reading, what the check would read "
                         "over the first n new tokens alone (how a check's length "
                         "was chosen: bench_check_tokens' arithmetic on its own logits)")
    ap.add_argument("--sizes", default="",
                    help="key=int,...: published keys of the configuration to replace. "
                         "On the CPU at a quarter of the widths (all layers, the whole "
                         "vocabulary) the readings come within a fifth of the chip's, "
                         "two minutes a seed: settle gains there (PERF.md 6, PR 40)")
    a = ap.parse_args(argv)
    parts = set(a.parts.split(","))
    import jax
    import numpy as np

    from benchmarks import harness
    from benchmarks.runners.serve import BenchReplica
    from ray_tpu.models import gpt
    from ray_tpu.serve.engine import EngineOptions, InferenceEngine

    config = harness.load_json(harness.ROOT, f"benchmarks/configs/{config_name}.json")
    config.update((k, int(v)) for k, v in (kv.split("=") for kv in a.sizes.split(",") if kv))
    arch = harness.arch(config["arch"])
    if a.curve:     # a script's process reads one configuration: keep its logits
        arch = _KeepLogits(arch)
        harness.arch = lambda name: arch
    m = arch.dims(config, a.rehearse)
    part = config["rehearsal"]["requests"] if a.rehearse else config["runners"]["requests"]
    opts = EngineOptions(**part["engine_options"])
    n_prompt, n_new = part["token_check"]["prompt_len"], part["token_check"]["new_tokens"]
    if a.check:
        n_prompt, n_new = (int(v) for v in a.check.split(":"))
    name, overrides = arch.program(config, m)
    cfg = gpt.CONFIGS[name](**overrides)
    if a.init_gains:
        gains = {**dict(cfg.init_gains),
                 **{k: float(v) for k, v in (kv.split("=") for kv in a.init_gains.split(","))}}
        cfg = dataclasses.replace(cfg, init_gains=tuple(gains.items()))
    init = jax.jit(lambda k: gpt.init_params(k, cfg))
    dev = jax.devices()[0]
    wrongs = wrongs_of(m, opts)

    def check(held, seed, dims=m):
        err, agree = BenchReplica.bench_check_tokens(
            held, config["arch"], dims, seed, n_prompt, n_new)
        out = {"token_err": err, "argmax_agree": agree}
        if a.curve:
            want, got = arch.last[n_prompt - 1:], np.asarray(held.tokens)
            short = want.max(-1) - want[np.arange(n_new), got]
            out["curve"] = {n: float(short[:n].max() / np.abs(want[:n]).max())
                            for n in (int(v) for v in a.curve.split(","))}
        return out

    rows = []
    for seed in (int(s) for s in a.seeds.split(",")):
        t0 = time.perf_counter()
        params = init(jax.random.PRNGKey(harness.key_seed(seed)))
        eng = InferenceEngine(cfg, params=params, options=opts)
        eng.start()
        held = _Held(params, eng.generate)
        row = {"seed": seed, "sound": check(held, seed)}
        row["distinct_tokens"] = len(set(held.tokens))
        if "growth" in parts:
            row["growth"] = growth(arch.make_logits(m), params, _prompt(seed, m, n_prompt))
        row.update(row_of(eng.stats()))
        for wname, change in wrongs.items():
            if wname in parts or "wrong" in parts:
                row[wname] = check(held, seed, {**m, **change})
        eng.shutdown()
        del eng, held
        for fname, fault in (faults or {}).items():
            if fname in parts or "faults" in parts:
                with fault():
                    eng = InferenceEngine(cfg, params=params, options=opts)
                    eng.start()
                    got = eng.generate(_prompt(seed, m, n_prompt), n_new)
                    eng.shutdown()
                    del eng
                row[fname] = check(_Held(params, lambda prompt, n, got=got: got), seed)
        if "float8" in parts:
            eng = InferenceEngine(cfg, params=degrade(params), options=opts)
            eng.start()
            params = None
            low = eng.generate(_prompt(seed, m, n_prompt), n_new)
            eng.shutdown()
            del eng      # and with it the rounded tree and its pool
            held = _Held(init(jax.random.PRNGKey(harness.key_seed(seed))),
                         lambda prompt, n: low)
            row["float8_weights"] = check(held, seed)
            del held
        params = None       # one tree at a time fits the chip
        row["seconds"] = time.perf_counter() - t0
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({"device": {"platform": dev.platform, "kind": dev.device_kind},
                      "prompt_len": n_prompt, "new_tokens": n_new,
                      "token_tolerance": config["runners"]["requests"]["token_tolerance"],
                      "rows": rows}))
    return 0


def _prompt(seed, m, n_prompt):
    """The check's own prompt (`bench_check_tokens` draws it so)."""
    import numpy as np

    return np.random.default_rng([seed, 1]).integers(1, m["vocab_size"], n_prompt).tolist()


def growth(logits, params, tokens, eps=1e-3):
    """How many times over a perturbation of the embedding, `eps` of its size,
    has grown when it reaches the float32 reference's logits: the rounding
    of a sound bfloat16 engine grows alike, and past some 50 it reads as
    float8 weights do (PERF.md 6, PR 40: 64-83 under gains that failed, 8
    under those that hold). The perturbed rows stay float32."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    e = params["tok_embed"].astype(jnp.float32)
    moved = e + jax.random.normal(jax.random.PRNGKey(0), e.shape) * eps * e.std()
    a, b = logits(params, tokens), logits({**params, "tok_embed": moved}, tokens)
    return float(np.sqrt(((b - a) ** 2).mean() / (a ** 2).mean()) / eps)


def degrade(params):
    """The tree with every matrix rounded to float8's mantissa (e4m3: 1 + 3
    bits; the device widens a real float8 convert away), in place."""
    import jax
    import jax.numpy as jnp

    def q(w):
        if w.ndim < 2:
            return w
        frac, exp = jnp.frexp(w.astype(jnp.float32))
        return jnp.ldexp(jnp.round(frac * 16.0) / 16.0, exp).astype(w.dtype)

    return jax.jit(lambda t: {k: q(v) for k, v in t.items()}, donate_argnums=0)(params)


if __name__ == "__main__":
    sys.exit(main())
