"""The one general traffic generator. A traffic mix is a JSON file of
parameters under `benchmarks/traffic/`; this module turns it, a seed and a
window length into the work of one run.

The arrival schedule and the lengths belong to the mix: they are drawn once
from the file's own `pool_seed`, so every `--seed` offers the same requests
at the same instants, and the seed gives the token contents (and, in the
runner, the weights). A seed that reordered the arrivals would change which
requests queue behind which, and with some tens of requests in a window the
median time to first token would follow the seed, not the program."""

from __future__ import annotations

from dataclasses import dataclass
from typing import List

import numpy as np


@dataclass
class Request:
    due_s: float          # offset from the start of the window
    prompt: List[int]
    max_new_tokens: int


def _lengths(spec: dict, n: int, rng) -> np.ndarray:
    """n integer lengths from one distribution, or from a weighted mixture
    (`{"mixture": [{"weight": w, ...dist...}, ...]}`) in fixed proportions."""
    if "mixture" in spec:
        parts, left = [], n
        comps = spec["mixture"]
        total = sum(c["weight"] for c in comps)
        for i, c in enumerate(comps):
            k = left if i == len(comps) - 1 else round(n * c["weight"] / total)
            parts.append(_lengths(c, k, rng))
            left -= k
        return np.concatenate(parts)
    dist = spec["dist"]
    if dist == "fixed":
        x = np.full(n, spec["value"], float)
    elif dist == "lognormal":
        x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    elif dist == "uniform":
        x = rng.uniform(spec["min"], spec["max"], n)
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return np.clip(np.rint(x), spec.get("min", 1), spec.get("max", 1 << 30)).astype(int)


def _gaps(spec: dict, n: int, rng) -> np.ndarray:
    """n inter-arrival gaps with mean 1/rate: `poisson` (exponential), or
    `gamma` with coefficient of variation `cv` (bursts for cv > 1)."""
    mean = 1.0 / spec["rate_rps"]
    process = spec.get("process", "poisson")
    if process == "poisson":
        return rng.exponential(mean, n)
    if process == "gamma":
        shape = 1.0 / spec["cv"] ** 2
        return rng.gamma(shape, mean / shape, n)
    raise ValueError(f"unknown arrival process {process!r}")


def requests(mix: dict, seed: int, seconds: float, vocab: int) -> List[Request]:
    """The requests due inside a window of `seconds`, in due order."""
    n = max(1, round(mix["arrivals"]["rate_rps"] * seconds))
    pool = np.random.default_rng(mix["pool_seed"])      # the same for every seed
    gaps = _gaps(mix["arrivals"], n, pool)
    gaps *= seconds / gaps.sum() * n / (n + 0.5)        # the last one is due inside
    prompts = _lengths(mix["prompt_len"], n, pool)
    outputs = _lengths(mix["output_len"], n, pool)
    rng = np.random.default_rng(seed)                   # token contents
    outputs = np.minimum(outputs, mix["max_total"] - prompts)
    due = np.cumsum(gaps)

    shared = mix.get("sharing") or {}
    prefixes = []
    if shared.get("prefixes"):
        lens = _lengths(shared["prefix_len"], shared["prefixes"], pool)
        prefixes = [rng.integers(1, vocab, int(k)).tolist() for k in lens]
        ranks = np.arange(1, len(prefixes) + 1, dtype=float) ** -shared.get("zipf", 1.0)
        picks = rng.choice(len(prefixes), n, p=ranks / ranks.sum())
    out = []
    for i in range(n):
        body = rng.integers(1, vocab, int(prompts[i])).tolist()
        if prefixes:
            head = prefixes[picks[i]]
            body = (head + body)[: max(len(head) + 1, int(prompts[i]))]
        out.append(Request(float(due[i]), body, int(outputs[i])))
    return out


def _pow2(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


def warm_plan(mix: dict, seconds: float, block_size: int, max_num_seqs: int,
              chunk: int) -> List[List[tuple]]:
    """What warm-up sends before the window, as real requests through the
    engine's public entry: waves of (prompt length, new tokens), each wave
    submitted together and drained before the next.

    It assumes of the engine only what its options say in public: KV in
    blocks of `block_size`, at most `max_num_seqs` lanes, prompts prefilled
    `chunk` tokens a step, one new admission a step, and programs keyed by
    power-of-two buckets of lanes, of a sequence's blocks and of a chunk's
    tokens. A program it misses shows as `compiles_in_window` and fails
    `correct`.

    Decode: for every bucket of blocks the mix can reach, one request that
    pins the width while one-token prompts are admitted one a step beside
    it, so the lanes pass through every bucket up to the top one.
    Prefill: the window's own prompt lengths (the schedule is the mix's, the
    same for every seed), the shortest prompt for each (chunk bucket, blocks
    bucket) pair they use, one new token each."""
    n = max(1, round(mix["arrivals"]["rate_rps"] * seconds))
    pool = np.random.default_rng(mix["pool_seed"])
    _gaps(mix["arrivals"], n, pool)
    prompts = _lengths(mix["prompt_len"], n, pool)
    outputs = np.minimum(_lengths(mix["output_len"], n, pool), mix["max_total"] - prompts)
    blocks = lambda tokens: -(-int(tokens) // block_size)
    w_lo = _pow2(blocks(prompts.min() + 2))
    w_hi = _pow2(blocks((prompts + outputs).max()))
    top = max_num_seqs // 2 + 1                    # first count in the top bucket
    waves, w = [], w_lo
    while w <= w_hi:
        room = w * block_size                      # most tokens at this width
        long_len = max(1, room // 2)               # just over the bucket below
        # the j-th short one is admitted j steps in and gets just enough
        # tokens for all to end together, two steps after the top is reached
        waves.append([(long_len, min(top + 7, room - long_len - 1))]
                     + [(1, min(top - j + 2, room - 2)) for j in range(1, top)])
        w *= 2
    need, picked = set(), []
    for length in sorted(set(int(x) for x in prompts)):
        sizes = [chunk] * (length // chunk) + ([length % chunk] if length % chunk else [])
        progs = {(_pow2(c), _pow2(blocks(length + 1))) for c in sizes}
        if not progs <= need:
            need |= progs
            picked.append((length, 1))
    return waves + [picked]


class TokenBatches:
    """Seeded training batches, shaped as a `StreamingIngest` source: one
    epoch of `epoch_batches` distinct [batch, seq+1] int32 arrays, the same
    every epoch."""

    def __init__(self, seed: int, batch: int, seq: int, vocab: int, epoch_batches: int):
        rng = np.random.default_rng(seed)
        self._batches = [
            rng.integers(0, vocab, (batch, seq + 1), dtype=np.int32)
            for _ in range(epoch_batches)
        ]

    def iter_batches(self, batch_size=None, batch_format="numpy", drop_last=True):
        for tokens in self._batches:
            yield {"tokens": tokens}
