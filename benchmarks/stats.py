"""Arithmetic of the yardstick: percentiles, the training rate over whole
steps (and the steady rate of the median group beside it), token-level
throughput. Pure Python, no JAX, so `selfcheck` can hold
it to known answers on any machine."""

from __future__ import annotations

import statistics
from typing import List, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """Linear-interpolated percentile, q in [0, 100] (numpy's default rule)."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    if pos == lo:                       # also keeps an infinite tail out of it
        return xs[lo]
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def group_seconds(step_ends: Sequence[float], start: float, group: int) -> List[float]:
    """Elapsed seconds of each consecutive group of `group` whole steps.

    `step_ends` are the instants the host read each step's loss; `start` is
    the end of the step before the first. A group runs from the end of the
    step before it to the end of its last step; a trailing partial group is
    dropped. No window length enters."""
    edges = [start, *step_ends]
    n_groups = len(step_ends) // group
    return [
        edges[(g + 1) * group] - edges[g * group] for g in range(n_groups)
    ]


def whole_step_rate(step_ends: Sequence[float], start: float,
                    tokens_per_step: int, chips: int, min_steps: int) -> float:
    """tokens/s/chip over ALL whole steps and all of their own time: from
    `start`, the end of the step before the first, to the end of the last.
    A slow step counts in full; where the nominal window ends does not."""
    if len(step_ends) < min_steps:
        raise ValueError(
            f"{len(step_ends)} whole steps in the window; the rate needs {min_steps}")
    return tokens_per_step * len(step_ends) / chips / (step_ends[-1] - start)


def group_median_rate(step_ends: Sequence[float], start: float, group: int,
                      tokens_per_step: int, chips: int,
                      min_groups: int = 5) -> float:
    """tokens/s/chip of the steady state: tokens of one group over the MEDIAN
    group's seconds. A stall in fewer than half the groups does not move it,
    so it is a per-layer reading (the model step), never the end-to-end rate."""
    secs = group_seconds(step_ends, start, group)
    if len(secs) < min_groups:
        raise ValueError(
            f"{len(secs)} whole groups of {group} steps in the window; "
            f"the rate needs {min_groups}"
        )
    return tokens_per_step * group / chips / statistics.median(secs)


def token_rate(token_times: Sequence[float]) -> float:
    """Output tokens/s over the span from the first to the last token."""
    if len(token_times) < 2:
        raise ValueError("token rate needs two tokens")
    first, last = min(token_times), max(token_times)
    return (len(token_times) - 1) / (last - first)
