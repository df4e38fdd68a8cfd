"""Percentile and rate arithmetic of the window, kept with the benchmark
so that every PR computes them the same way."""
from __future__ import annotations

from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0-100), linear between closest ranks."""
    if not values:
        raise ValueError("no values")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def rate(done_times: Sequence[float], t0: float, seconds: float) -> float:
    """Completions inside [t0, t0 + seconds] per second of the window:
    over all of the window, whatever stalled inside it."""
    return sum(1 for t in done_times if t0 <= t <= t0 + seconds) / seconds
