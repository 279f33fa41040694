"""The arithmetic every metric shares: rates over a window, tails over
all requests timed from when each was due, busy time as a union of
intervals, and the quartile spread that sets a bound."""

from __future__ import annotations

import math
import statistics
from typing import Iterable, Sequence

import numpy as np


def rate(work: float, seconds: float) -> float:
    """All the work of a window over all of its time."""
    if seconds <= 0:
        raise ValueError(f"window of {seconds} s")
    return work / seconds


def latencies_from_due(due: Sequence[float], done: Sequence[float | None],
                       missing_at: float) -> np.ndarray:
    """Latency of every request, each from when it was due. A request
    that never completed (``None`` or NaN) counts as done at
    ``missing_at``, the moment the run stopped waiting for it, so it sits
    in the tail."""
    due = np.asarray(due, np.float64)
    done = np.array(done, np.float64)
    return np.where(np.isnan(done), missing_at, done) - due


def percentile(values: Iterable[float], q: float) -> float:
    """The q-th percentile (0-100) over all values, nearest-rank: the
    smallest value with at least q% of the values at or below it."""
    v = np.sort(np.asarray(list(values), np.float64))
    if v.size == 0:
        raise ValueError("percentile of no values")
    rank = max(math.ceil(q / 100.0 * v.size), 1)
    return float(v[rank - 1])


def union_length(intervals: Iterable[tuple[float, float]],
                 lo: float = -math.inf, hi: float = math.inf) -> float:
    """Total length covered by [start, end) intervals, clipped to
    [lo, hi); overlaps count once."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals: Iterable[tuple[float, float]], lo: float,
         hi: float) -> list[tuple[float, float]]:
    """The idle stretches of [lo, hi) that no interval covers."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def idle_pct(busy_s: float, window_s: float) -> float:
    return 100.0 * (1.0 - busy_s / window_s)


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile over the median, as
    ``statistics.quantiles(values, n=4)`` gives them."""
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2
