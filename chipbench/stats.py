"""Arithmetic of the end-to-end numbers, kept with the benchmark."""

from __future__ import annotations

import math
import statistics
from typing import Iterable, List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank percentile (q in (0, 100]) over all values."""
    if not values:
        return None
    s = sorted(values)
    k = max(1, math.ceil(q / 100.0 * len(s)))
    return s[k - 1]


def rate(done: Iterable[tuple], t0: float) -> Optional[float]:
    """Work per second from the window's start to its last return.

    `done` holds ``(t_done, amount)`` of every request the window
    returned, the ones still in flight at its close included, so a stall
    anywhere in the window counts.
    """
    done = list(done)
    if not done:
        return None
    t_last = max(t for t, _ in done)
    total = sum(a for _, a in done)
    if t_last <= t0 or total <= 0:
        return None
    return total / (t_last - t0)


def spread(values: Sequence[float]) -> float:
    """Interquartile distance as a share of the median (Python's
    ``statistics.quantiles(values, n=4)`` quartiles)."""
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2


def union_length(intervals: Iterable[tuple]) -> float:
    """Total length covered by (start, end) intervals."""
    total = 0.0
    cur_s = cur_e = None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals: Iterable[tuple], lo: float, hi: float) -> List[tuple]:
    """The (start, end) stretches of [lo, hi] that no interval covers."""
    out = []
    t = lo
    for s, e in sorted(intervals):
        if e <= t:
            continue
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]
