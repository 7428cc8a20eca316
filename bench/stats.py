"""Percentile and window arithmetic of the benchmark.

A request that failed, or never produced what a latency measures, counts
as missing: it enters a percentile as +inf, so it can only push the tail
up.  Percentiles are nearest-rank on the sorted sample (no interpolation),
so a reported tail is a latency some request really had.
"""
from __future__ import annotations

import math
from typing import Iterable, List, Optional, Sequence, Tuple

MISSING = math.inf


def percentile(values: Iterable[float], q: float) -> float:
    """Nearest-rank ``q``-th percentile (0 < q <= 100).  ``MISSING``
    entries sort last.  An empty sample has no percentile: ``nan``."""
    xs = sorted(values)
    if not xs:
        return math.nan
    if not 0 < q <= 100:
        raise ValueError(f"percentile q must be in (0, 100], got {q}")
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[rank - 1]


def beyond(n: int, q: float) -> int:
    """How many samples of ``n`` lie beyond the nearest-rank ``q``-th
    percentile; a reported tail wants ten or more."""
    return n - max(1, math.ceil(q / 100.0 * n))


def ttfts(due: Sequence[float], first: Sequence[Optional[float]]
          ) -> List[float]:
    """Time to first token of each request, from its due time; a request
    with no first token is ``MISSING``."""
    return [MISSING if f is None else f - d for d, f in zip(due, first)]


def token_gaps(times: Sequence[float]) -> List[float]:
    """Gaps between consecutive tokens of one request."""
    return [b - a for a, b in zip(times, times[1:])]


def union_length(intervals: Iterable[Tuple[float, float]]) -> float:
    """Total length covered by the union of [start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
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
