"""Metric arithmetic that later PRs may not change: rates read between
events, percentiles with failures counted as missing."""

from __future__ import annotations

import math
import statistics
from typing import Iterable, List, Optional, Sequence, Tuple


def between_events_rate(events: Iterable[Tuple[float, float]],
                        t0: float, t1: float) -> Optional[float]:
    """``events`` are (instant, amount). Of those with t0 <= instant <= t1:
    the amounts that arrived AFTER the first instant, up to and including
    the last, over the time between those two instants. A count over a fixed
    wall window is off by up to one burst at each edge; this is not, because
    both ends of the interval are events. None with fewer than two instants."""
    inside = sorted((t, a) for t, a in events if t0 <= t <= t1)
    if not inside:
        return None
    first, last = inside[0][0], inside[-1][0]
    if last <= first:
        return None
    amount = sum(a for t, a in inside if t > first)
    return amount / (last - first)


def median_interval_rate(events: Iterable[Tuple[float, float]],
                         t0: float, t1: float,
                         least: int = 3) -> Optional[float]:
    """The median, over every two consecutive instants with t0 <= instant
    <= t1, of the later one's amount over the time between them. One stall
    of the host inside a window of five intervals moves the first-to-last
    rate by the stall's share of the window and this not at all; a stall in
    EVERY interval moves both alike, and one in every other interval only
    the first-to-last rate, which therefore stays printed beside it. With
    fewer than ``least`` intervals a median has nothing to outvote: then
    ``between_events_rate``."""
    inside = sorted((t, a) for t, a in events if t0 <= t <= t1)
    rates = [a / (t - before) for (before, _), (t, a)
             in zip(inside, inside[1:]) if t > before]
    if len(rates) < least:
        return between_events_rate(inside, t0, t1)
    return statistics.median(rates)


def fixed_window_rate(events: Iterable[Tuple[float, float]],
                      t0: float, t1: float) -> float:
    """What PR 23 did, kept for the test that shows why it was replaced."""
    return sum(a for t, a in events if t0 <= t <= t1) / (t1 - t0)


def percentile(values: Sequence[float], q: float, missing: int = 0) -> float:
    """Nearest-rank percentile of ``values`` plus ``missing`` samples that
    count as infinitely bad (failed or timed-out requests)."""
    n = len(values) + missing
    if n == 0:
        return math.nan
    rank = max(1, math.ceil(q * n))
    if rank > len(values):
        return math.inf
    return sorted(values)[rank - 1]


def median(values: Sequence[float]) -> float:
    return percentile(values, 0.5)


def union_length(intervals: List[Tuple[float, float]]) -> float:
    total, end = 0.0, -math.inf
    for a, b in sorted(intervals):
        if b <= end:
            continue
        total += b - max(a, end)
        end = b
    return total
