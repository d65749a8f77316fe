"""The between-events estimator is exact on bursts; a fixed-window count is
off by up to a burst at each edge (what refused PR 23)."""
import math

import pytest

from benchmarks.harness.rates import (
    between_events_rate, fixed_window_rate, percentile, union_length)


def bursts(period, size, start, end):
    t, out = start, []
    while t <= end:
        out.append((t, float(size)))
        t += period
    return out


@pytest.mark.parametrize("offset", [0.0, 0.3, 1.1, 2.39])
def test_exact_on_burst_timelines_whatever_the_phase(offset):
    period, size = 2.4, 450  # PR 23's deployment: 450 tokens every 2.4 s
    events = bursts(period, size, offset - 10 * period, 200.0)
    true_rate = size / period
    got = between_events_rate(events, 50.0, 95.0)
    assert got == pytest.approx(true_rate, rel=1e-12)


def test_fixed_window_count_is_off_by_a_burst():
    period, size = 2.4, 450
    true_rate = size / period
    errs = []
    for k in range(24):
        events = bursts(period, size, 0.1 * k - 10 * period, 200.0)
        errs.append(fixed_window_rate(events, 50.0, 95.0) / true_rate - 1)
    assert max(errs) - min(errs) > 0.04  # whole bursts at the edges: over 4%


def test_rate_needs_two_instants_and_counts_after_the_first():
    assert between_events_rate([], 0, 10) is None
    assert between_events_rate([(5.0, 3.0)], 0, 10) is None
    assert between_events_rate([(1.0, 7.0), (3.0, 2.0), (5.0, 2.0), (11.0, 9.0)],
                               0, 10) == pytest.approx(4.0 / 4.0)


def test_percentile_counts_failures_as_missing():
    vals = [float(i) for i in range(1, 11)]
    assert percentile(vals, 0.9) == 9.0
    assert percentile(vals, 0.5) == 5.0
    assert percentile(vals[:9], 0.9, missing=1) == 9.0
    assert percentile(vals[:8], 0.9, missing=2) == math.inf
    assert math.isnan(percentile([], 0.9))


def test_union_length():
    assert union_length([(0, 2), (1, 3), (5, 6), (5.5, 5.7)]) == pytest.approx(4.0)
