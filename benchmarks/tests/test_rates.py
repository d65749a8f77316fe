"""The between-events estimator is exact on bursts; a fixed-window count is
off by up to a burst at each edge (what refused PR 23)."""
import math

import pytest

from benchmarks.harness.rates import (
    between_events_rate, fixed_window_rate, median_interval_rate, percentile,
    union_length)


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


def reports(gaps, tokens=163_840.0, start=100.0):
    """A train run's (instant, tokens since the report before) records."""
    out, t = [(start, 3 * 16_384.0)], start
    for gap in gaps:
        t += gap
        out.append((t, tokens))
    return out


@pytest.mark.parametrize("stalled", [0, 1, 2, 3, 4])
def test_one_stall_in_five_intervals_moves_the_mean_and_not_the_median(stalled):
    """``train_moe_8k`` as ISSUE 51 found it: ten steps of 0.867 s a report,
    five intervals in the 50 s window, one host stall of 1.3 s in one of them
    (the sixth report falls outside the window and is not read)."""
    gaps = [8.67] * 6
    gaps[stalled] += 1.3
    events = reports(gaps)
    true_rate = 163_840.0 / 8.67
    first_to_last = between_events_rate(events, 100.0, 150.0)
    assert 0.025 < 1 - first_to_last / true_rate < 0.03
    assert median_interval_rate(events, 100.0, 150.0) \
        == pytest.approx(true_rate, rel=1e-12)
    # no stall: the two agree
    steady = reports([8.67] * 6)
    assert median_interval_rate(steady, 100.0, 150.0) == pytest.approx(
        between_events_rate(steady, 100.0, 150.0), rel=1e-12)


def test_a_stall_in_most_intervals_moves_the_median_too():
    # what a median must not hide: a step that is slower all through
    gaps = [8.67 + 1.3] * 3 + [8.67] * 2
    assert median_interval_rate(reports(gaps), 100.0, 150.0) \
        == pytest.approx(163_840.0 / 9.97)


@pytest.mark.parametrize("gaps", [[], [8.67], [8.67, 9.97]])
def test_under_three_intervals_the_rate_is_first_to_last(gaps):
    """A traced run reads the reports before the profiler starts (two
    intervals), and a median of two has nothing to outvote a stall with."""
    events = reports(gaps)
    assert median_interval_rate(events, 100.0, 150.0) \
        == between_events_rate(events, 100.0, 150.0)
    if len(gaps) == 2:
        assert median_interval_rate(events, 100.0, 150.0) \
            == pytest.approx(2 * 163_840.0 / (8.67 + 9.97))
    # with three the median takes over
    three = reports([8.67, 9.97, 8.67])
    assert median_interval_rate(three, 100.0, 150.0) \
        == pytest.approx(163_840.0 / 8.67)


def test_an_even_count_of_intervals_takes_the_middle_two():
    four = reports([8.0, 8.5, 9.0, 12.0])
    assert median_interval_rate(four, 100.0, 150.0) == pytest.approx(
        (163_840.0 / 8.5 + 163_840.0 / 9.0) / 2)


def test_percentile_counts_failures_as_missing():
    vals = [float(i) for i in range(1, 11)]
    assert percentile(vals, 0.9) == 9.0
    assert percentile(vals, 0.5) == 5.0
    assert percentile(vals[:9], 0.9, missing=1) == 9.0
    assert percentile(vals[:8], 0.9, missing=2) == math.inf
    assert math.isnan(percentile([], 0.9))


def test_union_length():
    assert union_length([(0, 2), (1, 3), (5, 6), (5.5, 5.7)]) == pytest.approx(4.0)
