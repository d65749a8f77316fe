"""Tokens of the steps reported after the first report inside the window,
up to the last, over the time between those two reports, over chips: all the
work and all the time of the window, so that a stall of the host inside it
shows. In a traced run the reports before the profiler starts are read (it
stalls the loop for seconds). ``both`` also gives the median of the report
intervals' rates (``rates.median_interval_rate``), which one stall in five
intervals cannot move: the runner prints it beside the metric, so that a
reader of a run's ``stderr`` can tell a stall from a slower step."""
from benchmarks.harness.rates import between_events_rate, median_interval_rate


def both(ctx):
    """(first to last, median of intervals), tokens/s/chip, or None."""
    if "reports" not in ctx:
        return None
    events = list(zip(ctx["reports"], (float(a) for a in ctx["report_tokens"])))
    until = ctx.get("rate_until") or ctx["window_close"]
    got = [f(events, ctx["window_open"], until)
           for f in (between_events_rate, median_interval_rate)]
    return None if None in got else tuple(r / ctx["chips"] for r in got)


def read(ctx, params):
    got = both(ctx)
    return None if got is None else got[0]
