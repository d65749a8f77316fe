"""Tokens of the steps reported after the first report inside the window,
up to the last, over the time between those two reports, over chips. In a
traced run the reports before the profiler starts are read (it stalls the
loop for seconds)."""
from benchmarks.harness.rates import between_events_rate


def read(ctx, params):
    if "reports" not in ctx:
        return None
    events = list(zip(ctx["reports"], (float(a) for a in ctx["report_tokens"])))
    until = ctx.get("rate_until") or ctx["window_close"]
    rate = between_events_rate(events, ctx["window_open"], until)
    return None if rate is None else rate / ctx["chips"]
