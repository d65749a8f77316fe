"""Output tokens arriving at the client after the first arrival instant
inside the window, up to and including the last, over the time between those
two instants. tokens/s."""
from benchmarks.harness.rates import between_events_rate


def read(ctx, params):
    if "records" not in ctx:
        return None
    events = [(t, 1.0) for r in ctx["records"] for t in r.arrivals]
    return between_events_rate(events, ctx["t_open"], ctx["t_close"])
