"""A percentile of the engine's queue wait (admission instant minus
submission) from the difference of ``stats()["queue_wait_hist"]`` between
polls inside the window; within the bucket that holds the rank the value is
interpolated on the log scale the edges are spaced on. params {"q": a
fraction}; ms."""
from benchmarks.readers.engine_counters import lookup
from benchmarks.readers.engine_step_wall import segments


def read(ctx, params):
    counts, edges = None, None
    for run in segments(ctx):
        first = lookup(run[0][1], "queue_wait_hist.counts")
        last = lookup(run[-1][1], "queue_wait_hist.counts")
        if first is None or last is None:
            return None
        edges = lookup(run[-1][1], "queue_wait_hist.edges_s")
        rise = [b - a for a, b in zip(first, last)]
        counts = rise if counts is None else [c + r for c, r in zip(counts, rise)]
    total = sum(counts or [])
    if not total:
        return None
    rank = params["q"] * total
    seen = 0.0
    for i, c in enumerate(counts):
        if c and seen + c >= rank:
            if i == 0:
                return 1e3 * edges[0]
            if i == len(edges):
                return 1e3 * edges[-1]
            lo, hi = edges[i - 1], edges[i]
            return 1e3 * lo * (hi / lo) ** ((rank - seen) / c)
        seen += c
    return 1e3 * edges[-1]
