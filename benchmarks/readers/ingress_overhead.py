"""Client latency (send to end of stream) minus the engine's own
``latency_s``, per request; median. ms."""
from benchmarks.harness.rates import median


def read(ctx, params):
    vals = [1e3 * ((r.finished - r.sent) - r.done["latency_s"])
            for r in ctx.get("records", [])
            if r.measured and r.error is None and r.done and r.sent is not None]
    return median(vals) if vals else None
