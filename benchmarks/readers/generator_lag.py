"""How late the load generator ran: send instant minus due instant. ms."""
from benchmarks.harness.rates import percentile


def read(ctx, params):
    vals = [1e3 * (r.sent - r.due) for r in ctx.get("records", [])
            if r.measured and r.sent is not None]
    return percentile(vals, params["q"]) if vals else None
