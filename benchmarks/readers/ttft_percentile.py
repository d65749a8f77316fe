"""Client clock, from the instant a request was DUE to its first streamed
token; failed or timed-out requests count as missing. params {"q"}; s."""
from benchmarks.harness.rates import percentile


def read(ctx, params):
    recs = [r for r in ctx.get("records", []) if r.measured]
    if not recs:
        return None
    good = [r.arrivals[0] - r.due for r in recs if r.arrivals and r.error is None]
    return percentile(good, params["q"], missing=len(recs) - len(good))
