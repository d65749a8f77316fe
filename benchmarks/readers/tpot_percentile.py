"""Per request (last token arrival - first) / (tokens - 1); percentile over
measured requests, failures counted as missing. params {"q"}; ms."""
from benchmarks.harness.rates import percentile


def read(ctx, params):
    recs = [r for r in ctx.get("records", []) if r.measured and r.max_tokens > 1]
    if not recs:
        return None
    good = [1e3 * (r.arrivals[-1] - r.arrivals[0]) / (len(r.arrivals) - 1)
            for r in recs
            if r.error is None and len(r.arrivals) == r.max_tokens]
    return percentile(good, params["q"], missing=len(recs) - len(good))
