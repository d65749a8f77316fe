"""Time between two of the wall instants a streamed request's done record
carries in ``"hops"`` (proxy, router, replica, engine on the way in; first
token and done record on the way back); percentile over the window's good
requests. params {"from", "to", "q": a fraction}; ms. None where no record has them."""
from benchmarks.harness.rates import percentile


def read(ctx, params):
    vals = []
    for r in ctx.get("records", []):
        hops = (r.done or {}).get("hops") if r.measured and r.error is None else None
        if hops and hops.get(params["from"]) and hops.get(params["to"]):
            vals.append(1e3 * (hops[params["to"]] - hops[params["from"]]))
    return percentile(vals, params["q"]) if vals else None
