"""Client clock, from the instant a request was DUE to its first streamed
token: the mean over the window's requests. A failed or timed-out request
counts as the traffic's ``drain_limit_s``, the longest the run waits for a
request (``ttft_percentile`` counts it as missing). s."""


def read(ctx, params):
    recs = [r for r in ctx.get("records", []) if r.measured]
    if not recs:
        return None
    waits = [r.arrivals[0] - r.due if r.arrivals and r.error is None
             else ctx["drain_limit_s"] for r in recs]
    return sum(waits) / len(waits)
