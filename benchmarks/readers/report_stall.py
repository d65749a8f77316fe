"""What one ``train.report`` a step costs the loop: the wall time of a step in
the probe after a traced run's window (``report_probe_steps`` steps, a report
after each) less that of a step inside the window (a report every
``report_every`` steps). ms."""
from benchmarks.readers import train_token_rate


def read(ctx, params):
    probe = ctx.get("probe_reports") or []
    rate = train_token_rate.read(ctx, {})
    if len(probe) < 2 or not rate:
        return None
    per_step = (probe[-1] - probe[0]) / (len(probe) - 1)
    in_window = ctx["tokens_per_step"] / (rate * ctx["chips"])
    return 1e3 * (per_step - in_window)
