"""1 - union of device-op intervals over the traced window. %."""


def read(ctx, params):
    trace = ctx.get("trace") or {}
    if not trace.get("window_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
