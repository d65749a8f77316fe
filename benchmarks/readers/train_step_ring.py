"""A statistic over the train loop's own ring of steps
(``ray_tpu.profiling.StepRing``, in the worker's final report under
``device_report.host.loops.train``): the steps that began inside the window
and ended before it closed, in a traced run before the profiler started
(``rate_until``: tracing stalls the loop for seconds). Params: ``column``
(``interval``, take to take; ``dispatch``, inside the compiled step's call;
any of the ring's), ``stat`` (``p50``, ``max``, ``mean``, or
``over_median_share``: (sum - n x median) / sum, what stalls and slow steps
took from the rate, in %), ``scale``. None, never 0.0, where the program keeps
no ring or none of its steps lies there."""


def window(ctx):
    """(from, to) on the wall clock: the window, cut at ``rate_until``; None
    where the run has no train window."""
    if ctx.get("window_open") is None:
        return None
    return ctx["window_open"], min(
        x for x in (ctx.get("rate_until"), ctx["window_close"]) if x is not None)


def steps(ctx):
    """[{column: value}] of the ring's steps inside the window."""
    host = (ctx.get("device_report") or {}).get("host") or {}
    ring = ((host.get("loops") or {}).get("train") or {}).get("ring") or {}
    cols = ring.get("columns") or []
    span = window(ctx)
    if "start" not in cols or span is None:
        return []
    lo, hi = span
    rows = [dict(zip(cols, row)) for row in ring["rows"]]
    return [r for r in rows if lo <= r["start"] and r["start"] + r["interval"] <= hi]


def read(ctx, params):
    values = sorted(r[params["column"]] for r in steps(ctx))
    if not values:
        return None
    n, total = len(values), sum(values)
    median = values[n // 2] if n % 2 else 0.5 * (values[n // 2 - 1] + values[n // 2])
    stat = params["stat"]
    if stat == "over_median_share":
        return 100.0 * (total - n * median) / total if total else None
    value = {"p50": median, "max": values[-1], "mean": total / n}[stat]
    return value * params.get("scale", 1.0)
