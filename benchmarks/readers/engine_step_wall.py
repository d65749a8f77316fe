"""Window time over the ``decode_steps`` delta of ``LLMEngine.stats()``,
between polls inside the window; the seconds in which the profiler was being
started, run and stopped are left out (they stall the loop). ms per step."""


def segments(ctx):
    """Runs of consecutive polls inside the window and outside the trace."""
    marks = ctx.get("marks") or {}
    lo, hi = marks.get("open", 0), marks.get("close", 0)
    a, b = marks.get("trace_call", (None, None))
    runs, cur = [], []
    for t, s in marks.get("polls", []):
        if not lo <= t <= hi:
            continue
        if a is not None and a - 0.6 <= t <= b + 0.6:
            if cur:
                runs.append(cur)
            cur = []
            continue
        cur.append((t, s))
    if cur:
        runs.append(cur)
    return [r for r in runs if len(r) >= 2]


def read(ctx, params):
    dt = steps = 0.0
    for run in segments(ctx):
        dt += run[-1][0] - run[0][0]
        steps += run[-1][1]["decode_steps"] - run[0][1]["decode_steps"]
    return 1e3 * dt / steps if steps > 0 else None
