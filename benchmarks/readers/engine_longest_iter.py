"""The longest engine iteration that started inside the window and outside
the seconds the profiler took, from the flight recorder in
``LLMEngine.stats()``: the ring of the last 256 iterations as every poll and
the report after the window saw it, and ``slow_iters``. Instants are on
``time.time()``; the window's and the profiler's are moved there by the pair
of clocks the runner read at the window's opening. None, never 0.0, when no
iteration was recorded there (or the program has no recorder). s."""


def iterations(ctx):
    """{wall start: seconds} of every iteration any snapshot holds."""
    marks = ctx.get("marks") or {}
    snapshots = [s for _t, s in marks.get("polls", [])]
    snapshots.append((ctx.get("device_report") or {}).get("engine") or {})
    found = {}
    for stats in snapshots:
        ring = stats.get("ring") or {}
        cols = ring.get("columns") or []
        if "start" in cols:
            start = cols.index("start")
            phases = [i for i, c in enumerate(cols)
                      if c not in ("start", "active", "admitted", "retired")]
            for row in ring["rows"]:
                found[row[start]] = sum(row[i] for i in phases)
        for rec in stats.get("slow_iters") or []:
            found[rec["at"]] = rec["total_s"]
    return found


def read(ctx, params):
    marks = ctx.get("marks") or {}
    if "open_wall" not in marks or "close" not in marks:
        return None
    to_wall = marks["open_wall"] - marks["open"]
    lo, hi = marks["open_wall"], marks["close"] + to_wall
    a, b = marks.get("trace_call", (None, None))
    inside = []
    for start, seconds in iterations(ctx).items():
        if not lo <= start <= hi:
            continue
        if a is not None and a + to_wall - 0.6 <= start + seconds \
                and start <= b + to_wall + 0.6:
            continue
        inside.append(seconds)
    return max(inside) if inside else None
