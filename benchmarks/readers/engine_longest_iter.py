"""The longest engine iteration that started inside the window and outside
the seconds the profiler took, from the flight recorder in
``LLMEngine.stats()``: the ring of the last 256 iterations as every poll and
the report after the window saw it, and ``slow_iters``. Instants are on
``time.time()``; the window's and the profiler's are moved there by the pair
of clocks the runner read at the window's opening. None, never 0.0, when no
iteration was recorded there (or the program has no recorder). s."""


def _snapshots(ctx):
    """``stats()`` as every poll and the report after the window saw it."""
    polls = (ctx.get("marks") or {}).get("polls", [])
    return [s for _t, s in polls] + [
        (ctx.get("device_report") or {}).get("engine") or {}]


def ring_rows(ctx):
    """{wall start: {column: value}} of every ring row any snapshot holds."""
    found = {}
    for stats in _snapshots(ctx):
        ring = stats.get("ring") or {}
        cols = ring.get("columns") or []
        if "start" in cols:
            for row in ring["rows"]:
                named = dict(zip(cols, row))
                found[named["start"]] = named
    return found


def row_seconds(row):
    """An iteration's length: the sum of its phases."""
    return sum(v for c, v in row.items()
               if c not in ("start", "active", "admitted", "retired"))


def iterations(ctx):
    """{wall start: seconds} of every iteration any snapshot holds."""
    found = {start: row_seconds(row) for start, row in ring_rows(ctx).items()}
    for stats in _snapshots(ctx):
        for rec in stats.get("slow_iters") or []:
            found[rec["at"]] = rec["total_s"]
    return found


def in_window(ctx, seconds_by_start):
    """The starts of ``{wall start: seconds}`` that lie inside the window and
    whose iteration does not touch the profiler's call (0.6 s either side)."""
    marks = ctx.get("marks") or {}
    if "open_wall" not in marks or "close" not in marks:
        return []
    to_wall = marks["open_wall"] - marks["open"]
    lo, hi = marks["open_wall"], marks["close"] + to_wall
    a, b = marks.get("trace_call", (None, None))
    inside = []
    for start, seconds in seconds_by_start.items():
        if not lo <= start <= hi:
            continue
        if a is not None and a + to_wall - 0.6 <= start + seconds \
                and start <= b + to_wall + 0.6:
            continue
        inside.append(start)
    return inside


def read(ctx, params):
    found = iterations(ctx)
    inside = [found[start] for start in in_window(ctx, found)]
    return max(inside) if inside else None
