"""The longest the process's stall watch (``ray_tpu.profiling.StallWatch``)
ran LATE in any one second of the window: its thread asks to wake every 100
ms and notes by how much it overslept, the longest of each wall second kept
for ten minutes (``late_ring``, in ``device_report.host.watch``). A process
that is stopped, throttled or whose interpreter is held wakes nobody on
time, so this is the length of the longest such pause; scheduler jitter, a
millisecond or less, in a quiet run. Serving: the whole seconds inside the
window that do not touch the profiler's call (0.6 s either side, as
``engine_longest_iter``). Training: those inside the window and before
``rate_until``. None, never 0.0, where the program keeps no such ring. ms."""
from benchmarks.readers import train_step_ring


def window(ctx):
    """(from, to, (profiler from, to) or None) on the wall clock, or None."""
    marks = ctx.get("marks")
    if marks is None:
        span = train_step_ring.window(ctx)
        return span and (*span, None)
    if "open_wall" not in marks or "close" not in marks:
        return None
    to_wall = marks["open_wall"] - marks["open"]
    call = marks.get("trace_call")
    if call is not None:
        call = (call[0] + to_wall - 0.6, call[1] + to_wall + 0.6)
    return marks["open_wall"], marks["close"] + to_wall, call


def read(ctx, params):
    host = (ctx.get("device_report") or {}).get("host") or {}
    ring = (host.get("watch") or {}).get("late_ring")
    span = window(ctx)
    if not ring or span is None:
        return None
    lo, hi, call = span
    inside = [ns for second, ns in ring
              if lo <= second and second + 1 <= hi
              and not (call and call[0] <= second + 1 and second <= call[1])]
    return max(inside) / 1e6 if inside else None
