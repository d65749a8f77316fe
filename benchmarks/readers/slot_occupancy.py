"""``stats()["active"]`` polled through the window (outside the seconds
the profiler took); mean. slots."""
from benchmarks.readers.engine_step_wall import segments


def read(ctx, params):
    vals = [s["active"] for run in segments(ctx) for _t, s in run]
    return sum(vals) / len(vals) if vals else None
