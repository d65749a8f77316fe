"""A LEVEL of ``LLMEngine.stats()`` (not a counter's rise) polled through the
window, outside the seconds the profiler took (``engine_step_wall``'s
segments); mean. params {"key", "over": optional key the level is a share of,
"scale"}. None where the program has no such field (a parent commit that
lacks it)."""
from benchmarks.readers.engine_counters import lookup
from benchmarks.readers.engine_step_wall import segments


def read(ctx, params):
    vals = []
    for run in segments(ctx):
        for _t, stats in run:
            level = lookup(stats, params["key"])
            whole = lookup(stats, params["over"]) if "over" in params else 1
            if level is None or not whole:
                return None
            vals.append(level / whole)
    return params.get("scale", 1.0) * sum(vals) / len(vals) if vals else None
