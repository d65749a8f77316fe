"""Differences of ``LLMEngine.stats()`` counters between polls inside the
window and outside the seconds the profiler took (``engine_step_wall``'s
segments). params {"plus": [keys], "minus": [keys], "over": key, "scale"}:
scale x (sum of plus - sum of minus) / over, or without "over" the difference
itself. A key may be dotted (``phase_ns.device_get``). None where the
program has no such counter (a parent commit that lacks it)."""
from benchmarks.readers.engine_step_wall import segments


def lookup(stats, key):
    for part in key.split("."):
        if not isinstance(stats, dict) or part not in stats:
            return None
        stats = stats[part]
    return stats


def delta(ctx, key):
    """The counter's rise over the segments, or None if it is not there."""
    runs = segments(ctx)
    if not runs:
        return None
    total = 0
    for run in runs:
        first, last = lookup(run[0][1], key), lookup(run[-1][1], key)
        if first is None or last is None:
            return None
        total += last - first
    return total


def read(ctx, params):
    sums = []
    for group in ("plus", "minus"):
        deltas = [delta(ctx, key) for key in params.get(group, [])]
        if any(d is None for d in deltas):
            return None
        sums.append(sum(deltas))
    value = sums[0] - sums[1]
    if "over" in params:
        over = delta(ctx, params["over"])
        if not over:
            return None
        value /= over
    return params.get("scale", 1.0) * value
