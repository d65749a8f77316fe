"""A memory-bound mechanism's share of its roofline: the least time the chip
could take to move the bytes its calls NEED (a function of the configuration's
family, ``families/<name>.py``) over the exclusive device time of the
operations that do the work, matched by HLO text. params {"ops": regex,
"bytes": name of the family's function, "ops_per_call": how many matched
operations one call of the mechanism runs, "touched": optional pair of
engine counters [sum, ticks] whose rise gives the mean of a per-tick count
the bytes depend on}; %. None where nothing matches or a counter is missing.

``bytes`` functions are called as ``fn(cfg, calls, rows, mean)``: the cell's
configuration, the calls of the mechanism in the trace, the slots the decode
program runs over, and the counters' mean (None without ``touched``)."""
from benchmarks.harness import roofline
from benchmarks.harness.manifest import family_of
from benchmarks.readers.engine_counters import delta
from benchmarks.readers.ops_share import ops_seconds_and_count


def read(ctx, params):
    trace = ctx.get("trace") or {}
    seconds, count = ops_seconds_and_count(trace, params["ops"])
    if seconds <= 0 or not count:
        return None
    mean = None
    if params.get("touched"):
        total, ticks = (delta(ctx, key) for key in params["touched"])
        if total is None or not ticks:
            return None
        mean = total / ticks
    cfg = ctx["cfg"]
    need = getattr(family_of(cfg), params["bytes"])(
        cfg, count / params.get("ops_per_call", 1),
        cfg["deployment"]["num_slots"], mean)
    peak = roofline.peaks_for(ctx["device_report"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / peak) / seconds
