"""``bytes_roofline`` for a mechanism whose bytes follow the tokens in the
cache DURING the profile: the least time the chip could take to move the
bytes its calls NEED over the exclusive device time of the operations that do
the work, where the bytes a call needs are a function of the tokens in the
cache of the requests in flight, averaged over the traced interval from the
client's records (``kernel_roofline._live_tokens``).

``bytes_roofline`` takes such a count from the engine's counters between
polls, and the polls stop while the profiler runs and while it writes its
profile (30-40 s here), so its mean is that of the window's first quarter. A
full-attention layer's rows are the contexts of the slots live at that
moment, and 13-17 s into a closed loop of 24 callers the first round of
requests is being replaced by the second, another half of the cycle of
lengths: the first quarter's mean over the profile's seconds read 82%, 92%
and 103% in three runs of one kernel (PERF.md 6, PR 35). The client sees a
request end about 0.1 s after the engine retired it, of a life of 21 s.

params {"ops": regex, "bytes": name of the family's function, called as
``fn(cfg, calls, rows, live_tokens)``, "ops_per_call"}; %. None where
nothing matches or no profile was taken."""
from benchmarks.harness import roofline
from benchmarks.harness.manifest import family_of
from benchmarks.readers.kernel_roofline import _live_tokens
from benchmarks.readers.ops_share import ops_seconds_and_count


def read(ctx, params):
    trace = ctx.get("trace") or {}
    seconds, count = ops_seconds_and_count(trace, params["ops"])
    if seconds <= 0 or not count or "records" not in ctx:
        return None
    live = _live_tokens(ctx)
    if not live:
        return None
    cfg = ctx["cfg"]
    need = getattr(family_of(cfg), params["bytes"])(
        cfg, count / params.get("ops_per_call", 1),
        cfg["deployment"]["num_slots"], live)
    peak = roofline.peaks_for(ctx["device_report"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / peak) / seconds
