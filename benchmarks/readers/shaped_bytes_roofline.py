"""A memory-bound kernel's share of its roofline where the calls differ in
shape (``flops_roofline``'s twin over bytes): the least time the chip could
take to move the bytes its calls NEED (a function of the configuration's
family, ``families/<name>.py``, of each call's own shape) over the kernel's
exclusive device time in the trace. params {"pattern": regex over the HLO
text the trace names the kernel's operations by, whose groups are the
integers of the call's shape; "bytes": name of the family's function, called
as ``fn(cfg, *groups)`` for ONE call}; %. None where nothing matches (a
program without the kernel)."""
import re

from benchmarks.harness import roofline
from benchmarks.harness.manifest import family_of


def read(ctx, params):
    trace = ctx.get("trace") or {}
    rx = re.compile(params["pattern"])
    calls = [(found, count) for op, count in trace.get("op_count", {}).items()
             if (found := rx.search(op))]
    seconds = sum(trace.get("op_self_s", {}).get(found.string, 0.0)
                  for found, _ in calls)
    if seconds <= 0:
        return None
    cfg = ctx["cfg"]
    fn = getattr(family_of(cfg), params["bytes"])
    need = sum(count * fn(cfg, *(int(g) for g in found.groups()))
               for found, count in calls)
    peak = roofline.peaks_for(ctx["device_report"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / peak) / seconds
