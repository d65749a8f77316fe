"""``flops_roofline`` for a kernel whose work follows what the PROGRAM counted
and not the call's padded shape: the least time the chip could take for the
operations its calls NEED over the kernel's exclusive device time in the
trace, where what one call needs is a function of its own shape (the groups
of ``pattern``, as ``flops_roofline``) AND of the mean of an engine counter
a program call between the polls of the window (``bytes_roofline``'s
``touched``): a prefill's causal pairs are those of its prompts, not of the
bucket they were padded to. The mean is over the window's program calls and
the kernel's seconds over the profile's: two samples of one cycle of
lengths, so a short profile reads a few calls' draw of it.

params {"pattern": regex over the HLO text the trace names the kernel's
operations by, whose groups are the integers of the call's shape; "flops":
name of the family's function, called as ``fn(cfg, mean, *groups)`` for ONE
call of the kernel; "touched": [counter summed, counter of program calls]};
%. None where nothing matches or a counter is missing (a program without
the kernel or the counter)."""
import re

from benchmarks.harness import roofline
from benchmarks.harness.manifest import family_of
from benchmarks.readers.engine_counters import delta


def read(ctx, params):
    trace = ctx.get("trace") or {}
    rx = re.compile(params["pattern"])
    calls = [(found, count) for op, count in trace.get("op_count", {}).items()
             if (found := rx.search(op))]
    seconds = sum(trace.get("op_self_s", {}).get(found.string, 0.0)
                  for found, _ in calls)
    if seconds <= 0:
        return None
    total, program_calls = (delta(ctx, key) for key in params["touched"])
    if total is None or not program_calls:
        return None
    cfg = ctx["cfg"]
    fn = getattr(family_of(cfg), params["flops"])
    need = sum(count * fn(cfg, total / program_calls,
                          *(int(g) for g in found.groups()))
               for found, count in calls)
    peak = roofline.peaks_for(ctx["device_report"]["kind"])["bf16_flops"]
    return 100.0 * (need / peak) / seconds
