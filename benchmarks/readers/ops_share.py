"""The share of a named program's device time that some of its operations
take: their exclusive time in the trace over the program's time. The
operations are matched as the other readers match kernels, by a regular
expression over the HLO text the trace names them by (an operand shape that
only that mechanism has). params {"ops": regex, "module": regex over the
program's name}; %. None where nothing matches."""
import re


def ops_seconds_and_count(trace, pattern):
    rx = re.compile(pattern)
    seconds = sum(v for k, v in trace.get("op_self_s", {}).items() if rx.search(k))
    count = sum(v for k, v in trace.get("op_count", {}).items() if rx.search(k))
    return seconds, count


def read(ctx, params):
    trace = ctx.get("trace") or {}
    seconds, _ = ops_seconds_and_count(trace, params["ops"])
    named = re.compile(params["module"])
    whole = sum(v for k, v in trace.get("module_s", {}).items() if named.search(k))
    if seconds <= 0 or whole <= 0:
        return None
    return 100.0 * seconds / whole
