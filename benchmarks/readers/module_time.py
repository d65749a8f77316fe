"""Device time of one jitted program in the trace over its calls, over the
steps its calls hold, or over the rows they hold. The program is recognised
by its own name in the trace (``jit_<name>(<fingerprint>)``; every compiled
shape of it is a module of its own there) or, where it has none, by an
operation that runs inside it. params:

    "module": regex over the program's name; or
    "contains" / "without": regex over the program's largest operations
    "steps_key": key of the deployment block that says how many steps a call
        holds (a decode chunk); absent for a call
    "rows_from": regex with one group over the program's operations
        (``module_ops`` of the reducer, which keeps every operation such a
        pattern matches): the group of the heaviest match is the rows a call
        of that module holds, and the time is then a ROW's, so that calls of
        1 and 4 rows are one quantity. A matched module without such an
        operation has no known rows: nothing is read (a module of which
        the profile holds no whole call is left out before that: the part
        of a call it saw may end before the operation, and its time is not
        read anyway)
    "cut_at_edges": false where the runner starts and stops the profile
        between calls (training); else the calls that touch the profile's
        first or last instant are left out (``trace_reduce._whole``): the
        profile saw a part of them

ms. None where no whole call of such a program is in the trace."""
import re


def _modules(trace, params):
    if params.get("module"):
        named = re.compile(params["module"])
        return [m for m in trace.get("module_s", {}) if named.search(m)]
    has = re.compile(params["contains"])
    lacks = re.compile(params["without"]) if params.get("without") else None
    return [mod for mod, ops in trace.get("module_ops", {}).items()
            if any(has.search(k) for k in ops)
            and not (lacks and any(lacks.search(k) for k in ops))]


def _rows(ops, pattern):
    """The group of the heaviest operation ``pattern`` matches, or None."""
    found = {op: m for op, m in ((op, pattern.search(op)) for op in ops) if m}
    if not found:
        return None
    return int(found[max(found, key=ops.get)].group(1))


def read(ctx, params):
    trace = ctx.get("trace") or {}
    which = "module_whole" if params.get("cut_at_edges", True) else "module"
    seconds, count = trace.get(which + "_s", {}), trace.get(which + "_count", {})
    names = _modules(trace, params)
    per = 1
    if names and params.get("steps_key"):
        per = ctx["cfg"]["deployment"][params["steps_key"]]
    rows_from = re.compile(params["rows_from"]) if params.get("rows_from") else None
    total, units = 0.0, 0
    for name in names:
        if not count.get(name):
            # every call of it touches the profile's edge: no time is read
            # from it, so the part the profile saw need not name its rows
            continue
        rows = 1
        if rows_from is not None:
            rows = _rows(trace.get("module_ops", {}).get(name, {}), rows_from)
            if rows is None:
                return None
        total += seconds.get(name, 0.0)
        units += count.get(name, 0) * per * rows
    if not units:
        return None
    return 1e3 * total / units
