"""Device time of one jitted program in the trace over its calls (and over
the steps a call holds). A jitted partial has no name in the trace, so the
program is recognised by an operation that runs inside it. params
{"contains": regex over the program's largest ops, "without": regex,
"module": regex over the program's own name where it has one, "steps_key"}; ms."""
import re


def read(ctx, params):
    trace = ctx.get("trace") or {}
    if params.get("module"):
        named = re.compile(params["module"])
        names = [m for m in trace.get("module_s", {}) if named.search(m)]
        calls = sum(trace["module_count"][n] for n in names)
        if not calls:
            return None
        return 1e3 * sum(trace["module_s"][n] for n in names) / calls
    has = re.compile(params["contains"])
    lacks = re.compile(params["without"]) if params.get("without") else None
    names = []
    for mod, ops in trace.get("module_ops", {}).items():
        if any(has.search(k) for k in ops) and not (
                lacks and any(lacks.search(k) for k in ops)):
            names.append(mod)
    calls = sum(trace["module_count"][n] for n in names)
    if not calls:
        return None
    per = 1
    if params.get("steps_key"):
        per = ctx["cfg"]["deployment"][params["steps_key"]]
    return 1e3 * sum(trace["module_s"][n] for n in names) / (calls * per)
