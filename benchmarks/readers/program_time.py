"""Device time of a jitted program that has a name of its own in the trace
(``jit_<name>``), over its calls, or over the steps its calls hold. params
{"module": regex over the program's name, "steps_key": key of the deployment
block that says how many steps a call holds, or absent for a call}; ms. None
where no program of that name ran (a commit whose programs are named
otherwise)."""
import re


def read(ctx, params):
    trace = ctx.get("trace") or {}
    named = re.compile(params["module"])
    names = [m for m in trace.get("module_s", {}) if named.search(m)]
    calls = sum(trace["module_count"][n] for n in names)
    if not calls:
        return None
    per = ctx["cfg"]["deployment"][params["steps_key"]] \
        if params.get("steps_key") else 1
    return 1e3 * sum(trace["module_s"][n] for n in names) / (calls * per)
