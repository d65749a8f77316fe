"""From a profiler trace to numbers: device busy union, idle share,
per-operation self time, program (module) time and counts, and the longest
idle gaps with what the host was doing in them.

``load_xplane`` turns jax's ``.xplane.pb`` into plain lists; ``reduce`` works
on those lists only, so it is tested on a small recorded trace kept as JSON
(``tests/data/small_trace.json``)."""

from __future__ import annotations

import glob
import os
import re
from itertools import chain
from typing import Any, Dict, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MIN_GAP_NS = 10_000  # shorter pauses between two ops are not idle gaps worth listing
# the flash-attention forward call in an op's HLO text (``op_key``): output and
# first operand ``bf16[rows, heads, seq, head_dim]``; the group is the rows
FLASH_CALL_ROWS = (r"= bf16\[(\d+),\d+,\d+,\d+\]\S* "
                   r"custom-call\(bf16\[\1,\d+,\d+,\d+\]")


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def op_key(text: str) -> str:
    """An XLA op event is named by its HLO text, ``%copy.89 = bf16[8,1537,64,128]{...}
    copy(...)``. Keep it (cut to 400 characters, without the ``%``): readers
    match kernels by regular expressions over name, shapes and operands."""
    return text.lstrip("%")[:400]


def load_xplane(path: str, host_min_ns: int = 50_000) -> Dict[str, Any]:
    """{"devices": {plane: {"ops": [[name, start_ns, dur_ns]], "modules": [...]}},
        "host": [[thread, name, start_ns, dur_ns]]}
    Host events shorter than ``host_min_ns`` cannot explain a gap worth
    listing and are dropped."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key is None:
                    continue
                for ev in line.events:
                    dev[key].append([op_key(ev.name), int(ev.start_ns),
                                     int(ev.duration_ns)])
            if dev["ops"]:
                out["devices"][plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.duration_ns >= host_min_ns:
                        out["host"].append([line.name, ev.name,
                                            int(ev.start_ns), int(ev.duration_ns)])
    return out


def _self_times(events: List[List]) -> Dict[str, float]:
    """Exclusive time per name: an op that encloses others (a while loop
    around its body) is charged only what its children do not cover."""
    sums: Dict[str, float] = {}
    stack: List[List] = []  # [name, end, self_ns]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _end, self_ns = stack.pop()
            sums[name] = sums.get(name, 0.0) + max(self_ns, 0)

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return sums


def _whole(events: List[List], first: int, last: int) -> List[List]:
    """The events a profile holds whole: those that touch neither its first
    nor its last instant. A profile taken over a span of wall time cuts the
    program that was running at either end, and records the part it saw as an
    event of its own; counted as a call, it makes a time a call read short."""
    return [e for e in events if e[1] > first and e[1] + e[2] < last]


def _ops_by_module(modules: List[List], ops: List[List]) -> Dict[str, Dict[str, float]]:
    """Total duration of each op by the module whose span holds its start."""
    import bisect

    spans = sorted((start, start + dur, name) for name, start, dur in modules)
    starts = [s[0] for s in spans]
    out: Dict[str, Dict[str, float]] = {}
    for name, start, dur in ops:
        i = bisect.bisect_right(starts, start) - 1
        if i < 0 or start >= spans[i][1]:
            continue
        table = out.setdefault(spans[i][2], {})
        table[name] = table.get(name, 0.0) + dur
    return out


def _busy_intervals(events: List[List]) -> List[Tuple[int, int]]:
    merged: List[List[int]] = []
    for _name, start, dur in sorted(events, key=lambda e: e[1]):
        end = start + dur
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def _attribute(gap: Tuple[int, int], host: List[List]) -> str:
    best, best_overlap = "unattributed", 0
    a, b = gap
    for thread, name, start, dur in host:
        overlap = min(b, start + dur) - max(a, start)
        if overlap > best_overlap:
            best, best_overlap = f"{thread}:{name}", overlap
    if best_overlap < 0.2 * (b - a):
        return "unattributed"
    return best


def reduce(trace: Dict[str, Any], top: int = 10,
           keep: Sequence[str] = ()) -> Dict[str, Any]:
    """busy_s and window_s are averaged over the device planes; op and
    module sums are totals over them, in seconds. ``module_whole_*`` count
    only the calls the profile holds whole (``_whole``). ``module_ops`` keeps
    a program's twelve largest operations and, whatever their time, those a
    ``keep`` pattern matches (a reader that knows a program's rows by one
    small operation's shape names it there)."""
    kept = [re.compile(k) for k in keep]
    devices = trace["devices"]
    if not devices:
        return {"planes": 0}
    busy, window = [], []
    op_self: Dict[str, float] = {}
    op_count: Dict[str, int] = {}
    mod_sum: Dict[str, float] = {}
    mod_count: Dict[str, int] = {}
    whole_sum: Dict[str, float] = {}
    whole_count: Dict[str, int] = {}
    mod_ops: Dict[str, Dict[str, float]] = {}
    gaps: List[Tuple[int, int]] = []
    for dev in devices.values():
        ops = dev["ops"]
        merged = _busy_intervals(ops)
        busy.append(sum(b - a for a, b in merged))
        window.append(merged[-1][1] - merged[0][0])
        gaps += [(merged[i][1], merged[i + 1][0]) for i in range(len(merged) - 1)
                 if merged[i + 1][0] - merged[i][1] >= MIN_GAP_NS]
        for name, ns in _self_times(ops).items():
            op_self[name] = op_self.get(name, 0.0) + ns
        for name, _s, _d in ops:
            op_count[name] = op_count.get(name, 0) + 1
        for name, _s, dur in dev["modules"]:
            mod_sum[name] = mod_sum.get(name, 0.0) + dur
            mod_count[name] = mod_count.get(name, 0) + 1
        first = min(e[1] for e in chain(ops, dev["modules"]))
        last = max(e[1] + e[2] for e in chain(ops, dev["modules"]))
        for name, _s, dur in _whole(dev["modules"], first, last):
            whole_sum[name] = whole_sum.get(name, 0.0) + dur
            whole_count[name] = whole_count.get(name, 0) + 1
        for mod, inside in _ops_by_module(dev["modules"], ops).items():
            table = mod_ops.setdefault(mod, {})
            for name, ns in inside.items():
                table[name] = table.get(name, 0.0) + ns
    gaps.sort(key=lambda g: g[0] - g[1])
    host = trace.get("host", [])
    n = len(devices)
    return {
        "planes": n,
        "busy_s": sum(busy) / n / 1e9,
        "window_s": sum(window) / n / 1e9,
        "op_self_s": {k: v / 1e9 for k, v in op_self.items()},
        "op_count": op_count,
        "module_s": {k: v / 1e9 for k, v in mod_sum.items()},
        "module_count": mod_count,
        "module_whole_s": {k: v / 1e9 for k, v in whole_sum.items()},
        "module_whole_count": whole_count,
        # a jitted partial has no name of its own ("jit__unknown(hash)"), so a
        # program is recognised by the operations that ran inside it
        "module_ops": {m: {k: v / 1e9 for i, (k, v) in enumerate(sorted(
            t.items(), key=lambda kv: -kv[1]))
            if i < 12 or any(rx.search(k) for rx in kept)}
            for m, t in mod_ops.items()},
        "device_ops": [[k, v / 1e9] for k, v in sorted(
            op_self.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[_attribute(g, host), (g[1] - g[0]) / 1e9]
                      for g in gaps[:top]],
    }


def sanitize(name: str) -> str:
    """Names in the result line hold no space, comma or slash."""
    return re.sub(r"[^A-Za-z0-9_.\-:]", "_", name)[:64]


def summarize_dir(trace_dir: str, keep: Sequence[str] = ()) -> Dict[str, Any]:
    """What the process that took the profile sends back: ``reduce`` of the
    newest trace under ``trace_dir``."""
    path = find_xplane(trace_dir) if trace_dir else None
    if path is None:
        return {"planes": 0}
    return reduce(load_xplane(path), keep=keep)
