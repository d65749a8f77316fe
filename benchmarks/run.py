"""The benchmark's one command.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A cell in ``BENCHMARK.json`` names a configuration file, a traffic file and
its chips; the configuration's ``kind`` names the runner. The last line of
standard output is one JSON object (see PERF.md). Without a chip: a non-zero
exit and no result. ``--rehearse`` (used only by the tests) runs the same
control flow at tiny widths on the CPU backend and can never report a device
metric."""

from __future__ import annotations

import time

T_PROCESS = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def evaluate_check(check, limits):
    """Every number compared, beside its limit. Returns (correct, lines,
    compared): ``compared`` is {name: [value, limit]} of what was held to a
    limit, for the result's line."""
    if not check:
        return False, ["check: nothing was compared"], {}
    ok, lines, compared = True, [], {}
    for key, limit in sorted(limits.items()):
        value = check.get(key)
        good = value is not None and math.isfinite(value) and value <= limit
        ok = ok and good
        compared[key] = [value, limit]
        lines.append(f"check {key}={value} limit={limit} {'ok' if good else 'FAIL'}")
    for key, value in sorted(check.items()):
        if isinstance(value, bool):
            ok = ok and value
            compared[key] = [value, True]
            lines.append(f"check {key}={value} limit=True {'ok' if value else 'FAIL'}")
        elif key not in limits:
            lines.append(f"check {key}={value} (reported, no limit)")
    if not limits:
        ok = False
        lines.append("check: no limits are set for this traffic")
    return ok, lines, compared


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args(argv)

    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.setdefault("RAY_TPU_FAKE_TPU_CHIPS", "1")
    from benchmarks.harness import manifest as mf
    from benchmarks.harness.session import BenchSession, NoChip
    from benchmarks.harness.trace_reduce import sanitize
    from benchmarks.harness.weights import load_config_file

    manifest = mf.load_manifest()
    resolved = mf.resolve_cell(manifest, args.workload)
    cfg = load_config_file(resolved["config_file"], args.rehearse)
    with open(resolved["traffic_file"]) as f:
        traffic = json.load(f)
    runner = mf.load_plugin("runners", cfg["kind"])
    session = BenchSession(resolved["cell"]["chips"], args.workload)
    try:
        with session:
            ctx = runner.run(args, resolved, cfg, traffic, session, T_PROCESS)
    except NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 3
    except BaseException:  # noqa: BLE001 - one failure, reported, no result line
        traceback.print_exc()
        return 1
    if session.left_behind:
        print(f"no result: processes left behind: {session.left_behind}",
              file=sys.stderr)
        return 4
    dev = ctx["device_report"]
    if dev["platform"] != "tpu" and not args.rehearse:
        print(f"no result: the worker ran on {dev['platform']}", file=sys.stderr)
        return 3
    if dev["count"] != resolved["cell"]["chips"]:
        print(f"no result: the worker held {dev['count']} device(s)",
              file=sys.stderr)
        return 3

    correct, lines, compared = evaluate_check(
        ctx.get("check"), ctx.get("check_limits", {}))
    for line in lines:
        print(line)
    group = "per_layer" if args.trace else "end_to_end"
    # a rehearsal on the CPU backend may not print a number under a metric's name
    metrics = {} if args.rehearse else mf.read_metrics(
        manifest, args.workload, group, ctx)
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"],
              "memory_peak_bytes": dev.get("memory_peak_bytes")}
    out = {"correct": bool(correct), "attempted": ctx["attempted"],
           "failed": ctx["failed"], "metrics": metrics, "device": device}
    trace = ctx.get("trace")
    if args.trace and trace and trace.get("planes"):
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        out["breakdown"] = {
            "device_ops": [[sanitize(n), s] for n, s in trace["device_ops"]],
            "idle_gaps": [[sanitize(n), s] for n, s in trace["idle_gaps"]]}
    if args.rehearse:
        out["rehearsal"] = True
        out["observed"] = {k: ctx.get(k) for k in (
            "plan_offered", "setup_s", "errors", "check", "report_waits",
            "probe_reports")}
    if ctx.get("errors"):
        print(f"errors: {ctx['errors']}", file=sys.stderr)
    # last in the line and last on stderr: what was compared, beside its
    # limit, and for a run whose requests never came back where it stood
    out["check"] = dict(compared)
    if ctx.get("errors"):
        out["check"]["errors"] = [str(e)[:200] for e in ctx["errors"][:2]]
    if ctx.get("diagnosis"):
        out["check"]["diagnosis"] = ctx["diagnosis"]
    for line in lines:
        print(line, file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
