"""Two sets of runs of one cell with the benchmark's own command, the same
seeds in both sets, and the statistics the driver's noise check takes from
them: per metric and set the median, the whole-set spread and the spread
without the run farthest from the median (interquartile distance of
``statistics.quantiles(values, n=4)`` over the median), then the mean of
the trimmed spreads, the wider whole-set spread and the medians apart. For
a train cell both rates of the runner's ``train rate`` line are tabled.
``--trace 1 --sets 1`` keeps one traced run's per-layer line instead.

    python3 benchmarks/tools/noise_sets.py --workload train_moe_8k \\
        --seeds 5100000011,5100000029,... --out chiprun_out/sets_train_moe_8k.json

This process never touches JAX: every run is a child that holds the chip."""

from __future__ import annotations

import argparse
import json
import os
import re
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
RATE_LINE = re.compile(r"train rate tokens/s/chip: first to last "
                       r"([0-9.]+), median of intervals ([0-9.]+)")


def spread(values):
    """Interquartile distance over the median; None under two values."""
    if len(values) < 2:
        return None
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def trimmed(values):
    """``values`` without the one farthest from their median."""
    if len(values) < 3:
        return list(values)
    mid = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - mid))
    return [v for i, v in enumerate(values) if i != far]


def set_statistics(sets):
    """``sets``: a list of lists of values, one list a set."""
    per_set = [{"median": statistics.median(s), "spread": spread(s),
                "spread_trimmed": spread(trimmed(s))} for s in sets if s]
    out = {"sets": per_set}
    if len(per_set) == 2 and all(p["spread"] is not None for p in per_set):
        a, b = (p["median"] for p in per_set)
        out["mean_trimmed"] = statistics.mean(
            p["spread_trimmed"] for p in per_set)
        out["wider"] = max(p["spread"] for p in per_set)
        out["medians_apart"] = abs(b - a) / a
    return out


def run_once(workload, seed, seconds, trace):
    cmd = [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
           "--workload", workload, "--seed", str(seed),
           "--seconds", str(seconds), "--trace", str(trace)]
    t0 = time.time()
    done = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
    lines = done.stdout.strip().splitlines()
    rec = {"seed": seed, "rc": done.returncode, "wall_s": time.time() - t0,
           "stderr_kept": [ln for ln in done.stderr.splitlines()
                           if ln.startswith(("report gaps", "train rate",
                                             "no result", "errors"))]}
    try:
        rec["result"] = json.loads(lines[-1])
    except (IndexError, ValueError):
        rec["stdout_tail"] = done.stdout[-2000:]
        rec["stderr_tail"] = done.stderr[-4000:]
    rec["checks"] = [ln for ln in lines if ln.startswith("check ")]
    return rec


def values_of(rec):
    """The run's end-to-end values, and for a train cell the two rates."""
    out = {k: v["value"] for k, v in
           (rec.get("result") or {}).get("metrics", {}).items()}
    for ln in rec["stderr_kept"]:
        m = RATE_LINE.search(ln)
        if m:
            out["rate.first_to_last"] = float(m.group(1))
            out["rate.median_of_intervals"] = float(m.group(2))
    return out


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True, help="comma separated")
    p.add_argument("--sets", type=int, default=2)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--warm-seed", type=int, default=None,
                   help="one run before the sets, so that none compiles")
    p.add_argument("--trace", type=int, choices=(0, 1), default=0,
                   help="1: traced runs (per-layer metrics; one set is enough)")
    p.add_argument("--out", required=True)
    args = p.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        seconds = args.seconds or json.load(f)["run_seconds"]
    seeds = [int(s) for s in args.seeds.split(",")]
    record = {"workload": args.workload, "seconds": seconds, "runs": []}

    def keep():
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(record, f, indent=1)

    if args.warm_seed is not None:
        rec = run_once(args.workload, args.warm_seed, seconds, 0)
        record["runs"].append({"set": "warm", **rec})
        keep()
    for k in range(args.sets):
        for seed in seeds:
            rec = run_once(args.workload, seed, seconds, args.trace)
            record["runs"].append({"set": k, **rec})
            keep()
            print(f"set {k} seed {seed} rc {rec['rc']} "
                  f"correct {(rec.get('result') or {}).get('correct')} "
                  f"{json.dumps(values_of(rec))}", flush=True)
            for ln in rec["stderr_kept"]:
                print("   " + ln, flush=True)
    names = sorted({n for r in record["runs"] for n in values_of(r)})
    record["statistics"] = {}
    for name in names:
        sets = [[values_of(r)[name] for r in record["runs"]
                 if r["set"] == k and name in values_of(r)]
                for k in range(args.sets)]
        record["statistics"][name] = set_statistics(sets)
        print(name, json.dumps(record["statistics"][name]), flush=True)
    keep()
    bad = [r for r in record["runs"]
           if r["rc"] != 0 or not (r.get("result") or {}).get("correct")]
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
