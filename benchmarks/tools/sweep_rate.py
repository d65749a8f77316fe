"""The one sweep for an open-loop traffic's knee: one process, one replica,
the same traffic at several rates. For each rate: the backlog (sent minus
finished) sampled through the window and its slope, TTFT and TPOT tails,
tokens per second read between arrivals.

    python3 benchmarks/tools/sweep_rate.py --workload serve_chat --seconds 30 \\
        --rates 2,3,4,5,6,8 --out chiprun_out/sweep.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def backlog_slope(records, t_open, t_close):
    xs, ys = [], []
    t = t_open
    while t <= t_close:
        sent = sum(1 for r in records if r.sent is not None and r.sent <= t)
        done = sum(1 for r in records if r.finished is not None and r.finished <= t)
        xs.append(t - t_open)
        ys.append(sent - done)
        t += 1.0
    n = len(xs)
    mx, my = sum(xs) / n, sum(ys) / n
    slope = sum((x - mx) * (y - my) for x, y in zip(xs, ys)) / \
        max(sum((x - mx) ** 2 for x in xs), 1e-9)
    return slope, ys


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, default=30)
    p.add_argument("--rates", required=True)
    p.add_argument("--seed", type=int, default=11)
    p.add_argument("--out", default="chiprun_out/sweep.json")
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args()
    args.trace = 0
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.setdefault("RAY_TPU_FAKE_TPU_CHIPS", "1")
    from benchmarks.harness import loadgen, manifest as mf
    from benchmarks.harness.session import BenchSession
    from benchmarks.harness.weights import load_config_file
    from benchmarks.readers import serve_token_rate, tpot_percentile, ttft_percentile
    from benchmarks.runners import serve as rs

    manifest = mf.load_manifest()
    resolved = mf.resolve_cell(manifest, args.workload)
    cfg = load_config_file(resolved["config_file"], args.rehearse)
    traffic = json.load(open(resolved["traffic_file"]))
    rows = []
    with BenchSession(resolved["cell"]["chips"], args.workload):
        plan = rs.make_plan(args, cfg, traffic)
        handle, address, ready = rs.deploy(args, resolved, cfg, plan)
        print(json.dumps({"replica_ready_s": ready}), flush=True)
        for k, rate in enumerate(float(r) for r in args.rates.split(",")):
            plan = rs.make_plan(args, cfg, traffic, seed=args.seed + k, rate=rate)
            res = loadgen.run_open_loop(address, rs.PATH, plan,
                                        lambda: None, lambda: None)
            ctx = {"records": res.records, "t_open": res.t_open,
                   "t_close": res.t_close}
            slope, samples = backlog_slope(res.records, res.t_open, res.t_close)
            measured = res.measured
            stats = handle.engine_stats.remote().result(timeout=30)
            row = {"rate": rate, "offered": len(measured),
                   "failed": sum(1 for r in measured if not res.ok(r)),
                   "backlog_slope_per_s": slope,
                   "backlog_first_last": [samples[0], samples[-1]],
                   "backlog_max": max(samples),
                   "ttft_p50": ttft_percentile.read(ctx, {"q": 0.5}),
                   "ttft_p90": ttft_percentile.read(ctx, {"q": 0.9}),
                   "tpot_p50": tpot_percentile.read(ctx, {"q": 0.5}),
                   "tpot_p90": tpot_percentile.read(ctx, {"q": 0.9}),
                   "tokens_per_s": serve_token_rate.read(ctx, {}),
                   "drain_s": res.drained_at - res.t_close,
                   "engine": stats}
            rows.append(row)
            print(json.dumps(row), flush=True)
            time.sleep(1.0)
    os.makedirs(os.path.dirname(os.path.join(ROOT, args.out)), exist_ok=True)
    with open(os.path.join(ROOT, args.out), "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
