"""Read the two numbers every limit of ``correct`` is set from: the largest
that sound runs of the program give over a dozen seeds, and the smallest that
the control gives (the plain reference computed in fp8, the precision below
the configuration's bfloat16, put in the program's place). One process.

    python3 benchmarks/tools/read_limits.py --workload serve_chat --seeds 12 --control 3
    python3 benchmarks/tools/read_limits.py --workload train_4k --seeds 12 --control 3
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def serve_limits(args, resolved, cfg, traffic, out):
    from benchmarks.harness import loadgen
    from benchmarks.harness.session import BenchSession
    from benchmarks.runners import serve as rs

    spec = traffic["check"]
    length = spec["length"] if not args.rehearse else cfg["deployment"]["max_seq_len"]
    with BenchSession(resolved["cell"]["chips"], args.workload):
        args.seed = args.first_seed
        plan = rs.make_plan(args, cfg, traffic)
        handle, address, _ready = rs.deploy(args, resolved, cfg, plan)
        for k in range(args.seeds):
            seed = args.first_seed + 7919 * k
            if k:
                handle.reseed.remote(seed).result(timeout=600)
            plan = rs.make_plan(args, cfg, traffic, seed=seed)
            if plan["mode"] == "open":
                take = [r for r in plan["requests"] if r["measured"]][: spec["requests"]]
            else:
                take = [plan["next_request"](i) for i in range(spec["requests"])]
            reqs = [{**r, "due": 0.05 * i, "measured": True} for i, r in enumerate(take)]
            res = loadgen.run_open_loop(
                address, rs.PATH, {"requests": reqs, "seconds": 1.0,
                                   "drain_limit_s": 600.0}, lambda: None, lambda: None)
            bad = [r.error for r in res.records if not res.ok(r)]
            if bad:
                raise RuntimeError(f"seed {seed}: requests failed: {bad[:2]}")
            samples = [{"prompt": q["tokens"], "tokens": r.tokens}
                       for q, r in zip(reqs, res.records)]
            row = {"seed": seed, "side": "program",
                   **handle.check_requests.remote(samples, length).result(timeout=900)}
            out.append(row)
            print(json.dumps(row), flush=True)
            if k < args.control:
                prompts = [q["tokens"] for q in reqs[: args.control_prompts]]
                toks = handle.control_tokens.remote(
                    prompts, args.control_steps, length, "fp8").result(timeout=1800)
                samples = [{"prompt": p_, "tokens": t} for p_, t in zip(prompts, toks)]
                row = {"seed": seed, "side": "control_fp8",
                       **handle.check_requests.remote(samples, length).result(timeout=900)}
                out.append(row)
                print(json.dumps(row), flush=True)


def train_limits(args, resolved, cfg, traffic, out):
    """In this process: the tool holds the chip itself."""
    import jax

    from benchmarks.harness.manifest import family_of, load_plugin
    from benchmarks.runners.train import compare, make_checkers

    if jax.devices()[0].platform != "tpu" and not args.rehearse:
        raise SystemExit("no accelerator")
    family = family_of(cfg)
    config = family.program_config(cfg)
    params = dict(traffic["params"])
    if args.rehearse:
        params.update(traffic.get("rehearsal", {}))
    params["rows"] = cfg["deployment"]["batch_rows"]
    gen = load_plugin("generators", traffic["generator"])
    checkers = make_checkers(family, cfg, config)
    r = cfg["deployment"]["check_rows"]
    for k in range(args.seeds):
        seed = args.first_seed + 7919 * k
        data = gen.generate(params, seed, 1.0, cfg["vocab_size"])
        tokens, targets = data["tokens"][:r], data["targets"][:r]
        weights = family.make_weights(config, seed)
        sides = ["program"] + (["control"] if k < args.control else [])
        for side in sides:
            row = {"seed": seed, "side": side,
                   **compare(checkers, side, weights, tokens, targets)}
            out.append(row)
            print(json.dumps(row), flush=True)
        del weights


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--control", type=int, default=3)
    p.add_argument("--control-prompts", type=int, default=3, dest="control_prompts")
    p.add_argument("--control-steps", type=int, default=16, dest="control_steps")
    p.add_argument("--first-seed", type=int, default=2200000011, dest="first_seed")
    p.add_argument("--seconds", type=float, default=10)
    p.add_argument("--out", default="")
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args()
    args.trace = 0
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.setdefault("RAY_TPU_FAKE_TPU_CHIPS", "1")
    from benchmarks.harness import manifest as mf
    from benchmarks.harness.weights import load_config_file

    manifest = mf.load_manifest()
    resolved = mf.resolve_cell(manifest, args.workload)
    cfg = load_config_file(resolved["config_file"], args.rehearse)
    traffic = json.load(open(resolved["traffic_file"]))
    out = []
    {"serve": serve_limits, "train": train_limits}[cfg["kind"]](
        args, resolved, cfg, traffic, out)
    path = os.path.join(ROOT, args.out or f"chiprun_out/limits_{args.workload}.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
