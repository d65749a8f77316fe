"""Runner for ``"kind": "serve"`` configurations: serve.run -> proxy ->
router -> replica -> LLMEngine, HTTP, token streaming."""

from __future__ import annotations

import os
import sys
import threading
import time
from typing import Any, Dict, List

from benchmarks.harness import loadgen
from benchmarks.harness import manifest as mf

PATH = "/llm"


def _buckets_in_play(plan, buckets: List[int]) -> List[int]:
    if plan["mode"] == "open":
        lens = [len(r["tokens"]) for r in plan["requests"]]
    else:
        lens = [len(plan["next_request"](i)["tokens"])
                for i in range(plan["offered"]["cycle"])]
    used = set()
    for n in lens:
        used.add(next((b for b in buckets if n <= b), buckets[-1]))
    return sorted(used)


def make_plan(args, cfg, traffic, seed=None, rate=None):
    gen = mf.load_plugin("generators", traffic["generator"])
    params = dict(traffic["params"])
    if args.rehearse:
        params.update(traffic.get("rehearsal", {}))
    if rate is not None:
        params["rate_per_s"] = rate
    return gen.generate(params, args.seed if seed is None else seed,
                        args.seconds, cfg["vocab_size"])


def deploy(args, resolved, cfg, plan):
    """serve.run the benchmark's deployment and warm this cell's shapes.
    Returns (handle, address, replica_ready_s)."""
    import numpy as np

    from ray_tpu import serve

    from benchmarks.runners.serve_replica import BenchLLM

    dep = cfg["deployment"]
    app = serve.deployment(
        BenchLLM, name="llm", stream=True,
        max_ongoing_requests=dep["max_ongoing_requests"],
        ray_actor_options={"num_tpus": resolved["cell"]["chips"]},
    ).bind(config_file=resolved["config_file"], seed=args.seed,
           rehearse=args.rehearse)
    t_run = time.time()
    handle = serve.run(app, name="llm", http_port=0, timeout=900.0)
    address = serve.http_address()

    # warm every shape this cell's traffic uses, and no other: one request
    # per prefill bucket in play (the engine brings up the bucket's programs
    # at every row count it runs when it first meets it) + the decode
    rng = np.random.default_rng(0)
    warm = []
    for b in _buckets_in_play(plan, sorted(
            -(-x // dep["page_size"]) * dep["page_size"]
            for x in dep["prefill_buckets"])):
        n = min(b, dep["max_seq_len"] - dep["decode_chunk"] - 2)
        warm.append({"due": 0.0, "measured": False,
                     "tokens": rng.integers(1, cfg["vocab_size"], n).tolist(),
                     "max_tokens": dep["decode_chunk"] + 1})
    t_probe = None
    for w in warm:
        res = loadgen.run_open_loop(
            address, PATH, {"requests": [w], "seconds": 0.0,
                            "drain_limit_s": 900.0, "request_timeout_s": 900.0},
            lambda: None, lambda: None)
        rec = res.records[0]
        if not res.ok(rec):
            raise RuntimeError(f"warm-up request failed: {rec.error}")
        t_probe = t_probe or time.time()
    # the handle's first call (its router, its connection to the replica) is
    # made here, with the engine idle: a traced run's first poll at the
    # window's opening is then a call like every later one (PERF.md 6, PR 56)
    handle.engine_stats.remote().result(timeout=60)
    return handle, address, t_probe - t_run


def diagnose(handle) -> Dict[str, Any]:
    """For a run whose requests did not come back or whose profile caught no
    device operation: whether the engine still steps, and where the
    replica's threads stand. Printed on stderr and carried in the result's
    line; no metric reads it."""
    keys = ("iters", "decode_steps", "active", "queued", "compiles")
    out: Dict[str, Any] = {}
    try:
        first = handle.engine_stats.remote().result(timeout=30)
        time.sleep(2.0)
        second = handle.engine_stats.remote().result(timeout=30)
        out["engine"] = {k: [first.get(k), second.get(k)] for k in keys}
        stacks = handle.thread_stacks.remote().result(timeout=30)
        out["engine_thread"] = next(
            (v for k, v in stacks.items() if k.startswith("llm-engine")), None)
        out["threads"] = len(stacks)
    except Exception as e:  # noqa: BLE001 - a diagnosis may itself fail
        out["error"] = repr(e)[:300]
    return out


def run(args, resolved: Dict[str, Any], cfg: Dict[str, Any],
        traffic: Dict[str, Any], session, t_process: float) -> Dict[str, Any]:
    dep = cfg["deployment"]
    plan = make_plan(args, cfg, traffic)
    handle, address, replica_ready_s = deploy(args, resolved, cfg, plan)

    marks: Dict[str, Any] = {"polls": []}
    stop_poll = threading.Event()
    trace_dir = os.path.join(session.scratch, "trace")

    def poller():
        traced = False
        while not stop_poll.wait(0.5):
            t_open = marks.get("open")
            if t_open is None:
                continue
            now = time.perf_counter()
            try:
                if not traced and now - t_open > args.seconds * 0.25:
                    t_call = time.perf_counter()
                    handle.trace_start.remote(trace_dir).result(timeout=60)
                    t_a = time.perf_counter()
                    time.sleep(min(4.0, args.seconds * 0.25))
                    t_b = time.perf_counter()
                    handle.trace_stop.remote().result(timeout=120)
                    marks["traced"] = (t_a, t_b)
                    marks["trace_call"] = (t_call, time.perf_counter())
                    traced = True
                    continue
                marks["polls"].append(
                    (now, handle.engine_stats.remote().result(timeout=30)))
            except Exception as e:  # noqa: BLE001 - reported, not fatal here
                marks.setdefault("poll_errors", []).append(repr(e))

    def on_open():
        marks["open"] = time.perf_counter()
        marks["open_wall"] = time.time()

    def on_close():
        marks["close"] = time.perf_counter()

    thread = None
    if args.trace:
        thread = threading.Thread(target=poller, daemon=True, name="bench-poll")
        thread.start()
    runner = loadgen.run_open_loop if plan["mode"] == "open" \
        else loadgen.run_closed_loop
    result = runner(address, PATH, plan, on_open, on_close)
    stop_poll.set()
    if thread is not None:
        thread.join(timeout=200)

    # ---- after the window: reference check, reports, trace reduction ----
    measured = result.measured
    good = [r for r in measured if result.ok(r)]
    # a stall of the whole path shows as one long gap in many streams at
    # once; the engine's own latency_s of those requests says on which side
    # of the engine it sat
    gaps = sorted(((b - a, a - result.t_open, r) for r in good
                   for a, b in zip(r.arrivals, r.arrivals[1:])),
                  key=lambda g: -g[0])
    if gaps:
        hit = [r for g, _at, r in gaps if g > 0.5 * gaps[0][0]]
        beyond = sorted(r.finished - r.sent - r.done["latency_s"] for r in hit)
        print(f"token gaps: longest {gaps[0][0]:.2f} s at {gaps[0][1]:.1f} s into "
              f"the window; {sum(g[0] > 1.0 for g in gaps)} over 1 s; the "
              f"{len(hit)} streams with a gap over half the longest spent a median "
              f"{beyond[len(beyond) // 2]:.2f} s outside the engine", file=sys.stderr)
    check_spec = traffic["check"]
    length = check_spec["length"] if not args.rehearse else dep["max_seq_len"]
    samples = []
    for r in good[: check_spec["requests"]]:
        sent = plan["requests"][r.index] if plan["mode"] == "open" \
            else plan["next_request"](r.index)
        samples.append({"prompt": sent["tokens"], "tokens": r.tokens})
    diagnosis = None
    if len(good) < max(1, len(measured)):
        diagnosis = diagnose(handle)
        print(f"diagnosis: {diagnosis}", file=sys.stderr)
    check = handle.check_requests.remote(samples, length).result(timeout=900) \
        if samples else None
    report = handle.bench_report.remote().result(timeout=60)
    summary = None
    if args.trace:
        # the operations a metric of this cell knows a program's rows by
        manifest = mf.load_manifest()
        params = [mf.metric_params(m["name"], cfg, manifest=manifest)
                  for m in mf.metrics_for(manifest, args.workload, "per_layer")]
        keep = sorted({p["rows_from"] for p in params if "rows_from" in p})
        summary = handle.trace_summary.remote(keep).result(timeout=600)
        if not summary.get("planes") and diagnosis is None:
            diagnosis = diagnose(handle)
            print(f"diagnosis (the profile holds no device operation; "
                  f"poll errors {marks.get('poll_errors')}): {diagnosis}",
                  file=sys.stderr)
    return {
        "cfg": cfg, "plan_offered": plan["offered"],
        "records": result.records, "t_open": result.t_open, "t_close": result.t_close,
        "drain_limit_s": plan["drain_limit_s"],
        "attempted": len(measured), "failed": len(measured) - len(good),
        "errors": [r.error for r in measured if r.error][:5],
        "setup_s": marks["open_wall"] - t_process,
        "replica_ready_s": replica_ready_s, "device_report": report, "trace": summary, "marks": marks,
        "check": check, "check_limits": traffic["check"].get("limits", {}),
        "diagnosis": diagnosis,
    }
