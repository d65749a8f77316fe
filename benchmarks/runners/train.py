"""Runner for ``"kind": "train"`` configurations: TpuTrainer -> Data feed
(streaming_split -> iter_jax_batches) -> the family's train step, one
``train.report`` every ``report_every`` steps. A traced run goes on after the
window for ``report_probe_steps`` steps with one report a step, the path
ISSUE 24 asked for, and times them. The train loop below is the benchmark's
own and runs inside the worker that holds the chip(s)."""

from __future__ import annotations

import math
import os
import sys
import time
from typing import Any, Dict

from benchmarks.harness.manifest import load_plugin
from benchmarks.readers import train_token_rate


def init_state(family, config, optimizer, key):
    """TrainState from the family's seeded weights; trace it under jit."""
    import jax.numpy as jnp

    from ray_tpu.train.step import TrainState

    params = family.init_weights(config, key)
    return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                      opt_state=optimizer.init(params))


def _global_norm(tree):
    import jax
    import jax.numpy as jnp

    return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree.leaves(tree)))


def make_checkers(family, cfg, config):
    """jitted (params, tokens, targets) -> (loss, gradients): the program's
    loss, the family's plain float32 reference, and the reference in fp8
    (the control); and ``diff`` over two gradient trees."""
    import jax
    import jax.numpy as jnp

    def pair(fn):
        return jax.jit(lambda params, tokens, targets: jax.value_and_grad(
            lambda p: fn(p, tokens, targets))(params))

    def diff(got, want):
        delta = jax.tree.map(lambda a, b: a.astype(jnp.float32)
                             - b.astype(jnp.float32), got, want)
        return _global_norm(got), _global_norm(want), _global_norm(delta)

    return {
        "program": pair(lambda p, t, y: family.loss(p, t, y, config)),
        "reference": pair(lambda p, t, y: family.reference_loss(p, t, y, cfg)),
        "control": pair(
            lambda p, t, y: family.reference_loss(p, t, y, cfg, "fp8")),
        "diff": jax.jit(diff),
    }


def compare(checkers, side: str, params, tokens, targets) -> Dict[str, float]:
    """``side`` ("program" or "control") against the reference. The
    gradient is compared as a VECTOR (norm of the difference over the norm
    of the reference's): errors in random directions barely move a norm."""
    got_loss, got = checkers[side](params, tokens, targets)
    ref_loss, want = checkers["reference"](params, tokens, targets)
    got_norm, ref_norm, delta = (float(x) for x in checkers["diff"](got, want))
    got_loss, ref_loss = float(got_loss), float(ref_loss)
    return {"loss": got_loss, "ref_loss": ref_loss,
            "grad_norm": got_norm, "ref_grad_norm": ref_norm,
            "loss_abs_diff": abs(got_loss - ref_loss),
            "grad_norm_rel_diff": abs(got_norm - ref_norm) / max(ref_norm, 1e-12),
            "grad_rel_err": delta / max(ref_norm, 1e-12)}


def train_loop(job: Dict[str, Any]) -> None:
    import jax

    from ray_tpu import train
    from ray_tpu.train.session import get_dataset_shard
    from ray_tpu.train.step import default_optimizer
    from ray_tpu.utils.compile_cache import enable_compile_cache
    from ray_tpu.utils.device_report import device_report

    from benchmarks.harness.manifest import family_of
    from benchmarks.harness.trace_reduce import summarize_dir
    from benchmarks.harness.weights import load_config_file, seed_key

    enable_compile_cache()
    cfg = load_config_file(job["config_file"], job["rehearse"])
    dep = cfg["deployment"]
    family = family_of(cfg)
    config = family.program_config(cfg)
    fsdp = int(dep.get("fsdp", 1))
    mesh = batch_sh = None
    opt = default_optimizer(warmup_steps=10, total_steps=1000)
    if fsdp > 1:
        # untested on four chips in PR 24 (PERF.md, Open question 1)
        from ray_tpu.parallel.mesh import MeshConfig, batch_sharding_spec, make_mesh

        mesh = make_mesh(MeshConfig(fsdp=fsdp))
        batch_sh = jax.sharding.NamedSharding(mesh, batch_sharding_spec())
        make = jax.jit(lambda k: init_state(family, config, opt, k),
                       out_shardings=family.state_shardings(config, opt, mesh))
    else:
        make = jax.jit(lambda k: init_state(family, config, opt, k))
    state = make(seed_key(job["seed"]))
    step = family.make_train_step(config, opt, mesh=mesh)
    jax.block_until_ready(state)

    rows, seq = dep["batch_rows"], dep["max_seq_len"]
    batches = iter(get_dataset_shard("train").iter_jax_batches(
        batch_size=rows, sharding=batch_sh))
    reports, waits, losses, report_waits = [], [], [], []
    warm, every = dep["warmup_steps"], dep["report_every"]
    # after the window of a traced run: one report a step, each step timed
    probe_left = dep.get("report_probe_steps", 0) if job["trace"] else 0
    probe_reports = None
    first_rows = None
    window_open = None
    traced, trace_steps, trace_at = "no", 0, None
    trace_dir = os.path.join(job["scratch"], "trace")
    i = 0
    while True:
        t_a = time.perf_counter()
        try:
            batch = next(batches)
        except StopIteration:
            break
        waits.append(time.perf_counter() - t_a)
        tokens, targets = batch["tokens"], batch["targets"]
        if first_rows is None:
            r = dep["check_rows"]
            first_rows = (tokens[:r], targets[:r])
        i += 1
        # a traced run starts the profiler after the third report (the one
        # that opens the window and two more), so that two whole intervals
        # before it give the rate
        if job["trace"] and traced == "no" and len(reports) >= 3:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            trace_at = [time.time(), None]
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            traced = "on"
        state, out = step(state, tokens, targets)
        host = jax.device_get(out)
        if traced == "on":
            trace_steps += 1
            if trace_steps >= 3:
                jax.profiler.stop_trace()
                trace_at[1] = time.time()
                traced = "done"
        losses.append(float(host["loss"]))
        if probe_reports is not None:
            train.report({"step": i, "loss": losses[-1],
                          "grad_norm": float(host["grad_norm"])})
            probe_reports.append(time.time())
            if len(probe_reports) > probe_left:
                break
            continue
        # one report when warm-up ends (it opens the window), then one every
        # ``report_every`` steps
        closed = window_open is not None \
            and time.time() >= window_open + job["seconds"]
        if i == warm or (i > warm and (i - warm) % every == 0):
            t_r = time.perf_counter()
            train.report({"step": i, "loss": losses[-1],
                          "grad_norm": float(host["grad_norm"])})
            report_waits.append(time.perf_counter() - t_r)
            now = time.time()
            reports.append((now, i))
            if i == warm:
                window_open = now
            closed = now >= window_open + job["seconds"]
        if closed:
            if not probe_left:
                break
            probe_reports = [time.time()]
    if traced == "on":
        jax.profiler.stop_trace()
    peak = max(((d.memory_stats() or {}).get("peak_bytes_in_use") or 0)
               for d in jax.devices())
    del state
    check = compare(make_checkers(family, cfg, config), "program",
                    family.make_weights(config, job["seed"]), *first_rows)
    summary = summarize_dir(trace_dir) if job["trace"] else None
    train.report({"final": True, "reports": reports, "input_waits": waits,
                  "report_waits": report_waits,
                  "probe_reports": probe_reports,
                  "losses": losses, "window_open": window_open,
                  "tokens_per_step": rows * seq, "check": check,
                  "trace": summary, "trace_steps": trace_steps,
                  "trace_at": trace_at,
                  "device": device_report(), "memory_peak_bytes": peak})


def run(args, resolved: Dict[str, Any], cfg: Dict[str, Any],
        traffic: Dict[str, Any], session, t_process: float) -> Dict[str, Any]:
    import ray_tpu.data as rd
    from ray_tpu.train import RunConfig, ScalingConfig, TpuTrainer

    dep = cfg["deployment"]
    chips = resolved["cell"]["chips"]
    params = dict(traffic["params"])
    if args.rehearse:
        params.update(traffic.get("rehearsal", {}))
    gen = load_plugin("generators", traffic["generator"])
    data = gen.generate(params, args.seed, args.seconds, cfg["vocab_size"])
    ds = rd.from_numpy({"tokens": data["tokens"], "targets": data["targets"]})
    trainer = TpuTrainer(
        train_loop,
        train_loop_config=dict(
            config_file=resolved["config_file"], seed=args.seed,
            seconds=args.seconds, trace=bool(args.trace),
            rehearse=args.rehearse, scratch=session.scratch),
        scaling_config=ScalingConfig(num_workers=1, use_tpu=True,
                                     tpus_per_worker=chips),
        run_config=RunConfig(name="bench_train", storage_path=os.path.join(
            session.scratch, "train_results")),
        datasets={"train": ds},
    )
    t_fit = time.time()
    result = trainer.fit()
    if result.error is not None:
        raise RuntimeError(f"training failed: {result.error!r}")
    final = result.metrics_history[-1]
    if not final.get("final"):
        raise RuntimeError("the train loop ended without its final report")
    stamped, w_open = final["reports"], final["window_open"]
    # (instant, tokens of the steps since the report before it)
    tokens = final["tokens_per_step"]
    reports = [t for t, _i in stamped]
    amounts = [tokens * (i - (stamped[k - 1][1] if k else 0))
               for k, (_t, i) in enumerate(stamped)]
    if w_open is None:
        raise RuntimeError("the dataset ended before warm-up did")
    w_close = w_open + args.seconds
    inside = [t for t in reports if w_open <= t <= w_close]
    finite = all(math.isfinite(x) for x in final["losses"])
    print("report gaps s: " + " ".join(
        f"{b - a:.4f}" for a, b in zip(reports, reports[1:]))
        + f"; longest input wait {max(final['input_waits']):.3f} s"
        + f", longest report wait {max(final['report_waits']):.3f} s", file=sys.stderr)
    ctx = {
        "cfg": cfg, "chips": chips,
        "plan_offered": data["offered"],
        "reports": reports, "report_tokens": amounts,
        "window_open": w_open, "window_close": w_close,
        "tokens_per_step": final["tokens_per_step"],
        "input_waits": final["input_waits"], "losses": final["losses"],
        "report_waits": final["report_waits"],
        "probe_reports": final["probe_reports"],
        "attempted": len(inside), "failed": 0 if finite else 1,
        "setup_s": w_open - t_process,
        "trainer_ready_s": reports[0] - t_fit,
        "device_report": {**final["device"],
                          "memory_peak_bytes": final["memory_peak_bytes"]},
        "trace": final["trace"], "trace_steps": final["trace_steps"],
        # tracing stalls the loop for seconds: rates in a traced run are read
        # before the profiler starts
        "rate_until": (final["trace_at"] or [None])[0],
        "check": {**final["check"], "all_losses_finite": finite},
        "check_limits": traffic.get("check", {}).get("limits", {}),
    }
    # the metric is the first-to-last rate: every token and every second of
    # the window. The median of the intervals' rates beside it leaves one
    # stall out, so the two apart say "a stall", the two alike "a slower step"
    rates = train_token_rate.both(ctx)
    if rates:
        print(f"train rate tokens/s/chip: first to last {rates[0]:.3f}, "
              f"median of intervals {rates[1]:.3f}", file=sys.stderr)
    return ctx
