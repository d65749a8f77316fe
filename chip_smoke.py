"""chip_smoke.py: the quickest proof that Serve and Train still start on the chip.

    python chip_smoke.py             # one TPU chip, as the driver runs it
    python chip_smoke.py --chips 4   # only what exists across four chips
    python chip_smoke.py --rehearse  # same control flow, tiny model, CPU

Drives the two paths a user takes, through the cluster runtime, at the full
width of ``LlamaConfig.llama_1b``: a ``TpuTrainer`` job fed by a Data
pipeline, then an ``LLMDeployment`` behind ``serve.run`` and the HTTP proxy.
This process starts a one-node cluster (``ray_tpu.cluster.Cluster`` +
``ray_tpu.init(address=...)``) and never initialises a jax backend itself:
a chip belongs to one process at a time, and here that process is always a
dedicated TPU worker leased by the node agent.

Each phase prints one JSON line. The last line of stdout is
``{"ok": true, "device": {...}}`` with the device as jax reports it inside
the worker, or ``{"ok": false, "error": ...}`` and a non-zero exit. There is
no CPU branch: without a chip the resources phase fails. ``--rehearse`` is
the guide's chip-less rehearsal (fake chips on the CPU backend, interpret
mode kernels); it names the device it found and can never say ``tpu``.

Values printed here (TTFT, latencies, step times) are smoke values from a
handful of requests and steps, not benchmark numbers.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import signal
import sys
import threading
import time

FULL = dict(
    model="llama_1b", batch=8, seq=2048, warmup=3, steps=5, attn="flash",
    num_slots=64, decode_chunk=32, max_seq_len=2048, total_pages=64 * 8 + 1,
    prompt_lens=(32, 256), new_tokens=32,
)
# the same control flow at a size the CPU backend and the Pallas interpreter
# finish in a minute or two
TINY = dict(
    model="tiny", batch=8, seq=128, warmup=1, steps=2, attn="flash_interpret",
    num_slots=4, decode_chunk=4, max_seq_len=128, total_pages=None,
    prompt_lens=(8, 32), new_tokens=8,
)
MULTI_CHIP_STEPS = 3
WAIT_S = 600.0        # any single wait on the cluster
DEADLINE_S = 1100.0   # the whole script (the driver allows 1200)


class SmokeFailure(Exception):
    pass


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, "ok": True, **fields}), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# --------------------------------------------------------------------------- #
# Code that runs inside dedicated TPU workers (pickled by value from here)
# --------------------------------------------------------------------------- #
class ChipProbe:
    """A ``num_tpus=1`` task or actor: what does a leased worker really hold?"""

    def report(self, previous_holder=None):
        import jax.numpy as jnp

        from ray_tpu.utils.device_report import device_report

        x = jnp.ones((1024, 1024), jnp.bfloat16)
        first = float((x @ x)[0, 0])  # a program ran on whatever this is
        files = set()
        for fd in os.listdir("/proc/self/fd"):
            try:
                target = os.readlink(f"/proc/self/fd/{fd}")
            except OSError:
                continue
            if target.startswith(("/dev/accel", "/dev/vfio/")) \
                    and target[-1].isdigit():
                files.add(target)
        return {
            **device_report(),
            "matmul_first": first,
            "previous_holder_alive": previous_holder is not None
            and os.path.exists(f"/proc/{previous_holder}"),
            "worker_chips": os.environ.get("RAY_TPU_WORKER_CHIPS"),
            "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
            "device_files": sorted(files),
        }


def train_loop(cfg):
    """TpuTrainer's train_loop_per_worker. One ``train.report`` per step."""
    import dataclasses

    import jax
    import optax

    from ray_tpu import train
    from ray_tpu.models.llama import LlamaConfig, llama_loss
    from ray_tpu.parallel.mesh import MeshConfig, batch_sharding_spec, make_mesh
    from ray_tpu.train.session import get_dataset_shard
    from ray_tpu.train.step import (
        default_optimizer, make_train_state_factory, make_train_step,
    )
    from ray_tpu.utils.device_report import device_report

    config = getattr(LlamaConfig, cfg["model"])(
        max_seq_len=cfg["seq"], remat="save_attn", attention_impl=cfg["attn"])
    mesh = batch_sh = None
    if cfg["fsdp"] > 1:
        mesh = make_mesh(MeshConfig(fsdp=cfg["fsdp"]))
        batch_sh = jax.sharding.NamedSharding(mesh, batch_sharding_spec())
    opt = default_optimizer(warmup_steps=10, total_steps=1000)
    state = make_train_state_factory(config, opt, mesh=mesh)(
        jax.random.key(cfg["seed"]))
    step = make_train_step(config, opt, mesh=mesh)
    jax.block_until_ready(state)
    first_report = {"device": device_report(), "placement": None}
    if mesh is not None:
        # code that has never run on more than one chip may put everything
        # on the first: every leaf must be split over all the mesh's devices
        bad = []
        for path, leaf in jax.tree_util.tree_flatten_with_path(state.params)[0]:
            shards = leaf.addressable_shards
            ids = {s.device.id for s in shards}
            if len(ids) != cfg["fsdp"] or \
                    sum(s.data.size for s in shards) != leaf.size:
                bad.append(jax.tree_util.keystr(path))
        first_report["placement"] = {
            "leaves": len(jax.tree.leaves(state.params)), "not_split": bad}

    def loss_and_gnorm(impl):
        c = dataclasses.replace(config, attention_impl=impl)

        def f(params, tokens, targets):
            loss, grads = jax.value_and_grad(
                lambda p: llama_loss(p, tokens, targets, c))(params)
            return loss, optax.global_norm(grads)

        return jax.jit(f)

    batches = get_dataset_shard("train").iter_jax_batches(
        batch_size=cfg["batch"], sharding=batch_sh)
    for i, batch in enumerate(batches, start=1):
        tokens, targets = batch["tokens"], batch["targets"]
        metrics = {"step": i}
        if i == 1:
            metrics.update(first_report)
            if cfg["check_reference"]:
                # the only check that the Mosaic kernels COMPUTE the right
                # thing at real shapes; two rows, so the reference's S x S
                # scores fit beside the train state
                pair = {}
                for name, impl in (("flash", cfg["attn"]),
                                   ("reference", "reference")):
                    loss, gnorm = loss_and_gnorm(impl)(
                        state.params, tokens[:2], targets[:2])
                    pair[name] = {"loss": float(loss), "grad_norm": float(gnorm)}
                metrics["attention_check"] = pair
        t0 = time.perf_counter()
        state, out = step(state, tokens, targets)
        jax.block_until_ready(out)
        t1 = time.perf_counter()
        host = jax.device_get(out)
        t2 = time.perf_counter()
        metrics.update(
            loss=float(host["loss"]), grad_norm=float(host["grad_norm"]),
            step_s=t1 - t0,
            # if block_until_ready really waited, the fetch after it is free
            device_get_after_sync_s=t2 - t1)
        last = i == cfg["num_steps"]
        if last:
            metrics["device_end"] = device_report()
        train.report(metrics)
        if last:
            return


def make_smoke_llm():
    from ray_tpu.serve.llm import LLMDeployment

    class SmokeLLM(LLMDeployment):
        """LLMDeployment plus the one thing only the chip's holder can
        compute: the reference model's answer on the same weights."""

        def reference_first_token(self, request):
            import dataclasses

            import jax
            import jax.numpy as jnp

            from ray_tpu.models.llama import llama_forward

            cfg = dataclasses.replace(
                self.engine.config, attention_impl="reference", remat=None)
            tokens = jnp.asarray([request["tokens"]], jnp.int32)
            last = jax.jit(lambda p, t: llama_forward(p, t, cfg)[0, -1])(
                self.engine.params, tokens)
            last = jax.device_get(last)
            return {"token": int(last.argmax()), "max_logit": float(last.max()),
                    "logit_of_got": float(last[int(request["got"])])}

    return SmokeLLM


# --------------------------------------------------------------------------- #
# Phases (driver side; no jax backend here)
# --------------------------------------------------------------------------- #
def phase_store(cluster) -> None:
    from ray_tpu import _native
    from ray_tpu.core.rpc import SyncRpcClient

    check(_native.available(),
          "librtpu_native.so could not be built from arena.cc / channel.cc "
          "(see the warning above); the arena store is not available")
    agent = SyncRpcClient(cluster.nodes[0].address)
    try:
        backend = agent.call("node_info")["store"]["backend"]
    finally:
        agent.close()
    check(backend == "arena", f"object store backend is {backend!r}, not arena")
    emit("store", native_library="built from source", backend=backend)


def probe_actors(ray_tpu, n: int, rehearse: bool, previous_holder=None):
    """n ``num_tpus=1`` actors alive at once; returns their reports."""
    # its own runtime env: the agent may not hand it a warm worker of
    # another env, it has to evict that one and wait for the chip
    Probe = ray_tpu.remote(
        num_tpus=1, runtime_env={"env_vars": {"CHIP_SMOKE_PROBE": "actor"}},
    )(ChipProbe)
    actors = [Probe.remote() for _ in range(n)]
    try:
        reports = ray_tpu.get(
            [a.report.remote(previous_holder) for a in actors], timeout=WAIT_S)
    finally:
        for a in actors:
            ray_tpu.kill(a)
    for r in reports:
        check(r["pid"] != os.getpid(), "probe ran in the driver process")
        if rehearse:
            check(r["platform"] != "tpu", "rehearsal reached a real TPU")
        else:
            check(r["platform"] == "tpu" and r["count"] == 1,
                  f"a num_tpus=1 actor sees {r['count']} x {r['platform']}")
    return reports


def phase_resources(ray_tpu, chips: int, rehearse: bool):
    tpus = ray_tpu.cluster_resources().get("TPU", 0)
    check(tpus == chips,
          f"cluster_resources()['TPU'] is {tpus}, expected {chips} "
          "(accelerators.detect_num_chips counts /dev/accel* and /dev/vfio/N, "
          "and nothing when JAX_PLATFORMS=cpu)")
    # a TPU task leaves its dedicated worker idle, warm and holding the
    # chip. The actor after it needs that chip in a new process: the agent
    # must evict the idle worker AND wait until it is gone, because SIGKILL
    # is not instant for a process inside libtpu
    task = ray_tpu.remote(num_tpus=1)(lambda: ChipProbe().report())
    first = ray_tpu.get(task.remote(), timeout=WAIT_S)
    (r,) = probe_actors(ray_tpu, 1, rehearse, previous_holder=first["pid"])
    check(r["pid"] != first["pid"], "a worker of another env was reused")
    if chips == 1:  # with more chips the actor takes a free one instead
        check(not r["previous_holder_alive"],
              f"worker {first['pid']} still existed when {r['pid']} opened "
              "the chip")
        check(r["count"] == tpus,
              f"agent counted {tpus} chip(s), jax in the worker sees {r['count']}")
    emit("resources", cluster_tpus=tpus, task_worker_pid=first["pid"],
         worker_pid=r["pid"],
         evicted_holder_gone_before_reopen=not r["previous_holder_alive"],
         platform=r["platform"], kind=r["kind"], devices_in_worker=r["count"],
         device_files=r["device_files"])
    return r


def vocab_size(size) -> int:
    from ray_tpu.models.llama import LlamaConfig

    return getattr(LlamaConfig, size["model"])().vocab_size


def token_dataset(size, rows: int, seed: int):
    import numpy as np

    import ray_tpu.data as rd

    seqs = np.random.default_rng(seed).integers(
        0, vocab_size(size), (rows, size["seq"] + 1), dtype=np.int32)
    return rd.from_numpy({"tokens": seqs[:, :-1], "targets": seqs[:, 1:]})


def run_train(size, rehearse: bool, *, tpus: int, num_steps: int,
              check_reference: bool, seed: int, name: str):
    from ray_tpu.train import RunConfig, ScalingConfig, TpuTrainer

    ds = token_dataset(size, rows=size["batch"] * num_steps, seed=seed)
    vocab = vocab_size(size)
    trainer = TpuTrainer(
        train_loop,
        train_loop_config=dict(
            model=size["model"], seq=size["seq"], batch=size["batch"],
            attn=size["attn"], fsdp=tpus, seed=seed, num_steps=num_steps,
            check_reference=check_reference),
        scaling_config=ScalingConfig(num_workers=1, use_tpu=True,
                                     tpus_per_worker=tpus),
        run_config=RunConfig(name=name, storage_path=os.path.join(
            os.environ["RAY_TPU_SESSION_DIR"], "train_results")),
        datasets={"train": ds},
    )
    result = trainer.fit()
    if result.error is not None:
        raise SmokeFailure(f"{name}: training failed: {result.error!r}")
    hist = result.metrics_history
    check(len(hist) == num_steps,
          f"{name}: {len(hist)} reports for {num_steps} steps")
    losses = [m["loss"] for m in hist]
    check(all(math.isfinite(x) for x in losses), f"{name}: losses {losses}")
    # llama_init gives unit-variance logits (unit-rms hidden x N(0, 1/h)
    # head), and E[logsumexp - gold] over V such logits is ln V + var/2
    expected = math.log(vocab) + 0.5
    check(abs(losses[0] - expected) < 0.5,
          f"{name}: first loss {losses[0]:.3f} is not within 0.5 of "
          f"ln({vocab}) + 0.5 = {expected:.3f}")
    device = hist[0]["device"]
    check(device["pid"] != os.getpid(), f"{name}: trained in the driver")
    if not rehearse:  # fake chips cannot narrow what the CPU backend shows
        check(device["platform"] == "tpu",
              f"{name}: trained on {device['platform']}")
        check(device["count"] == tpus,
              f"{name}: worker holds {device['count']} devices, asked for {tpus}")
    return hist


def phase_train(size, rehearse: bool, seed: int):
    num_steps = size["warmup"] + size["steps"]
    hist = run_train(size, rehearse, tpus=1, num_steps=num_steps,
                     check_reference=True, seed=seed, name="smoke_train")
    pair = hist[0]["attention_check"]
    dl = abs(pair["flash"]["loss"] - pair["reference"]["loss"])
    dg = abs(pair["flash"]["grad_norm"] - pair["reference"]["grad_norm"]) \
        / max(pair["reference"]["grad_norm"], 1e-9)
    # bf16: 8 bits of mantissa in every attention output; the loss averages
    # thousands of tokens, the gradient norm does not average as kindly
    check(dl < 2e-2 and dg < 3e-2,
          f"flash and reference attention disagree: {pair}")
    device, end = hist[0]["device"], hist[-1]["device_end"]
    steady = hist[size["warmup"]:]
    emit("train", worker_pid=device["pid"], platform=device["platform"],
         kind=device["kind"], model=size["model"], batch=size["batch"],
         seq=size["seq"], losses=[round(m["loss"], 4) for m in hist],
         attention_check=pair,
         smoke_step_s=[round(m["step_s"], 4) for m in steady],
         smoke_device_get_after_sync_s=[
             round(m["device_get_after_sync_s"], 5) for m in steady],
         state_bytes_in_use=device["devices"][0]["bytes_in_use"],
         peak_bytes_in_use=end["devices"][0]["peak_bytes_in_use"],
         compile_cache=end["compile_cache"])
    return device


def _post(address: str, path: str, body: dict, stream: bool):
    """One request over the HTTP proxy. Returns (records, first_record_s,
    total_s); a stream=True deployment answers in ndjson chunks."""
    import http.client

    host, port = address.replace("http://", "").split(":")
    conn = http.client.HTTPConnection(host, int(port), timeout=WAIT_S)
    try:
        t0 = time.perf_counter()
        conn.request("POST", path, body=json.dumps(body),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            raise SmokeFailure(f"HTTP {resp.status}: {resp.read()[:2000]!r}")
        records, first = [], None
        if stream:
            while True:
                line = resp.readline()
                if not line:
                    break
                if first is None:
                    first = time.perf_counter() - t0
                records.append(json.loads(line))
        else:
            records = [json.loads(line) for line in resp.read().splitlines()]
            first = time.perf_counter() - t0
        return records, first, time.perf_counter() - t0
    finally:
        conn.close()


def phase_serve(ray_tpu, size, rehearse: bool, seed: int) -> None:
    import numpy as np

    from ray_tpu import serve

    vocab = vocab_size(size)
    app = serve.deployment(
        make_smoke_llm(), name="llm", stream=True, max_ongoing_requests=16,
        ray_actor_options={"num_tpus": 1},
    ).bind(model=size["model"], num_slots=size["num_slots"],
           decode_chunk=size["decode_chunk"], max_seq_len=size["max_seq_len"],
           total_pages=size["total_pages"])
    t0 = time.perf_counter()
    # the train worker must be gone before this replica can open the chip:
    # the agent hands the chip over only once the old holder has exited, and
    # this wait is bounded
    handle = serve.run(app, name="llm", http_port=0, timeout=WAIT_S)
    ready_s = time.perf_counter() - t0
    address = serve.http_address()
    rng = np.random.default_rng(seed)
    lo, hi = size["prompt_lens"]
    lens = [lo, hi] + [int(x) for x in rng.integers(lo, hi + 1, 5)]
    prompts = [rng.integers(1, vocab, n).tolist() for n in lens]
    prompts.append(prompts[0])  # one prompt sent twice
    new = size["new_tokens"]

    def body(p, stream=False):
        return {"tokens": p, "max_tokens": new, "stream": stream,
                "timeout": WAIT_S}

    # compile outside the measured requests: one per prefill bucket in play
    t0 = time.perf_counter()
    for p in (prompts[0], prompts[1]):
        _post(address, "/llm", body(p), stream=False)
    warm_s = time.perf_counter() - t0

    results = [None] * len(prompts)
    errors = []

    def client(i):
        try:
            results[i] = _post(address, "/llm", body(prompts[i], stream=i == 1),
                               stream=i == 1)
        except Exception as e:  # noqa: BLE001 - reported below
            errors.append(f"request {i}: {type(e).__name__}: {e}")

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(WAIT_S)
    check(not errors and all(r is not None for r in results),
          f"requests failed or hung: {errors}")
    answers, ttfts, latencies = [], [], []
    for i, (records, first_s, total_s) in enumerate(results):
        if i == 1:  # streamed: token records, then the done record
            toks = [r["token"] for r in records if "token" in r]
            done = records[-1]
            check(done.get("done") and done["num_tokens"] == len(toks),
                  f"stream ended with {done}")
            ttfts.append(done["ttft_s"])
        else:
            (rec,) = records
            toks = rec["tokens"]
            ttfts.append(rec["ttft_s"])
        check(len(toks) == new, f"request {i}: {len(toks)} tokens, not {new}")
        check(all(0 <= t < vocab for t in toks), f"request {i}: bad token ids")
        answers.append(toks)
        latencies.append(total_s)
    check(answers[0] == answers[-1],
          "the same prompt, sent twice, got different greedy tokens")
    ref = handle.reference_first_token.remote(
        {"tokens": prompts[2], "got": answers[2][0]}).result(timeout=WAIT_S)
    # equal, or a tie that bf16 rounding decides: padding to the prefill
    # bucket and the kernel's block order move a logit by its last bits
    check(ref["token"] == answers[2][0]
          or ref["max_logit"] - ref["logit_of_got"] < 0.05,
          f"first token {answers[2][0]} vs reference {ref}")
    report = handle.runtime_report.remote().result(timeout=WAIT_S)
    check(report["pid"] != os.getpid(), "the replica is the driver process")
    if rehearse:
        check(report["platform"] != "tpu", "rehearsal reached a real TPU")
    else:
        check(report["platform"] == "tpu", f"served on {report['platform']}")
        check(report["decode_attention"] == "pallas_paged"
              and report["decode_kernel_calls"] > 0,
              "the paged decode program holds no Pallas kernel: "
              f"{report['decode_attention']}, "
              f"{report['decode_kernel_calls']} tpu_custom_call")
    emit("serve", replica_pid=report["pid"], platform=report["platform"],
         kind=report["kind"], model=size["model"], requests=len(prompts),
         new_tokens=new, prompt_lens=[len(p) for p in prompts],
         decode_attention=report["decode_attention"],
         decode_kernel_calls=report["decode_kernel_calls"],
         first_token={"got": answers[2][0], "reference": ref},
         replica_ready_s=round(ready_s, 2), warmup_compile_s=round(warm_s, 2),
         smoke_ttft_s=[round(x, 4) for x in ttfts],
         smoke_latency_s=[round(x, 4) for x in latencies],
         smoke_stream_first_chunk_s=round(results[1][1], 4),
         peak_bytes_in_use=report["devices"][0]["peak_bytes_in_use"],
         compile_cache=report["compile_cache"])
    serve.shutdown()


def phase_multichip(ray_tpu, size, rehearse: bool, seed: int):
    """Only what exists across chips, and what it is compared with."""
    n = 4
    one = run_train(size, rehearse, tpus=1, num_steps=MULTI_CHIP_STEPS,
                    check_reference=False, seed=seed, name="smoke_train_1chip")
    four = run_train(size, rehearse, tpus=n, num_steps=MULTI_CHIP_STEPS,
                     check_reference=False, seed=seed, name="smoke_train_fsdp4")
    l1 = [m["loss"] for m in one]
    l4 = [m["loss"] for m in four]
    check(all(abs(a - b) < 5e-2 for a, b in zip(l1, l4)),
          f"fsdp=4 losses {l4} differ from the one-chip losses {l1}")
    placement = four[0]["placement"]
    check(placement is not None and not placement["not_split"],
          f"parameters not split over {n} devices: {placement}")
    dev1, dev4 = one[0]["device"], four[0]["device"]
    if not rehearse:
        whole = dev1["devices"][0]["bytes_in_use"]
        parts = [d["bytes_in_use"] for d in dev4["devices"]]
        check(all(0.2 * whole < p < 0.35 * whole for p in parts),
              f"per-device state {parts} is not about a quarter of {whole}")
    emit("train_fsdp4", worker_pid=dev4["pid"], platform=dev4["platform"],
         kind=dev4["kind"], devices_in_worker=dev4["count"],
         losses_fsdp4=[round(x, 4) for x in l4],
         losses_one_chip=[round(x, 4) for x in l1],
         params_split_over=n, param_leaves=placement["leaves"],
         state_bytes_one_chip=dev1["devices"][0]["bytes_in_use"],
         state_bytes_per_device=[d["bytes_in_use"] for d in dev4["devices"]],
         smoke_step_s_fsdp4=[round(m["step_s"], 4) for m in four],
         smoke_step_s_one_chip=[round(m["step_s"], 4) for m in one])
    reports = probe_actors(ray_tpu, n, rehearse)
    leased = [r["worker_chips"] for r in reports]
    check(len(set(leased)) == n, f"leased chips overlap: {leased}")
    check(len({r["pid"] for r in reports}) == n, "actors share a process")
    if not rehearse:
        files = [tuple(r["device_files"]) for r in reports]
        check(all(len(f) == 1 for f in files) and len(set(files)) == n,
              f"actors do not hold {n} distinct device files: {files}")
    emit("four_actors", pids=[r["pid"] for r in reports], leased_chips=leased,
         visible_chips=[r["visible_chips"] for r in reports],
         device_files=[r["device_files"] for r in reports],
         device_ids=[[d["id"] for d in r["devices"]] for r in reports],
         devices_per_actor=[r["count"] for r in reports])
    return dev4


# --------------------------------------------------------------------------- #
def run(args) -> dict:
    import ray_tpu
    from ray_tpu.cluster import Cluster

    size = TINY if args.rehearse else FULL
    cluster = Cluster(initialize_head=True,
                      head_node_args={"num_cpus": max(8, os.cpu_count() or 8)})
    try:
        os.environ["RAY_TPU_SESSION_DIR"] = cluster.session_dir
        ray_tpu.init(address=cluster.gcs_address)
        phase_store(cluster)
        phase_resources(ray_tpu, args.chips, args.rehearse)
        if args.chips == 1:
            device = phase_train(size, args.rehearse, args.seed)
            phase_serve(ray_tpu, size, args.rehearse, args.seed)
        else:
            device = phase_multichip(ray_tpu, size, args.rehearse, args.seed)
    finally:
        try:
            ray_tpu.shutdown()
        finally:
            cluster.shutdown()
    return {"platform": device["platform"], "kind": device["kind"],
            "count": device["count"]}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--rehearse", action="store_true",
                        help="tiny model on the CPU backend with fake chips")
    args = parser.parse_args(argv)
    if args.rehearse:
        # before anything spawns: every process of the rehearsal inherits it
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["RAY_TPU_FAKE_TPU_CHIPS"] = str(args.chips)
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.chips}").strip()

    def on_deadline(signum, frame):
        raise SmokeFailure(f"not finished after {DEADLINE_S:.0f} s")

    signal.signal(signal.SIGALRM, on_deadline)
    signal.setitimer(signal.ITIMER_REAL, DEADLINE_S)
    t0 = time.perf_counter()
    try:
        device = run(args)
        jax = sys.modules.get("jax")
        if jax is not None:
            from jax._src import xla_bridge

            check(not xla_bridge.backends_are_initialized(),
                  "the driver process initialised a jax backend")
        if args.rehearse:
            check(device["platform"] != "tpu", "rehearsal reached a real TPU")
    except BaseException as e:  # noqa: BLE001 - every failure ends in one line
        import traceback

        traceback.print_exc()
        print(json.dumps({"ok": False,
                          "error": f"{type(e).__name__}: {e}"[:2000]}),
              flush=True)
        return 1
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
    print(json.dumps({"phase": "done", "ok": True, "driver_pid": os.getpid(),
                      "driver_jax_backend_initialised": False,
                      "wall_s": round(time.perf_counter() - t0, 1)}),
          flush=True)
    last = {"ok": True, "device": device}
    if args.rehearse:
        last["rehearsal"] = True
    print(json.dumps(last), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
