"""The control plane (ISSUE r06): batched submission, windowed actor calls,
pushed completions, inline small results — and the ray_perf smoke
invocation."""

import json
import os
import subprocess
import sys
import time

import pytest

import ray_tpu
from ray_tpu.cluster import Cluster
from ray_tpu.core import serialization
from ray_tpu.core.config import inline_max_bytes
from ray_tpu.core.worker import global_worker


@pytest.fixture(scope="module")
def pipe_cluster():
    c = Cluster(initialize_head=True, head_node_args={"num_cpus": 2})
    c.add_node(num_cpus=1, resources={"away": 1.0})
    ray_tpu.init(address=c.gcs_address)
    yield c
    ray_tpu.shutdown()
    c.shutdown()


def _runtime():
    return global_worker().runtime


# --------------------------------------------------------------- submission
def test_batch_flush_on_size(pipe_cluster):
    """A burst of submissions coalesces into far fewer submit_task_batch
    RPCs than tasks (size-triggered flushes)."""
    @ray_tpu.remote
    def nop(i):
        return i

    rt = _runtime()
    before_batches = rt.submit_batches_sent
    before_tasks = rt.tasks_submitted
    n = 200
    refs = [nop.remote(i) for i in range(n)]
    assert ray_tpu.get(refs, timeout=120) == list(range(n))
    sent = rt.submit_batches_sent - before_batches
    assert rt.tasks_submitted - before_tasks == n
    assert 0 < sent < n, f"expected coalescing, got {sent} batches for {n} tasks"


def test_batch_flush_on_timer(pipe_cluster):
    """A single buffered spec flushes on the ~1 ms window timer (nothing else
    forces it out) and the task completes promptly."""
    @ray_tpu.remote
    def one():
        return 41

    rt = _runtime()
    before = rt.submit_batches_sent
    ref = one.remote()
    # no get() yet: only the timer can flush this lone spec
    deadline = time.monotonic() + 5.0
    while rt.submit_batches_sent == before and time.monotonic() < deadline:
        time.sleep(0.005)
    assert rt.submit_batches_sent > before, "window timer never flushed"
    assert ray_tpu.get(ref, timeout=60) == 41


# ------------------------------------------------------------- actor calls
def test_out_of_order_actor_completions(pipe_cluster):
    """Windowed pipelining: later calls may complete first; every completion
    must resolve ITS OWN ObjectRef."""
    @ray_tpu.remote(max_concurrency=4)
    class Sleeper:
        def echo(self, i, delay):
            time.sleep(delay)
            return i

    a = Sleeper.remote()
    # earlier submissions sleep longest -> completions arrive reversed
    refs = [a.echo.remote(i, 0.3 - i * 0.07) for i in range(4)]
    assert ray_tpu.get(refs, timeout=60) == [0, 1, 2, 3]


def test_ordered_actor_preserves_submission_order(pipe_cluster):
    """max_concurrency=1 actors execute pipelined calls in submission order
    (seq gate on the worker)."""
    @ray_tpu.remote
    class Accum:
        def __init__(self):
            self.log = []

        def add(self, i):
            self.log.append(i)
            return list(self.log)

    a = Accum.remote()
    refs = [a.add.remote(i) for i in range(20)]
    out = ray_tpu.get(refs, timeout=60)
    assert out[-1] == list(range(20))
    for i, snapshot in enumerate(out):
        assert snapshot == list(range(i + 1))


# ------------------------------------------------------------ inline results
def _payload_of_exact_size(target: int) -> bytes:
    """bytes value whose SERIALIZED payload is exactly `target` bytes."""
    n = max(0, target - 16)
    while True:
        size = len(serialization.pack(b"x" * n)[0])
        if size == target:
            return b"x" * n
        n += target - size
        assert n >= 0


def test_inline_result_round_trip_thresholds(pipe_cluster):
    """0-byte, exactly-threshold and threshold+1 payloads all round-trip;
    at-most-threshold results are served from the inline cache (no arena),
    bigger ones via the store."""
    limit = inline_max_bytes()

    @ray_tpu.remote
    class Echo:
        def echo(self, v):
            return v

    a = Echo.remote()
    rt = _runtime()

    exact = _payload_of_exact_size(limit)
    over = _payload_of_exact_size(limit + 1)
    for value, want_inline in ((b"", True), (exact, True), (over, False)):
        ref = a.echo.remote(value)
        assert ray_tpu.get(ref, timeout=60) == value
        cached = ref.id.hex() in rt._inline_cache
        assert cached == want_inline, (
            f"payload of serialized size {len(serialization.pack(value)[0])} "
            f"(limit {limit}): inline-cached={cached}, want {want_inline}")


def test_inline_ref_passed_as_dependency(pipe_cluster):
    """An inline-only actor result used as a task argument is promoted to
    the cluster store first, so the consumer resolves it."""
    @ray_tpu.remote
    class Maker:
        def make(self):
            return 1234

    @ray_tpu.remote
    def consume(v):
        return v + 1

    a = Maker.remote()
    inner = a.make.remote()
    assert ray_tpu.get(inner, timeout=60) == 1234
    assert inner.id.hex() in _runtime()._inline_cache  # served inline
    assert ray_tpu.get(consume.remote(inner), timeout=60) == 1235


def test_inline_error_round_trip(pipe_cluster):
    @ray_tpu.remote
    class Bad:
        def boom(self):
            raise ValueError("inline boom")

    a = Bad.remote()
    with pytest.raises(ValueError, match="inline boom"):
        ray_tpu.get(a.boom.remote(), timeout=60)


# ---------------------------------------------------------- push completions
def test_push_wait_wakes_on_remote_seal(pipe_cluster):
    """wait() on a task running on ANOTHER node wakes via the pushed seal
    event (holder channel) shortly after the remote seal."""
    @ray_tpu.remote(resources={"away": 1.0})
    def slowly():
        time.sleep(0.4)
        return "done"

    ref = slowly.remote()
    t0 = time.monotonic()
    ready, not_ready = ray_tpu.wait([ref], timeout=30)
    elapsed = time.monotonic() - t0
    assert len(ready) == 1 and not not_ready
    assert elapsed < 15, f"wait took {elapsed:.1f}s"
    assert ray_tpu.get(ref, timeout=60) == "done"


def test_get_resolves_remote_task_via_push(pipe_cluster):
    """get() on remote-node results: the pushed seal (with inline payload)
    resolves it without an arena read on the remote node's store."""
    @ray_tpu.remote(resources={"away": 1.0})
    def tiny(i):
        return {"i": i}

    refs = [tiny.remote(i) for i in range(8)]
    assert ray_tpu.get(refs, timeout=120) == [{"i": i} for i in range(8)]


# ----------------------------------------------------------------- tooling
def test_ray_perf_cluster_smoke():
    """Fast smoke of the perf harness itself (satellite: CI-attributable
    perf): every metric line parses and is positive."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "ray_perf.py"),
         "--cluster", "--smoke"],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    metrics = {}
    for line in proc.stdout.splitlines():
        line = line.strip()
        if line.startswith("{"):
            rec = json.loads(line)
            metrics[rec["metric"]] = rec["value"]
    for key in ("cluster_tasks_per_sec", "cluster_actor_calls_per_sec",
                "cluster_puts_per_sec", "cluster_batched_get_per_sec"):
        assert metrics.get(key, 0) > 0, metrics
