"""User profile events: spans that land on the cluster timeline.

Reference capability: src/ray/core_worker/profile_event.{h,cc} +
python/ray/_private/profiling.py:20-40 — `with ray.profiling.profile("x"):`
inside a task records a span shipped to the observability backend and
rendered by `ray timeline`. Here: spans buffer thread-locally in the
worker, flush to the node agent when the task finishes (one RPC only when
profiling was used), and the dashboard's /api/timeline merges them as
cat="user" chrome-trace events next to the task-state spans.

    import ray_tpu

    @ray_tpu.remote
    def step():
        with ray_tpu.profile("load"):
            ...
        with ray_tpu.profile("compute", extra={"batch": 8}):
            ...

``span`` is the one primitive for spans on the DEVICE trace's clock (the
engine loop, the data feed, ``train.report``): it writes into a running
``jax.profiler`` trace and nowhere else. ``profile`` enters it too, so user
spans reach a device trace as well as the dashboard. ``host_events`` counts
what can stop a host thread from outside: compiles and garbage collections.
"""

from __future__ import annotations

import gc
import sys
import threading
import time
from contextlib import contextmanager, nullcontext
from typing import Any, Dict, List, Optional

# process-wide buffer: async actor methods record on the event-loop thread
# while the flush runs on an executor thread, so the buffer must NOT be
# thread-local. Bounded: an unflushed producer (local runtime, long-lived
# profiling loop) can't grow memory without limit.
_MAX_PENDING = 20000
_spans: List[Dict[str, Any]] = []
_lock = threading.Lock()
# local-runtime sink (no agent to ship to): bounded in-process span log
_local_runtime_spans: List[Dict[str, Any]] = []


_NULL_SPAN = nullcontext()
_annotation = None  # jax.profiler.TraceAnnotation, once jax is in the process


def span(name: str, **ints: int):
    """A host span in a running ``jax.profiler`` trace (the device trace's
    clock): ``with span("engine.admit"): ...``. Fixed-string names, integer
    attributes. With no trace running the annotation records nothing; in a
    process that never imported jax (the proxy) this is one shared null
    context and jax stays unimported."""
    global _annotation
    if _annotation is None:
        jax = sys.modules.get("jax")
        profiler = getattr(jax, "profiler", None)
        if profiler is None:
            return _NULL_SPAN
        _annotation = profiler.TraceAnnotation
    return _annotation(name, **ints)


class HostEvents:
    """Process-wide counts of the two host-side events that stop a thread
    from outside its own code: XLA compiles (one ``jax.monitoring`` listener
    on the backend-compile event, which also covers a program fetched from
    the persistent cache) and garbage collections (one ``gc.callbacks``
    hook); and of the items this process's streaming calls yielded
    (``count_stream_item``), with how many of them went to the caller in a
    reply, past the agent and the GCS. Cumulative and monotone; read without
    a lock."""

    GC_LONG_NS = 50_000_000

    def __init__(self) -> None:
        self.compiles = 0
        self.compile_s = 0.0
        self.gc_pause_ns = 0
        self.gc_pauses_over_50ms = 0
        self.gc_longest_ns = 0
        self._gc_started_ns = 0
        self.stream_items = 0
        self.stream_items_inline = 0

    def _on_duration(self, event: str, duration_s: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration_s

    def _on_gc(self, phase: str, _info: Dict[str, Any]) -> None:
        if phase == "start":
            self._gc_started_ns = time.perf_counter_ns()
            return
        pause = time.perf_counter_ns() - self._gc_started_ns
        self.gc_pause_ns += pause
        if pause > self.GC_LONG_NS:
            self.gc_pauses_over_50ms += 1
        if pause > self.gc_longest_ns:
            self.gc_longest_ns = pause


_host_events = HostEvents()
_hooked = False


def host_events() -> HostEvents:
    """The process's ``HostEvents``, hooked in on first use (jax must be
    imported by then: the caller is about to compile with it)."""
    global _hooked
    with _lock:
        if not _hooked:
            import jax.monitoring

            jax.monitoring.register_event_duration_secs_listener(
                _host_events._on_duration)
            gc.callbacks.append(_host_events._on_gc)
            _hooked = True
    return _host_events


def count_stream_item(inline: bool) -> None:
    """One item yielded by a streaming call of this process (a worker calls
    this from many request threads, and none of them needs jax)."""
    with _lock:
        _host_events.stream_items += 1
        _host_events.stream_items_inline += inline


@contextmanager
def profile(name: str, extra: Optional[Dict[str, Any]] = None):
    """Record a named span for the cluster timeline (and, through ``span``,
    for a device trace that is running)."""
    start = time.time()
    try:
        with span(str(name)):
            yield
    finally:
        end = time.time()
        entry: Dict[str, Any] = {"name": str(name), "start": start, "end": end}
        if extra:
            entry["extra"] = {str(k): v for k, v in extra.items()}
        try:
            from ray_tpu.core.worker import global_worker

            w = global_worker()
            task_id = getattr(w, "current_task_id", None)
            if task_id is not None:
                entry["task_id"] = task_id.hex() if hasattr(task_id, "hex") \
                    else str(task_id)
        except Exception:  # noqa: BLE001 - outside a runtime
            pass
        with _lock:
            _spans.append(entry)
            del _spans[:-_MAX_PENDING]


def record_external_span(name: str, start: float, end: float,
                         extra: Optional[Dict[str, Any]] = None) -> None:
    """Record an already-timed span (the tracing bridge: util/tracing.py
    spans ride the same flush path to the agent/timeline)."""
    span: Dict[str, Any] = {"name": str(name), "start": start, "end": end}
    if extra:
        span["extra"] = {str(k): v for k, v in extra.items()}
    with _lock:
        _spans.append(span)
        del _spans[:-_MAX_PENDING]


def drain() -> List[Dict[str, Any]]:
    """Take (and clear) every recorded span (worker/local flush paths)."""
    global _spans
    with _lock:
        out, _spans = _spans, []
    return out


def flush_local() -> None:
    """Local-runtime sink: move pending spans into the in-process log
    (read back with local_spans(); there is no agent to ship to)."""
    spans = drain()
    if spans:
        with _lock:
            _local_runtime_spans.extend(spans)
            del _local_runtime_spans[:-_MAX_PENDING]


def local_spans() -> List[Dict[str, Any]]:
    with _lock:
        return list(_local_runtime_spans)
