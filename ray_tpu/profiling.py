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

Three primitives for the loops that feed the chip:

- ``span``: the one primitive for spans on the DEVICE trace's clock (the
  engine loop, the data feed, ``train.report``, the watch's own
  ``watch.stall``): it writes into a running ``jax.profiler`` trace and
  nowhere else. ``profile`` enters it too, so user spans reach a device trace
  as well as the dashboard.
- ``HostEvents`` (``host_events()``): counts of what can stop a host thread
  from outside: compiles and garbage collections.
- ``StallWatch`` (``stall_watch()``): ONE sampler thread a process, fed by
  the ``Heartbeat`` of each watched loop, which looks at the process WHILE a
  wait lasts. Who beats where: the engine (``serve/llm.py`` ``_step``) at its
  seven phase boundaries and once an idle poll; the train loop through its
  ``StepRing`` (below) where it takes a batch (``data/dataset.py``
  ``_device_prefetch``), around the call of the compiled step
  (``train/step.py`` ``make_train_step``) and inside ``train.report``
  (``train/session.py``: the put, and the wait for the controller).

``StepRing`` is the train loop's flight recorder (the engine keeps its own
ring of iterations): the last 256 steps by part, counters, and the slow-step
records; ``step_ring()`` is the calling thread's.
"""

from __future__ import annotations

import gc
import logging
import os
import resource
import sys
import threading
import time
from collections import deque
from contextlib import contextmanager, nullcontext
from typing import Any, Callable, Dict, List, Optional, Tuple

logger = logging.getLogger("ray_tpu.profiling")

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


# ------------------------------------------------------------- stall watch
_CLK_TCK = os.sysconf("SC_CLK_TCK")
_PRESSURES = ("cpu", "io", "memory")


def _read(path: str) -> bytes:
    """The head of a small ``/proc`` or ``/sys`` file; empty where it cannot
    be read."""
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return b""
    try:
        return os.read(fd, 8192)
    except OSError:
        return b""
    finally:
        os.close(fd)


def _number_after(text: bytes, key: bytes) -> int:
    """The integer that follows ``key`` in ``text``; 0 where there is none."""
    at = text.find(key)
    if at < 0:
        return 0
    try:
        return int(text[at + len(key):].split(None, 1)[0])
    except (IndexError, ValueError):
        return 0


def _stat_fields(text: bytes) -> List[bytes]:
    """The fields of a ``stat`` file from the third (the state) on: the
    second, the name, may hold spaces and brackets."""
    return text[text.rfind(b")") + 2:].split()


def cpu_times() -> Tuple[float, float]:
    """(steal, iowait) in seconds since boot, summed over this machine's CPUs
    (``/proc/stat``, line 1): what the hypervisor gave to others, and what
    the CPUs idled with I/O outstanding. Zeros where it is not told. The one
    reader of ``/proc/stat``."""
    fields = _read("/proc/stat").split(b"\n", 1)[0].split()
    try:
        return int(fields[8]) / _CLK_TCK, int(fields[5]) / _CLK_TCK
    except (IndexError, ValueError):
        return 0.0, 0.0


def _throttled_s() -> float:
    """Seconds the container's CPU quota has held its processes back."""
    return _number_after(_read("/sys/fs/cgroup/cpu.stat"), b"throttled_usec ") / 1e6


def _process_counters() -> Dict[str, float]:
    """What the process and its machine have counted so far: every value a
    number that only rises, so that two readings give what a wait took."""
    stat = _stat_fields(_read("/proc/self/stat"))
    io = _read("/proc/self/io")
    steal, iowait = cpu_times()
    out = {
        "minor_faults": int(stat[7]) if len(stat) > 9 else 0,
        "major_faults": int(stat[9]) if len(stat) > 9 else 0,
        "read_bytes": _number_after(io, b"read_bytes: "),
        "write_bytes": _number_after(io, b"\nwrite_bytes: "),
        "steal_s": steal, "iowait_s": iowait, "throttled_s": _throttled_s(),
        "compiles": _host_events.compiles,
        "gc_s": _host_events.gc_pause_ns / 1e9,
    }
    for what in _PRESSURES:
        out[f"pressure_{what}_s"] = _number_after(
            _read("/proc/pressure/" + what), b"total=") / 1e6
    return out


def _born_ns() -> int:
    """The process's start on ``perf_counter_ns``'s clock (``/proc/self/stat``
    against ``/proc/uptime``; this call's instant where they cannot be read)."""
    now = time.perf_counter_ns()
    try:
        started = int(_stat_fields(_read("/proc/self/stat"))[19]) / _CLK_TCK
        return now - int((float(_read("/proc/uptime").split()[0]) - started) * 1e9)
    except (IndexError, ValueError):
        return now


def _frames(depth: int = 3) -> Dict[str, str]:
    """Where every Python thread stands, innermost frame first
    (``file:line function``), by thread name."""
    names = {t.ident: t.name for t in threading.enumerate()}
    out = {}
    for ident, frame in sys._current_frames().items():
        parts = []
        while frame is not None and len(parts) < depth:
            code = frame.f_code
            parts.append(f"{os.path.basename(code.co_filename)}:"
                         f"{frame.f_lineno} {code.co_name}")
            frame = frame.f_back
        out[names.get(ident, f"thread-{ident}")] = " < ".join(parts)
    return out


class Heartbeat:
    """One watched loop's pulse: the instant of its last ``beat`` and the
    phase it then entered, in two attributes that the loop thread writes and
    the watch reads with no lock between them. ``limit_ns`` is the loop's
    to publish: how old a beat may grow before the wait counts as a stall; 0,
    which it is until the loop knows its own pace, means never overdue.
    ``stats``, where the loop has any, is what ``StallWatch.loops`` shows
    of it."""

    __slots__ = ("name", "phases", "stats", "at_ns", "phase", "limit_ns",
                 "closed", "_watch", "_watched", "_given_up_at", "_seen_at",
                 "_seen_phase")

    def __init__(self, watch: "StallWatch", name: str, phases: Tuple[str, ...],
                 stats: Optional[Callable[[], Dict[str, Any]]] = None):
        self.name, self.phases, self.stats = name, phases, stats
        self.at_ns = self.phase = self.limit_ns = 0
        self._watch, self._watched, self._given_up_at = watch, False, 0
        self.closed = False
        self._seen_at = self._seen_phase = 0  # as the watch's last tick saw it

    def beat(self, phase: int, now_ns: int = 0) -> None:
        """The loop is alive and enters ``phase`` (an index into
        ``phases``), now or at the ``perf_counter_ns`` reading it holds."""
        self.phase = phase
        self.at_ns = now_ns or time.perf_counter_ns()
        if not self._watched:
            self._watch._register(self)

    def close(self) -> None:
        """The loop has ended: nothing waits for its next beat."""
        self.closed = True
        self._watch._forget(self)

    def in_flight(self) -> Optional[Dict[str, Any]]:
        """``{loop, phase, for_s}`` while the last beat is older than the
        limit, whether the watch still samples it or has given up; else
        None. Reckoned at the call, by the caller's thread."""
        limit, at = self.limit_ns, self.at_ns
        waited = time.perf_counter_ns() - at
        if self.closed or not limit or waited <= limit:
            return None
        return {"loop": self.name, "phase": self.phases[self.phase],
                "for_s": waited / 1e9}

    def describe(self) -> Dict[str, Any]:
        return {"phase": self.phases[self.phase], "limit_s": self.limit_ns / 1e9,
                "beat_age_s": (time.perf_counter_ns() - self.at_ns) / 1e9,
                "in_flight": self.in_flight(),
                **(self.stats() if self.stats is not None else {})}


class StallWatch:
    """The process's one sampler: a daemon thread ``stall-watch``, started by
    the first beat of the first ``Heartbeat`` (never at import, never in a
    constructor), that looks at the process WHILE a watched loop waits too
    long. It starts no profiler and writes nothing to disk: a stalled run
    reads the numbers it would have read without it.

    Idle, it wakes every ``TICK_S`` and does two things. (1) It notes its own
    LATENESS, how much later than asked it ran (wake to wake, less the wait
    asked for and its own CPU time between): the longest of each of the
    last ``LATE_SECONDS`` wall seconds is kept (``late_ring``), so that a
    reader can ask for a window long after it closed. A lateness over
    ``PAUSE_NS`` leaves a ``pause`` record: ``late_s``, the process's CPU time
    over it (``cpu_s``), how often the watch thread itself woke in it
    (``woke``: a thread that waits for the interpreter wakes once a switch
    interval, a thread of a stopped process not at all; None where the
    kernel counts no such switches, as the chip machine's sandbox does not:
    there a thread that keeps the interpreter ASLEEP reads like a stopped
    process), ``steal_s`` and
    ``throttled_s`` since the last reading (at most a second older), the
    Python frames on waking, and ``held``: the CPU time or the wake-ups cover
    over half the lateness, which says that a thread HELD THE INTERPRETER (the
    frames say which is there now); otherwise the process did not run at all
    (stopped, throttled, its CPUs stolen). (2) It compares each loop's last
    beat with the loop's limit.

    A wait that ended while the watch itself could not run (a stopped process
    wakes its loop and its watch together) is recorded all the same, from the
    beat the tick before had seen. While a beat is overdue it samples every
    ``SAMPLE_S`` (after
    ``FAST_SAMPLES_S`` once a second; after ``GIVE_UP_S`` it closes the record
    with ``ended: "gave_up"`` and watches that loop again from its next beat:
    a loop that stops stepping to evaluate or to save is not a stall for
    ever), inside ``span("watch.stall")``: every native thread's name, state
    and CPU time (``/proc/self/task/*/stat``; a Python thread goes by its
    Python name) and the ``wchan`` of those in disk wait; at the first and the
    last sample the process's faults and I/O bytes, the machine's steal and
    iowait, the container's throttled time, the pressure totals and
    ``HostEvents``' compiles and GC time; the Python frames once, at the
    first.

    When the beat moves, one ``stall`` record (the last ``KEPT`` are kept,
    ``stalls_total`` counts them, one warning line each at most every
    ``LOG_EVERY_S``): ``at`` and ``at_ns`` (the overdue beat, on the wall
    clock and on ``perf_counter_ns``, the device trace's clock), ``age_s``
    (the process's age then), ``loop``, ``phase``, ``waited_s`` (beat to
    beat), ``limit_s``, ``sampled_s`` and ``samples`` (the part of the wait
    the watch saw), ``ended``, ``cpu_by_thread`` (name -> seconds while
    sampled, threads over 1% of it), ``state_share`` (name -> share of the
    samples in ``R`` and in ``D``, threads over 5%), ``wchan`` (of threads
    seen in ``D``), ``watch_cpu_s`` (what the sampling itself took), the
    deltas of ``_process_counters`` over the sampled part,
    ``late_longest_s`` / ``late_held`` / ``late_woke`` (the longest pause
    inside the wait),
    ``cpus``, ``frames``, and ``class`` with ``because``. The rules of
    ``class``, in order, first match (``classify``):

    1. ``interpreter_held``: the longest pause inside the wait covers over
       half of ``waited_s`` and was ``held``.
    2. ``process_paused``: such a pause that was not held; or ``throttled_s``
       plus ``steal_s`` a CPU covers over half of ``waited_s``.
    3. ``thread_ran``: one thread's CPU time covers over half of
       ``sampled_s`` (``because`` names it).
    4. ``blocked_io``: a thread was in ``D`` in over half the samples, or the
       process read and wrote over ``IO_BYTES_PER_S`` a sampled second.
    5. ``page_faults``: over ``FAULTS_PER_S`` minor faults a sampled second,
       or over ``MAJOR_FAULTS`` major ones.
    6. ``all_asleep``: none of these. The process could run and had nothing
       to do: the cause lies under it, in the device runtime, the driver or
       the chip.
    """

    TICK_S = 0.1
    SAMPLE_S = 0.02
    FAST_SAMPLES_S = 30.0
    GIVE_UP_S = 120.0
    PAUSE_NS = 50_000_000
    LATE_SECONDS = 600
    KEPT = 16
    LOG_EVERY_S = 10.0
    IO_BYTES_PER_S = 16 << 20
    FAULTS_PER_S = 20_000
    MAJOR_FAULTS = 64

    def __init__(self) -> None:
        self._hearts: Tuple[Heartbeat, ...] = ()
        self._lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        self._wake = threading.Event()  # set by ``stop`` alone
        self._born_ns = 0
        self.ticks = 0
        self.pause_count = 0
        self.pause_ns = 0
        self.pause_longest_ns = 0
        self.stalls_total = 0
        self.stalls_unlogged = 0
        self._logged_at = -self.LOG_EVERY_S
        self._pauses: "deque[Dict[str, Any]]" = deque(maxlen=self.KEPT)
        self._stalls: "deque[Dict[str, Any]]" = deque(maxlen=self.KEPT)
        self._late_second = [0] * self.LATE_SECONDS
        self._late_ns = [0] * self.LATE_SECONDS
        # (perf_counter_ns, steal_s, throttled_s), one a second
        self._outside: "deque[Tuple[int, float, float]]" = deque(
            maxlen=self.LATE_SECONDS)
        self._cpu0 = self._woke0 = self._woke_at = self._own_cpu = 0
        # whether this kernel counts a thread's voluntary switches (a
        # sandboxed one may not): known from the first wait that added one
        self._counts_wakes = False
        self._sampling: Optional[Heartbeat] = None

    # ------------------------------------------------------------ loops
    def heartbeat(self, name: str, phases: Tuple[str, ...],
                  stats: Optional[Callable[[], Dict[str, Any]]] = None) -> Heartbeat:
        """A pulse for one loop. Making it costs an object; the watch knows
        of it, and starts, at its first beat."""
        return Heartbeat(self, name, phases, stats)

    def _register(self, heart: Heartbeat) -> None:
        with self._lock:
            if heart._watched:
                return
            taken = {h.name for h in self._hearts}
            base, n = heart.name, 1
            while heart.name in taken:
                n += 1
                heart.name = f"{base}#{n}"
            heart._watched = True
            self._hearts += (heart,)
            if self._thread is None:
                self._born_ns = _born_ns()
                self._thread = threading.Thread(
                    target=self._run, daemon=True, name="stall-watch")
                self._thread.start()

    def _forget(self, heart: Heartbeat) -> None:
        with self._lock:
            heart._watched = True  # a closed loop's late beat starts nothing
            self._hearts = tuple(h for h in self._hearts if h is not heart)

    def stop(self) -> None:
        """End the thread (tests; a process keeps its watch to its end)."""
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout=10)

    # ----------------------------------------------------------- thread
    def _run(self) -> None:
        self._rebase()
        self._woke_at, self._own_cpu = time.perf_counter_ns(), time.thread_time_ns()
        self._cpu0 = time.process_time_ns()
        self._woke0 = resource.getrusage(resource.RUSAGE_THREAD).ru_nvcsw
        while True:
            now = self._sleep(self.TICK_S)
            if now is None:
                return
            self.ticks += 1
            if self.ticks % 10 == 0:
                self._rebase()
            self._look(now)

    def _look(self, now: int) -> None:
        """One tick's look at every loop: sample the first whose beat is
        overdue, or was and has moved since the last look."""
        hearts = self._hearts
        for heart in hearts:
            limit, at = heart.limit_ns, heart.at_ns
            if not limit or at == heart._given_up_at:
                continue
            if now - at > limit:
                late_at, phase = at, heart.phase
            elif at - heart._seen_at > limit and heart._seen_at:
                # the wait ended before the watch could look: both were
                # kept from running (a stopped process wakes its loop
                # and its watch together). The beat of the last tick
                # stands for the one that went overdue
                late_at, phase = heart._seen_at, heart._seen_phase
            else:
                continue
            self._sampling = heart
            try:
                self._sample(heart, late_at, phase)
            except Exception:  # noqa: BLE001 - the watch outlives a bad read
                logger.exception("stall watch: a sample failed")
            self._sampling = None
            break
        for heart in hearts:  # what this tick saw, for the next one
            heart._seen_at, heart._seen_phase = heart.at_ns, heart.phase

    def _sleep(self, seconds: float) -> Optional[int]:
        """Wait ``seconds`` and note how late the wake-up came, reckoned from
        wake to wake less the wait asked for and this thread's own CPU time
        between them: a pause that falls on the thread while it works (or
        waits to run) counts like one that falls on its wait. Returns the
        instant after the wait, or None once ``stop`` was called."""
        if self._wake.wait(seconds):
            return None
        now, own = time.perf_counter_ns(), time.thread_time_ns()
        nvcsw = resource.getrusage(resource.RUSAGE_THREAD).ru_nvcsw
        late = max(0, now - self._woke_at - int(seconds * 1e9)
                   - (own - self._own_cpu))
        woke = nvcsw - self._woke0
        if woke:
            self._counts_wakes = True
        second = int(time.time())
        i = second % self.LATE_SECONDS
        if self._late_second[i] != second:
            self._late_second[i], self._late_ns[i] = second, late
        elif late > self._late_ns[i]:
            self._late_ns[i] = late
        if late > self.PAUSE_NS:
            self._pause(now, late, woke if self._counts_wakes else None)
        self._woke_at, self._own_cpu = now, own
        self._cpu0, self._woke0 = time.process_time_ns(), nvcsw
        return now

    def _rebase(self) -> Tuple[float, float]:
        """Read steal and throttled time; what they rose by since the last
        reading."""
        steal, throttled = cpu_times()[0], _throttled_s()
        last = self._outside[-1] if self._outside else (0, steal, throttled)
        self._outside.append((time.perf_counter_ns(), steal, throttled))
        return steal - last[1], throttled - last[2]

    def _pause(self, now: int, late: int, woke: Optional[int]) -> None:
        cpu = time.process_time_ns() - self._cpu0
        steal, throttled = self._rebase()
        self.pause_count += 1
        self.pause_ns += late
        self.pause_longest_ns = max(self.pause_longest_ns, late)
        self._pauses.append({
            "at": time.time(), "at_ns": now,
            "age_s": (now - self._born_ns) / 1e9, "late_s": late / 1e9,
            "cpu_s": cpu / 1e9, "woke": woke, "steal_s": steal,
            "throttled_s": throttled,
            "held": 2 * cpu >= late or bool(
                woke and 2 * woke * sys.getswitchinterval() * 1e9 >= late),
            "frames": _frames()})

    def _sample_threads(self, threads: Dict[int, list],
                        names: Dict[int, str]) -> None:
        """One look at every native thread: ``threads[tid]`` is [name, CPU
        ticks at first sight, CPU ticks now, samples in R, samples in D,
        wchan]."""
        try:
            tids = os.listdir("/proc/self/task")
        except OSError:
            return
        for tid in tids:
            text = _read(f"/proc/self/task/{tid}/stat")
            fields = _stat_fields(text)
            if len(fields) < 13:
                continue  # the thread ended between the listing and the read
            ticks = int(fields[11]) + int(fields[12])
            seen = threads.get(int(tid))
            if seen is None:
                comm = text[text.find(b"(") + 1:text.rfind(b")")].decode(
                    "utf-8", "replace")
                seen = threads[int(tid)] = [
                    names.get(int(tid), comm), ticks, ticks, 0, 0, None]
            seen[2] = ticks
            if fields[0] == b"R":
                seen[3] += 1
            elif fields[0] == b"D":
                seen[4] += 1
                seen[5] = _read(f"/proc/self/task/{tid}/wchan").decode(
                    "utf-8", "replace") or None

    def _sample(self, heart: Heartbeat, at0: int, phase0: int) -> None:
        clock = time.perf_counter_ns
        limit0 = heart.limit_ns
        started = clock()
        own_cpu0 = time.thread_time_ns()
        own = threading.get_native_id()
        names = {t.native_id: t.name for t in threading.enumerate()}
        frames = _frames()
        before = _process_counters()
        threads: Dict[int, list] = {}
        samples, ended = 0, "moved"
        with span("watch.stall"):
            while True:
                self._sample_threads(threads, names)
                samples += 1
                now = clock()
                if heart.at_ns != at0:
                    break
                if heart.closed:
                    ended = "closed"
                    break
                if now - started > self.GIVE_UP_S * 1e9:
                    ended, heart._given_up_at = "gave_up", at0
                    break
                if self._sleep(self.SAMPLE_S if now - started
                               < self.FAST_SAMPLES_S * 1e9 else 1.0) is None:
                    return
        after = _process_counters()
        moved = heart.at_ns
        waited = (moved if moved != at0 else now) - at0
        sampled = max(now - started, 1)
        threads.pop(own, None)
        dup = {}
        for t in threads.values():
            dup[t[0]] = dup.get(t[0], 0) + 1
        named = {tid: t[0] if dup[t[0]] == 1 else f"{t[0]}#{tid}"
                 for tid, t in threads.items()}
        cpu = {named[tid]: (t[2] - t[1]) / _CLK_TCK for tid, t in threads.items()}
        pauses = [p for p in self._pauses if p["at_ns"] > at0]
        longest = max(pauses, key=lambda p: p["late_s"], default=None)
        record = {
            "at": time.time() - (clock() - at0) / 1e9, "at_ns": at0,
            "age_s": (at0 - self._born_ns) / 1e9, "loop": heart.name,
            "phase": heart.phases[phase0], "waited_s": waited / 1e9,
            "limit_s": limit0 / 1e9, "sampled_s": sampled / 1e9,
            "samples": samples, "ended": ended,
            "cpu_by_thread": {n: s for n, s in cpu.items()
                              if s > 0.01 * sampled / 1e9},
            "state_share": {
                named[tid]: {"R": t[3] / samples, "D": t[4] / samples}
                for tid, t in threads.items() if t[3] + t[4] > 0.05 * samples},
            "wchan": {named[tid]: t[5] for tid, t in threads.items() if t[5]},
            "watch_cpu_s": (time.thread_time_ns() - own_cpu0) / 1e9,
            **{k: after[k] - before[k] for k in before},
            "late_longest_s": longest["late_s"] if longest else 0.0,
            "late_held": bool(longest and longest["held"]),
            "late_woke": longest["woke"] if longest else None,
            "cpus": os.cpu_count() or 1,
            "frames": frames,
        }
        record["class"], record["because"] = self.classify(record)
        self._stalls.append(record)
        self.stalls_total += 1
        if now / 1e9 - self._logged_at < self.LOG_EVERY_S:
            self.stalls_unlogged += 1
            return
        self._logged_at = now / 1e9
        logger.warning(
            "stall: loop %s waited %.3f s in %s (limit %.3f s) at age %.1f s: "
            "%s (%s); %d samples over %.3f s, cpu by thread %s, faults %d+%d, "
            "io %d B, steal %.2f s, throttled %.2f s, compiles %d, gc %.3f s; "
            "%d earlier ones not logged", heart.name, record["waited_s"],
            record["phase"], record["limit_s"], record["age_s"],
            record["class"], record["because"], samples, record["sampled_s"],
            record["cpu_by_thread"], record["minor_faults"],
            record["major_faults"], record["read_bytes"] + record["write_bytes"],
            record["steal_s"], record["throttled_s"], record["compiles"],
            record["gc_s"], self.stalls_unlogged)

    @classmethod
    def classify(cls, record: Dict[str, Any]) -> Tuple[str, str]:
        """(class, because) of a stall record by the fixed rules of the class
        docstring."""
        waited, sampled = record["waited_s"], record["sampled_s"]
        late = record["late_longest_s"]
        if late > waited / 2:
            if record["late_held"]:
                return "interpreter_held", \
                    f"a thread kept the interpreter for {late:.3f} s"
            return "process_paused", f"no thread ran for {late:.3f} s" + (
                "" if record.get("late_woke") is not None else
                " (or one kept the interpreter asleep: this kernel does not "
                "count the watch's wake-ups)")
        outside = record["throttled_s"] + record["steal_s"] / record["cpus"]
        if outside > waited / 2:
            return "process_paused", f"throttled or stolen for {outside:.3f} s"
        cpu = record["cpu_by_thread"]
        top = max(cpu, key=cpu.get, default=None)
        if top is not None and cpu[top] > sampled / 2:
            return "thread_ran", f"{top} ran {cpu[top]:.3f} s of {sampled:.3f} s"
        in_d = {n: s["D"] for n, s in record["state_share"].items()}
        worst = max(in_d, key=in_d.get, default=None)
        if worst is not None and in_d[worst] > 0.5:
            return "blocked_io", \
                f"{worst} in disk wait in {100 * in_d[worst]:.0f}% of samples"
        io = record["read_bytes"] + record["write_bytes"]
        if io > cls.IO_BYTES_PER_S * sampled:
            return "blocked_io", f"{io} bytes read and written"
        if record["minor_faults"] > cls.FAULTS_PER_S * sampled \
                or record["major_faults"] > cls.MAJOR_FAULTS:
            return "page_faults", (f"{record['minor_faults']} minor and "
                                   f"{record['major_faults']} major faults")
        return "all_asleep", "no thread ran, waited for a disk or faulted"

    # ---------------------------------------------------------- readers
    def steal_since(self, at_ns: int) -> float:
        """Seconds the hypervisor gave to others (summed over CPUs) since the
        watch's last reading at or before ``at_ns``: it reads once a second,
        so this covers the time since ``at_ns`` and at most a second more."""
        base = None
        for entry in reversed(tuple(self._outside)):  # the watch appends
            base = entry
            if entry[0] <= at_ns:
                break
        return cpu_times()[0] - base[1] if base else 0.0

    def stall_within(self, loop: str, from_ns: int, to_ns: int) -> Optional[Dict[str, Any]]:
        """The newest record of a stall of ``loop`` whose overdue beat lies
        between the two ``perf_counter_ns`` instants."""
        for record in reversed(tuple(self._stalls)):  # the watch appends
            if record["loop"] == loop and from_ns <= record["at_ns"] <= to_ns:
                return record
        return None

    def loops(self) -> Dict[str, Dict[str, Any]]:
        """Every watched loop by name: its pulse and, where it keeps any, its
        own stats (a ``StepRing``'s)."""
        return {h.name: h.describe() for h in self._hearts}

    def snapshot(self) -> Dict[str, Any]:
        """Counters, the kept records, the lateness ring (``[wall second,
        ns]``, oldest first) and ``in_flight``: the longest overdue beat of
        any loop NOW, whether or not the watch thread has got to it (or is
        itself held)."""
        waiting = [f for f in (h.in_flight() for h in self._hearts) if f]
        ring = sorted((s, ns) for s, ns in zip(self._late_second, self._late_ns) if s)
        sampling = self._sampling
        return {
            "ticks": self.ticks,
            "age_s": (time.perf_counter_ns() - self._born_ns) / 1e9
            if self._born_ns else None,
            "pause_count": self.pause_count, "pause_ns": self.pause_ns,
            "pause_longest_ns": self.pause_longest_ns,
            "pauses": list(self._pauses),
            "stalls_total": self.stalls_total, "stalls": list(self._stalls),
            "sampling": sampling.name if sampling else None,
            "in_flight": max(waiting, key=lambda f: f["for_s"], default=None),
            "late_ring": [list(pair) for pair in ring],
        }


_stall_watch = StallWatch()


def stall_watch() -> StallWatch:
    """The process's ``StallWatch``. Its thread starts with the first beat."""
    return _stall_watch



# ---------------------------------------------------------------- step ring
RING_STEPS = 256
# a row: wall start, then seconds: take to take, inside the feed's generator
# (host batch wait + device_put), inside the compiled step's call (the
# enqueue), ``train.report``'s put and its wait for the controller, what is
# left (the user's: ``device_get`` and their own code), the loop thread's CPU
# time; and the prefetch buffer's depth at the take
STEP_COLUMNS = ("start", "interval", "feed", "dispatch", "report_put",
                "report_wake", "rest", "cpu", "buffered")
STEP_PARTS = STEP_COLUMNS[2:7]
# a beat's phases, by the part the loop then enters
_REST, _FEED, _DISPATCH, _REPORT_PUT, _REPORT_WAKE = 4, 0, 1, 2, 3
# a step is slow over max(median + SLOW_STEP_OVER_S, SLOW_STEP_MEDIANS x
# median) of the ring, once the ring holds SLOW_STEP_MIN_STEPS: the stall this
# looks for is +1.2 s on a step of 0.77-0.95 s (PERF.md 2), and the same
# threshold is the heartbeat's limit, so the watch samples the rest of a step
# already 40% late. It misses a stall under 0.4 steps (0.3-0.4 s there).
SLOW_STEP_OVER_S = 0.25
SLOW_STEP_MEDIANS = 1.4
SLOW_STEP_MIN_STEPS = 8
SLOW_STEP_LOG_EVERY_S = 10.0


class StepRing:
    """The train loop's flight recorder. The loop is the user's, so the
    program marks the three places every such loop passes: TAKING A BATCH
    (``_device_prefetch``'s ``yield``: ``take`` when the generator hands a
    batch over, which ends one step and begins the next, ``back`` when the
    loop comes for another), CALLING THE STEP (``dispatch`` /
    ``dispatched`` around the callable ``make_train_step`` returns: the
    enqueue; long means the call blocked, on a compile or a full queue) and
    ``train.report`` (``report_put``, ``report_wait``, ``reported``). A loop
    that feeds itself has no steps here; its dispatch and report times still
    add up in the row that never closes.

    Constant work a step: one ring row (``STEP_COLUMNS``; the parts sum to
    the interval, ``rest`` being what no mark covers), the counters, the
    slow-step check against a threshold recomputed every
    ``SLOW_STEP_MIN_STEPS`` steps, and the beats of the loop's ``Heartbeat``
    with that threshold as its limit. ``stats()``: ``loop`` (the heart's name
    among the watch's loops), ``steps``, ``reports``,
    ``sum_ns`` (by column), ``longest_step_ns``, ``compiles`` and ``gc_ns``
    over the steps (``HostEvents``), ``limit_s``, ``ring`` (``columns``,
    ``rows``, oldest first), ``slow_steps`` (the last 16: wall instant,
    ``total_s``, longest ``part`` and ``part_s``, ``median_s``, compiles and
    GC over the step, the process's ``cpu_s`` and the loop thread's
    ``loop_cpu_s``, ``steal_s`` over it, ``buffered``, ``step``, and
    ``stall``: the watch's record of the wait inside it, where there is
    one) and ``slow_steps_unlogged``; each slow step is also one ``slow
    train step:`` warning line, at most one every 10 s."""

    def __init__(self, name: str = "train") -> None:
        self.heart = _stall_watch.heartbeat(name, STEP_PARTS, self.stats)
        self._rows: List[Optional[tuple]] = [None] * RING_STEPS
        self.steps = 0
        self.reports = 0
        self._sum_ns = [0] * (len(STEP_COLUMNS) - 2)  # interval .. cpu
        self.longest_step_ns = 0
        self._median_ns = 0
        self._compiles = self._gc_ns = 0
        self._slow: "deque[Tuple[Dict[str, Any], int, int]]" = deque(maxlen=16)
        self._slow_logged_at = -SLOW_STEP_LOG_EVERY_S
        self._slow_unlogged = 0
        # the open step
        self._take_ns = self._back_ns = self._mark_ns = 0
        self._parts = [0, 0, 0]  # dispatch, report_put, report_wake
        self._wall = 0.0
        self._buffered = 0
        self._cpu0 = self._process_cpu0 = self._compiles0 = self._gc0 = 0

    def close(self) -> None:
        self.heart.close()

    # ------------------------------------------------------------- marks
    def take(self, buffered: int) -> None:
        """The feed hands the loop a batch, ``buffered`` on the device
        counting it: the open step ends here and the next begins."""
        now = time.perf_counter_ns()
        cpu, process_cpu = time.thread_time_ns(), time.process_time_ns()
        host = _host_events
        if self._take_ns:
            self._close_step(now, cpu, process_cpu, host)
        self._take_ns, self._back_ns, self._wall = now, 0, time.time()
        self._parts = [0, 0, 0]
        self._buffered = buffered
        self._cpu0, self._process_cpu0 = cpu, process_cpu
        self._compiles0, self._gc0 = host.compiles, host.gc_pause_ns
        self.heart.beat(_REST, now)

    def back(self) -> None:
        """The loop has come for its next batch."""
        self._back_ns = now = time.perf_counter_ns()
        self.heart.beat(_FEED, now)

    def dispatch(self) -> None:
        self._mark_ns = now = time.perf_counter_ns()
        self.heart.beat(_DISPATCH, now)

    def dispatched(self) -> None:
        now = time.perf_counter_ns()
        self._parts[0] += now - self._mark_ns
        self.heart.beat(_REST, now)

    def report_put(self) -> None:
        self._mark_ns = now = time.perf_counter_ns()
        self.reports += 1
        self.heart.beat(_REPORT_PUT, now)

    def report_wait(self) -> None:
        now = time.perf_counter_ns()
        self._parts[1] += now - self._mark_ns
        self._mark_ns = now
        self.heart.beat(_REPORT_WAKE, now)

    def reported(self) -> None:
        now = time.perf_counter_ns()
        self._parts[2] += now - self._mark_ns
        self.heart.beat(_REST, now)

    # ------------------------------------------------------------ a step
    def _close_step(self, now: int, cpu: int, process_cpu: int,
                    host: HostEvents) -> None:
        interval = now - self._take_ns
        feed = now - self._back_ns if self._back_ns else 0
        dispatch, put, wake = self._parts
        parts = (feed, dispatch, put, wake,
                 interval - feed - dispatch - put - wake)
        loop_cpu = cpu - self._cpu0
        compiles = host.compiles - self._compiles0
        gc_ns = host.gc_pause_ns - self._gc0
        self._rows[self.steps % RING_STEPS] = (
            self._wall, interval / 1e9, *(ns / 1e9 for ns in parts),
            loop_cpu / 1e9, self._buffered)
        self.steps += 1
        sums = self._sum_ns
        for i, ns in enumerate((interval, *parts, loop_cpu)):
            sums[i] += ns
        self._compiles += compiles
        self._gc_ns += gc_ns
        if interval > self.longest_step_ns:
            self.longest_step_ns = interval
        limit = self.heart.limit_ns
        if limit and interval > limit:
            self._slow_step(now, interval, parts, compiles, gc_ns,
                            process_cpu - self._process_cpu0, loop_cpu)
        if self.steps % SLOW_STEP_MIN_STEPS == 0:
            lengths = sorted(r[1] for r in self._rows[:self.steps])
            median = lengths[len(lengths) // 2]
            self._median_ns = int(median * 1e9)
            self.heart.limit_ns = int(1e9 * max(
                median + SLOW_STEP_OVER_S, SLOW_STEP_MEDIANS * median))

    def _slow_step(self, now: int, interval: int, parts: tuple, compiles: int,
                   gc_ns: int, process_cpu: int, loop_cpu: int) -> None:
        worst = max(range(len(parts)), key=parts.__getitem__)
        record = {
            "at": self._wall, "step": self.steps, "total_s": interval / 1e9,
            "part": STEP_PARTS[worst], "part_s": parts[worst] / 1e9,
            "median_s": self._median_ns / 1e9, "compiles": compiles,
            "gc_s": gc_ns / 1e9, "cpu_s": process_cpu / 1e9,
            "loop_cpu_s": loop_cpu / 1e9,
            "steal_s": _stall_watch.steal_since(self._take_ns),
            "buffered": self._buffered,
        }
        self._slow.append((record, self._take_ns, now))
        if now / 1e9 - self._slow_logged_at < SLOW_STEP_LOG_EVERY_S:
            self._slow_unlogged += 1
            return
        self._slow_logged_at = now / 1e9
        logger.warning(
            "slow train step: %.3f s at %.3f (median %.3f s); longest part %s "
            "%.3f s; compiles %d, gc %.3f s; cpu %.3f s (loop thread %.3f s), "
            "steal %.2f s over it; buffered %d; %d earlier ones not logged",
            record["total_s"], self._wall, record["median_s"], record["part"],
            record["part_s"], compiles, record["gc_s"], record["cpu_s"],
            record["loop_cpu_s"], record["steal_s"], self._buffered,
            self._slow_unlogged)

    # ------------------------------------------------------------- stats
    def stats(self) -> Dict[str, Any]:
        n = self.steps
        rows = self._rows[:n] if n < RING_STEPS else \
            self._rows[n % RING_STEPS:] + self._rows[:n % RING_STEPS]
        return {
            "loop": self.heart.name, "steps": n, "reports": self.reports,
            "sum_ns": dict(zip(STEP_COLUMNS[1:-1], self._sum_ns)),
            "longest_step_ns": self.longest_step_ns,
            "compiles": self._compiles, "gc_ns": self._gc_ns,
            "limit_s": self.heart.limit_ns / 1e9,
            "ring": {"columns": list(STEP_COLUMNS),
                     "rows": [list(r) for r in rows]},
            "slow_steps": [joined_stall(self.heart.name, *slow)
                           for slow in self._slow],
            "slow_steps_unlogged": self._slow_unlogged,
        }


def joined_stall(loop: str, record: Dict[str, Any], from_ns: int,
                 to_ns: int) -> Dict[str, Any]:
    """A loop's slow record with the watch's record of the wait inside it
    under ``"stall"``, where the watch has one. Joined by the reader: the
    watch closes its record a sample after the beat moves, which may be
    after the loop has written its own."""
    if "stall" not in record:
        stall = _stall_watch.stall_within(loop, from_ns, to_ns)
        if stall is not None:
            record["stall"] = stall
    return record


_step_rings: Dict[int, StepRing] = {}  # by the loop's thread
_process_ring: Optional[StepRing] = None


def bind_step_ring(ring: Optional[StepRing]) -> None:
    """``ring`` is the calling thread's from now on (a train session's);
    None takes the binding away."""
    if ring is None:
        _step_rings.pop(threading.get_ident(), None)
    else:
        _step_rings[threading.get_ident()] = ring


def step_ring() -> StepRing:
    """The calling thread's ``StepRing``: its session's, or where no session
    is bound the process's own."""
    global _process_ring
    ring = _step_rings.get(threading.get_ident())
    if ring is None:
        if _process_ring is None:
            with _lock:
                if _process_ring is None:
                    _process_ring = StepRing("train.process")
        ring = _process_ring
    return ring


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
