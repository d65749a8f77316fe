"""``profiling.StallWatch``: one test a ``class`` of stall, each on a watch of
its own, with waits of 1.5 s against a limit of 0.3 s. What the watch sees is
asserted; what it costs when nothing stalls is the last test."""
import ctypes
import hashlib
import json
import os
import signal
import subprocess
import sys
import threading
import time

import pytest

from ray_tpu import profiling

LIMIT_S, WAIT_S = 0.3, 1.5
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def watched():
    """(watch, heart): a loop that has beaten and published its limit."""
    watch = profiling.StallWatch()
    heart = watch.heartbeat("loop", ("work", "wait"))
    heart.beat(0)
    heart.limit_ns = int(LIMIT_S * 1e9)
    yield watch, heart
    heart.close()
    watch.stop()
    assert not watch._thread.is_alive()


def last_stall(watch, heart):
    """The record of the wait that ``heart``'s newest beat ended."""
    deadline = time.time() + 10
    while time.time() < deadline:
        stalls = watch.snapshot()["stalls"]
        if stalls and stalls[-1]["at_ns"] < heart.at_ns \
                and stalls[-1]["waited_s"] > LIMIT_S:
            return stalls[-1]
        time.sleep(0.02)
    raise AssertionError(f"no stall record: {watch.snapshot()}")


def all_asleep(watch, heart):
    """The loop blocks on an event nobody sets; ``in_flight`` is read DURING
    the wait, from the heart and from the watch."""
    heart.beat(1)
    threading.Event().wait(WAIT_S / 2)
    seen = heart.in_flight(), watch.snapshot()
    threading.Event().wait(WAIT_S / 2)
    heart.beat(0)
    for flight in (seen[0], seen[1]["in_flight"]):
        assert flight["loop"] == "loop" and flight["phase"] == "wait"
        assert LIMIT_S < flight["for_s"] < WAIT_S
    assert seen[1]["sampling"] == "loop"
    record = last_stall(watch, heart)
    assert record["samples"] >= 5 and record["ended"] == "moved"
    assert "MainThread" in record["frames"]
    return record


def interpreter_held(watch, heart):
    """A thread inside a C call that keeps the interpreter: nothing in Python
    runs, the watch neither, and its lateness says for how long."""
    usleep = ctypes.PyDLL(None).usleep

    def hold_the_interpreter():
        usleep(int(WAIT_S * 1e6))
        time.sleep(0.5)  # still there when the watch takes the frames

    holder = threading.Thread(target=hold_the_interpreter, name="holder")
    heart.beat(1)
    holder.start()
    time.sleep(0.05)  # needs the interpreter: returns when the call does
    heart.beat(0)
    record = last_stall(watch, heart)
    holder.join(timeout=10)
    pause = watch.snapshot()["pauses"][-1]
    assert pause["held"] and pause["late_s"] > WAIT_S / 2
    assert pause["woke"] > 20 and "hold_the_interpreter" in pause["frames"]["holder"]
    assert record["late_held"] and record["late_longest_s"] == pause["late_s"]
    return record


def thread_ran(watch, heart):
    """A thread that spins in C with the interpreter released."""
    stop = threading.Event()

    def spin():
        while not stop.is_set():
            hashlib.pbkdf2_hmac("sha256", b"key", b"salt", 100_000)

    spinner = threading.Thread(target=spin, name="spinner")
    spinner.start()
    try:
        heart.beat(1)
        threading.Event().wait(WAIT_S)
        heart.beat(0)
    finally:
        stop.set()
        spinner.join(timeout=10)
    record = last_stall(watch, heart)
    assert "spinner" in record["because"]
    assert record["cpu_by_thread"]["spinner"] > record["sampled_s"] / 2
    assert record["state_share"]["spinner"]["R"] > 0.5
    return record


CHILD = """
import json, sys, threading, time
sys.path.insert(0, %r)
from ray_tpu import profiling
watch = profiling.StallWatch()
heart = watch.heartbeat("loop", ("work", "wait"))
heart.beat(0)
heart.limit_ns = int(%r * 1e9)
for _ in range(6):  # the watch is up and ticking
    heart.beat(0)
    time.sleep(0.05)
print("ready", flush=True)
heart.beat(1)
threading.Event().wait(%r)
heart.beat(0)
time.sleep(0.3)
print(json.dumps(watch.snapshot()), flush=True)
"""


def process_paused(_watch, _heart):
    """A child stopped with SIGSTOP for 1.5 s of a 2.2 s wait and continued;
    read from the child's own snapshot."""
    child = subprocess.Popen(
        [sys.executable, "-c", CHILD % (ROOT, LIMIT_S, WAIT_S + 0.7)],
        stdout=subprocess.PIPE, text=True)
    try:
        assert child.stdout.readline().strip() == "ready"
        time.sleep(0.2)
        os.kill(child.pid, signal.SIGSTOP)
        time.sleep(WAIT_S)
        os.kill(child.pid, signal.SIGCONT)
        out, _ = child.communicate(timeout=30)
    finally:
        child.kill()
    snap = json.loads(out.strip().splitlines()[-1])
    assert snap["pause_count"] >= 1 and snap["pause_longest_ns"] > 0.9 * WAIT_S * 1e9
    pause = max(snap["pauses"], key=lambda p: p["late_s"])
    assert not pause["held"] and pause["cpu_s"] < 0.2 and pause["woke"] < 20
    assert max(ns for _s, ns in snap["late_ring"]) == snap["pause_longest_ns"]
    waits = [s for s in snap["stalls"] if s["phase"] == "wait"]
    assert len(waits) == 1
    return waits[0]


@pytest.mark.parametrize("case", [all_asleep, process_paused, interpreter_held,
                                  thread_ran], ids=lambda f: f.__name__)
def test_a_wait_is_seen_while_it_lasts_and_classed(watched, case):
    record = case(*watched)
    assert record["class"] == case.__name__, record
    assert record["loop"] == "loop" and record["phase"] == "wait"
    assert record["waited_s"] >= WAIT_S - 0.05
    assert record["limit_s"] == LIMIT_S and record["age_s"] > 0
    assert abs(record["at"] - time.time()) < 120
    assert profiling.StallWatch.classify(record)[0] == case.__name__


QUIET = {"waited_s": 2.0, "sampled_s": 1.6, "late_longest_s": 0.0,
         "late_held": False, "throttled_s": 0.0, "steal_s": 0.0, "cpus": 8,
         "cpu_by_thread": {"llm-engine": 0.02}, "state_share": {},
         "read_bytes": 0, "write_bytes": 4096, "minor_faults": 120,
         "major_faults": 0}
RULES = [
    ("all_asleep", {}),
    ("interpreter_held", {"late_longest_s": 1.1, "late_held": True}),
    ("process_paused", {"late_longest_s": 1.1}),
    ("process_paused", {"throttled_s": 0.9, "steal_s": 1.6}),
    ("all_asleep", {"late_longest_s": 0.9, "steal_s": 7.0}),  # under half
    ("thread_ran", {"cpu_by_thread": {"llm-engine": 0.02, "tpu-compile": 0.9}}),
    ("all_asleep", {"cpu_by_thread": {"a": 0.7, "b": 0.7}}),  # no ONE thread
    ("blocked_io", {"state_share": {"cache-writer": {"R": 0.0, "D": 0.6}}}),
    ("blocked_io", {"write_bytes": 30 << 20}),
    ("page_faults", {"minor_faults": 40_000}),
    ("page_faults", {"major_faults": 65}),
    # the order: a pause goes before a thread that ran, I/O before faults
    ("process_paused", {"late_longest_s": 1.1,
                        "cpu_by_thread": {"tpu-compile": 1.5}}),
    ("blocked_io", {"write_bytes": 30 << 20, "minor_faults": 40_000}),
]


@pytest.mark.parametrize("want,changed", RULES,
                         ids=[f"{i}-{w}" for i, (w, _c) in enumerate(RULES)])
def test_the_rules_of_class_one_by_one(want, changed):
    got, because = profiling.StallWatch.classify({**QUIET, **changed})
    assert got == want and because


def test_a_quiet_loop_leaves_no_record(watched):
    watch, heart = watched
    for _ in range(60):
        heart.beat(0)
        time.sleep(0.02)
    snap = watch.snapshot()
    assert snap["stalls_total"] == 0 and snap["stalls"] == []
    assert snap["in_flight"] is None and snap["sampling"] is None
    assert snap["ticks"] >= 5 and snap["late_ring"]
    # a loaded machine may run the watch late; it may not stop it
    assert snap["pause_longest_ns"] < 0.5e9
    assert list(watch.loops()) == ["loop"]


def test_a_loop_that_never_comes_back_is_given_up_and_stays_in_flight(
        watched, monkeypatch):
    watch, heart = watched
    monkeypatch.setattr(watch, "GIVE_UP_S", 0.5)
    heart.beat(1)
    deadline = time.time() + 10
    while not watch.snapshot()["stalls"] and time.time() < deadline:
        time.sleep(0.05)
    record = watch.snapshot()["stalls"][-1]
    assert record["ended"] == "gave_up" and record["class"] == "all_asleep"
    time.sleep(0.3)
    snap = watch.snapshot()
    # sampled once, not again; the overdue beat still shows
    assert snap["stalls_total"] == 1 and snap["sampling"] is None
    assert snap["in_flight"]["for_s"] > record["waited_s"]
    monkeypatch.undo()
    heart.beat(0)  # watched again from its next beat
    heart.beat(1)
    threading.Event().wait(LIMIT_S + 0.4)
    heart.beat(0)
    deadline = time.time() + 10
    while watch.stalls_total < 2 and time.time() < deadline:
        time.sleep(0.05)
    assert [s["ended"] for s in watch.snapshot()["stalls"]] == ["gave_up", "moved"]


def test_a_wait_that_ended_before_the_watch_could_look_is_recorded():
    """A stopped process wakes its loop and its watch together, and the loop
    may beat first: the look that finds a beat younger than the limit, but
    more than the limit after the beat the last look saw, records the wait
    from that one."""
    watch = profiling.StallWatch()  # no thread: the test makes the looks
    heart = profiling.Heartbeat(watch, "loop", ("work", "wait"))
    heart._watched = True
    watch._hearts = (heart,)
    heart.limit_ns = int(LIMIT_S * 1e9)
    t0 = time.perf_counter_ns()
    heart.at_ns, heart.phase = t0, 1
    watch._look(t0 + 1_000_000)  # a quiet look: it remembers the beat
    assert watch.stalls_total == 0 and heart._seen_at == t0
    # 2 s later the loop has beaten (phase 0) and the watch looks at last
    heart.at_ns, heart.phase = t0 + 2_000_000_000, 0
    watch._pauses.append({"at_ns": t0 + 2_000_000_000, "late_s": 1.9,
                          "held": False, "woke": 1})
    watch._look(t0 + 2_001_000_000)
    (record,) = watch.snapshot()["stalls"]
    assert record["phase"] == "wait" and record["waited_s"] == pytest.approx(2.0)
    assert record["samples"] == 1 and record["ended"] == "moved"
    assert record["class"] == "process_paused" and record["late_longest_s"] == 1.9
    watch._look(t0 + 2_100_000_000)  # and only once
    assert watch.stalls_total == 1


def test_two_loops_of_one_name_and_a_closed_one(watched):
    watch, heart = watched
    other = watch.heartbeat("loop", ("work",))
    other.beat(0)
    assert other.name == "loop#2" and sorted(watch.loops()) == ["loop", "loop#2"]
    other.limit_ns = 1
    time.sleep(0.01)
    assert other.in_flight()["loop"] == "loop#2"
    other.close()
    assert other.in_flight() is None and list(watch.loops()) == ["loop"]
    other.beat(0)  # a closed loop's late beat starts nothing
    assert list(watch.loops()) == ["loop"]


def test_a_beat_and_the_idle_watch_cost_next_to_nothing(watched):
    watch, heart = watched
    heart.limit_ns = 0
    beat = heart.beat
    t0 = time.perf_counter()
    for _ in range(1_000_000):
        beat(0)
    assert (time.perf_counter() - t0) / 1_000_000 < 1e-6
    clock = time.pthread_getcpuclockid(watch._thread.ident)
    cpu0, t0 = time.clock_gettime(clock), time.perf_counter()
    time.sleep(2.0)
    used = time.clock_gettime(clock) - cpu0
    assert used < 0.01 * (time.perf_counter() - t0)
    assert len([t for t in threading.enumerate() if t.name == "stall-watch"
                and t is watch._thread]) == 1
