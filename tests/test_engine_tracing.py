"""The engine's always-on counters, flight recorder, spans and hops, on the
CPU at a tiny size. What they count is asserted; what they cost is measured
on the chip (PERF.md), never here."""
import glob
import logging
import os
import queue
import threading
import time
from contextlib import contextmanager

import numpy as np
import pytest

from prefill_groups import (GROUPS, calls_by_rows,
                            check_rows_follow_the_group, request,
                            submit_together)
from ray_tpu.serve import llm
from ray_tpu.serve.llm import LLMEngine


@pytest.fixture(scope="module")
def engine():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig, llama_init

    config = LlamaConfig.tiny(dtype=jnp.float32, remat=None,
                              attention_impl="reference")
    eng = LLMEngine(config, llama_init(config, jax.random.key(3)), num_slots=16,
                    decode_chunk=4, max_seq_len=256, prefill_buckets=[128])
    eng.generate([5, 6, 7], max_tokens=5, timeout=300)  # compile both programs
    yield eng
    eng.stop()


@pytest.fixture(scope="module")
def stepped(engine):
    """A second engine of the same shapes whose loop thread has stopped: a
    test calls ``_step`` itself and reads each counter between two
    iterations. It runs the first engine's compiled programs."""
    eng = LLMEngine(engine.config, engine.params, num_slots=16, decode_chunk=4,
                    max_seq_len=256, prefill_buckets=[128])
    eng.stop()
    eng._prefill, eng._decode = engine._prefill, engine._decode
    step_through(eng, request([5, 6, 7], 2))  # brings the 128 bucket up
    return eng


def step_through(eng, req, streamed=False):
    """``req`` from submission to its answer, an iteration at a time: the
    rise of ``stats()`` over each iteration."""
    if streamed:
        req.stream_q = queue.Queue()
    eng._submit(req)
    rises = []
    while not req.future.done():
        before = eng.stats()
        eng._step()
        rises.append(rise(before, eng.stats()))
    return rises


def rise(before, after):
    """{dotted key: after - before} of every integer counter."""
    out = {}
    for key, value in after.items():
        if isinstance(value, dict) and key != "ring":
            out.update({f"{key}.{k}": v - before[key][k]
                        for k, v in value.items() if isinstance(v, int)})
        elif isinstance(value, int) and not isinstance(value, bool):
            out[key] = value - before[key]
    return out


class Result:
    """A fake program's result: the array, and after how many operations
    issued behind it the chip is done with it (0: the moment the launch
    returns; None: not before the fetch)."""

    def __init__(self, array, ready_after=None):
        self.array, self.ready_after = array, ready_after
        self.ops_behind = 0

    def is_ready(self):
        return self.ready_after is not None and self.ops_behind >= self.ready_after

    def __array__(self):
        return np.asarray(self.array)


class SlowSplit:
    """``jax``, with a key split that takes ``seconds`` on the host."""

    def __init__(self, jax, seconds):
        self._jax, self._seconds, self.random = jax, seconds, self

    def __getattr__(self, name):
        return getattr(self._jax, name)

    def split(self, key):
        time.sleep(self._seconds)
        return self._jax.random.split(key)


def test_phases_partition_the_iteration(engine):
    submit_together(engine, [request([1, 2, 3], 9) for _ in range(3)])
    time.sleep(0.05)  # some idle polls behind the last busy iteration
    s = engine.stats()
    assert s["iters"] > 0 and set(s["phase_ns"]) == set(llm.PHASES)
    assert sum(s["phase_ns"].values()) == pytest.approx(s["iter_ns"], rel=0.02)
    assert s["idle_ns"] > 0
    ring = s["ring"]
    assert ring["columns"] == list(llm.RING_COLUMNS)
    assert len(ring["rows"]) == min(s["iters"], llm.RING_ITERS)
    starts = [r[0] for r in ring["rows"]]
    assert starts == sorted(starts) and abs(starts[-1] - time.time()) < 60
    longest = max(sum(r[1:7]) for r in ring["rows"])
    assert s["longest_iter_s"] >= longest - 1e-6
    assert s["retired"] == s["admitted"] and s["active"] == 0


def test_decode_rows_run_and_live_are_counted(engine):
    """One request alone in 16 slots: every chunk runs 16 slot-rows a tick,
    one of them live. The ring's ``active`` column says the same."""
    before = engine.stats()
    engine.generate([9, 8, 7], max_tokens=11, timeout=300)
    after = engine.stats()
    ticks = after["decode_steps"] - before["decode_steps"]
    assert ticks >= 11 - 1 and ticks % engine.decode_chunk == 0
    assert after["decode_rows_run"] - before["decode_rows_run"] == 16 * ticks
    assert after["decode_rows_live"] - before["decode_rows_live"] == ticks


def test_prefill_padding_is_counted_exactly(engine):
    before = engine.stats()
    submit_together(engine, [request([7 + i] * 100) for i in range(3)])
    after = engine.stats()
    rise = {k: after[k] - before[k] for k in before
            if k.startswith("prefill_") and k != "prefill_calls_by_rows"}
    # 3 rows of 100 tokens take the 4-row program of the 128 bucket, which is
    # one piece and so computes every row it was given; the rows a prefill
    # PROGRAM counts by depth are another family's (0 here)
    assert rise == {"prefill_calls": 1, "prefill_rows_real": 3,
                    "prefill_rows_padded": 4, "prefill_tokens_real": 300,
                    "prefill_tokens_padded": 512,
                    "prefill_rows_computed": 512,
                    "prefill_rows_self": 0, "prefill_rows_cross": 0,
                    "prefill_rows": 0, "prefill_attn_pairs": 0}
    assert calls_by_rows(before, after) == {1: 0, 4: 1}
    assert after["admitted"] - before["admitted"] == 3


@pytest.mark.parametrize("n,calls", GROUPS)
def test_prefill_rows_follow_the_group(engine, n, calls):
    """The fixture's first request met the 128 bucket alone: every row count
    of ``PREFILL_ROWS`` came up then, and a group of any size finds its
    program compiled."""
    assert tuple(engine.stats()["prefill_calls_by_rows"]) == llm.PREFILL_ROWS
    check_rows_follow_the_group(engine, n, calls, prompt_len=90)


def test_a_burst_is_admitted_over_iterations(engine, monkeypatch):
    """One iteration prefills at most ``PREFILL_TOKENS_PER_ITER`` padded
    tokens (here 4 rows of the 128 bucket): 18 requests at once enter as 4,
    4, 4, 4 and 2, a decode chunk after each, in the order they came."""
    monkeypatch.setattr(llm, "PREFILL_TOKENS_PER_ITER", 512)
    before = engine.stats()
    reqs = [request([3 + i] * 40, 9) for i in range(18)]
    submit_together(engine, reqs)
    after = engine.stats()
    new = after["ring"]["rows"][-(after["iters"] - before["iters"]):]
    col = after["ring"]["columns"].index("admitted")
    assert [int(r[col]) for r in new if r[col]] == [4, 4, 4, 4, 2]
    assert calls_by_rows(before, after) == {1: 0, 4: 5}
    ttfts = [r.ttft_s for r in reqs]
    assert all(max(ttfts[i - 4:i]) < min(ttfts[i:i + 4]) for i in (4, 8, 12, 16))


def test_queue_wait_histogram_and_its_p90(engine):
    from benchmarks.readers import engine_queue_wait

    before = engine.stats()
    waits = [3.0, 3.0] + [0.02] * 8
    submit_together(engine, [request([9, 8, 7], waited_s=w) for w in waits])
    after = engine.stats()
    counts = [b - a for a, b in zip(before["queue_wait_hist"]["counts"],
                                    after["queue_wait_hist"]["counts"])]
    edges = after["queue_wait_hist"]["edges_s"]
    assert len(counts) == len(edges) + 1 and sum(counts) == 10
    slow_bucket = next(i for i, e in enumerate(edges) if e > 3.0)
    assert counts[slow_bucket] == 2 and sum(counts[slow_bucket:]) == 2
    ctx = {"marks": {"open": 0.0, "close": 9.0,
                     "polls": [(1.0, before), (2.0, after)]}}
    assert 2500 < engine_queue_wait.read(ctx, {"q": 0.9}) < 4000
    assert 15 < engine_queue_wait.read(ctx, {"q": 0.5}) < 200


def test_a_slow_iteration_leaves_one_record_and_one_warning_line(engine):
    lines = []

    class Catch(logging.Handler):
        def emit(self, record):
            lines.append(record.getMessage())

    handler = Catch(level=logging.WARNING)
    llm.logger.addHandler(handler)
    real, slept = engine._decode, []

    before = engine.stats()
    # over the floor AND over the ring's median x 5, which a loaded machine
    # (tier-1 runs six workers) can push past a fifth of the floor
    busy = [sum(r[1:7]) for r in before["ring"]["rows"]]
    stall = max(llm.SLOW_ITER_FLOOR_S + 0.3,
                (llm.SLOW_ITER_MEDIANS + 1) * float(np.median(busy)))

    def stalled(*args):
        if not slept:
            slept.append(True)
            time.sleep(stall)
        return real(*args)

    engine._decode = stalled
    try:
        engine.generate([4, 5, 6], max_tokens=9, timeout=300)
    finally:
        engine._decode = real
        llm.logger.removeHandler(handler)
    after = engine.stats()
    new = after["slow_iters"][len(before["slow_iters"]):]
    assert len(new) == 1
    rec = new[0]
    assert rec["phase"] in ("decode_dispatch", "device_get")
    assert rec["total_s"] >= llm.SLOW_ITER_FLOOR_S and rec["phase_s"] >= 0.3
    assert rec["total_s"] > llm.SLOW_ITER_MEDIANS * rec["median_s"]
    assert rec["compiles"] == 0 and rec["active"] == 1
    # the loop thread slept through the stall: it used far less CPU than wall
    assert 0 <= rec["loop_cpu_s"] <= rec["cpu_s"] + 0.05
    assert rec["loop_cpu_s"] < rec["total_s"] - 1.0
    assert rec["steal_s"] >= 0
    assert abs(rec["at"] - time.time()) < 60
    assert after["longest_iter_s"] >= rec["total_s"]
    slow_lines = [m for m in lines if m.startswith("slow engine iteration")]
    assert len(slow_lines) == 1 and rec["phase"] in slow_lines[0]


class BlockedGet:
    """``jax``, whose first ``device_get`` waits for ``release`` (or
    ``seconds``) before it fetches."""

    def __init__(self, jax, seconds):
        self._jax, self._seconds = jax, seconds
        self.release, self.blocked = threading.Event(), False

    def __getattr__(self, name):
        return getattr(self._jax, name)

    def device_get(self, tree):
        if not self.blocked:
            self.blocked = True
            self.release.wait(self._seconds)
        return self._jax.device_get(tree)


def test_a_blocked_fetch_is_in_flight_while_it_lasts_and_joined_after(
        engine, monkeypatch):
    """The iteration that does not end is in no counter; ``in_flight`` shows
    it DURING the wait, the stall watch samples it, and the slow record has
    the watch's record under ``stall``. ``steal_s`` is the steal over the
    iteration: against a steal clock that runs a second a second it reads
    about the iteration's length, not the engine's age or the machine's."""
    from ray_tpu import profiling

    monkeypatch.setattr(profiling, "cpu_times",
                        lambda: (5000.0 + time.perf_counter(), 0.0))
    # the heart's limit is published once the ring holds eight iterations
    engine.generate([1, 2, 3], max_tokens=40, timeout=300)
    time.sleep(1.3)  # the watch's next reading, once a second, is off that clock
    before = engine.stats()
    assert before["in_flight"] is None
    real = engine._jax
    engine._jax = BlockedGet(real, 2.5)
    done = []
    caller = threading.Thread(target=lambda: done.append(
        engine.generate([4, 5, 6], max_tokens=5, timeout=300)))
    caller.start()
    try:
        deadline, seen = time.time() + 30, None
        while seen is None and time.time() < deadline:
            seen = engine.stats()["in_flight"]
            time.sleep(0.02)
        during = engine.stats()
    finally:
        time.sleep(0.5)  # some samples of the wait
        engine._jax.release.set()
        caller.join(timeout=60)
        engine._jax = real
    assert done and seen["phase"] == "device_get"
    assert seen["loop"].startswith("llm-engine") and seen["for_s"] >= llm.SLOW_ITER_FLOOR_S
    # the counters stood still: what they show is the last finished iteration
    assert during["iters"] == before["iters"] and during["in_flight"] is not None
    deadline = time.time() + 10
    while time.time() < deadline:
        after = engine.stats()
        new = after["slow_iters"][len(before["slow_iters"]):]
        if new and "stall" in new[0]:
            break
        time.sleep(0.05)
    assert len(new) == 1 and after["in_flight"] is None
    rec = new[0]
    stall = rec["stall"]
    assert rec["phase"] == stall["phase"] == "device_get"
    assert stall["loop"] == seen["loop"] and stall["class"] == "all_asleep", stall
    assert stall["waited_s"] >= llm.SLOW_ITER_FLOOR_S and stall["samples"] >= 5
    assert stall["limit_s"] >= llm.SLOW_ITER_FLOOR_S
    assert rec["total_s"] - 0.2 <= rec["steal_s"] <= rec["total_s"] + 1.5


def test_a_new_shape_raises_compiles_by_one(engine):
    import jax

    fn = jax.jit(lambda x: x * 2 + 1)
    fn(np.zeros(5, np.float32))
    before = engine.stats()
    fn(np.zeros(5, np.float32))
    assert engine.stats()["compiles"] == before["compiles"]
    fn(np.zeros(7, np.float32))
    after = engine.stats()
    assert after["compiles"] == before["compiles"] + 1
    assert after["compile_s"] > before["compile_s"]


def test_gc_pauses_are_counted(engine):
    import gc

    before = engine.stats()
    gc.collect()
    after = engine.stats()
    assert after["gc_pause_ns"] > before["gc_pause_ns"]
    assert after["gc_longest_s"] > 0
    assert after["gc_pauses_over_50ms"] >= before["gc_pauses_over_50ms"]


def sizes(value, path=""):
    """{path: len} of every container in ``value``."""
    out = {}
    if isinstance(value, dict):
        out[path] = len(value)
        for k, v in value.items():
            out.update(sizes(v, f"{path}.{k}"))
    elif isinstance(value, (list, tuple)):
        out[path] = len(value)
        if value and isinstance(value[0], (list, dict)):
            out.update(sizes(value[0], f"{path}[]"))
    return out


def test_stats_does_not_grow_with_requests(engine):
    def run(n):
        reqs = [request([1 + i % 200, 2, 3], max_tokens=2) for i in range(n)]
        for r in reqs:
            engine._submit(r)
        for r in reqs:
            r.future.result(timeout=300)
        return engine.stats()

    bounded = {".ring.rows": llm.RING_ITERS, ".slow_iters": 16}
    few, many = sizes(run(10)), sizes(run(1000))
    for path, limit in bounded.items():
        assert few.pop(path) <= limit and many.pop(path) <= limit
    assert few == many
    assert engine.stats()["admitted"] >= 1010


def test_a_profile_holds_the_six_phases_and_the_programs_by_name(engine, tmp_path):
    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    before = engine.stats()
    try:
        engine.generate([3, 1, 4, 1, 5], max_tokens=6, timeout=300)
        time.sleep(0.05)  # a whole idle poll inside the profile
    finally:
        jax.profiler.stop_trace()
    after = engine.stats()
    path = sorted(glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    names, prefill_attrs, programs, ops = set(), None, [], 0
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                names.add(ev.name)
                if ev.name == "engine.prefill_dispatch":
                    prefill_attrs = dict(ev.stats)
                elif ev.name == "engine.launch":
                    programs.append(dict(ev.stats)["program"])
                elif ev.name == "engine.slot_update":
                    ops += 1
    assert {"engine." + p for p in llm.PHASES} <= names
    assert "engine.idle" in names
    # one prefill, then a chunk of 4 and a chunk that ends the reply; a span
    # an operation the helper counted: the group's one and a key split a
    # chunk (the retired slot goes dead with the next group's)
    assert sorted(programs) == [llm.PREFILL, llm.DECODE, llm.DECODE]
    assert ops == 3 == rise(before, after)["work_calls.slot_update"]
    # state_rows: slots whose recurrent state the prefill wrote (PR 29); a
    # Llama keeps none
    assert prefill_attrs == {"bucket": 128, "rows_real": 1, "rows_padded": 1,
                             "state_rows": 0}
    assert "PjitFunction(paged_decode_steps)" in names
    assert "PjitFunction(paged_prefill)" in names
    # the module line of a device trace reads the lowered module's name
    assert "module @jit_paged_decode_steps" in engine.decode_program_text()


def test_streamed_tokens_latencies_and_hops(engine):
    prompt = [11, 12, 13, 14]
    whole = engine.generate(prompt, max_tokens=9, timeout=300)
    t0 = time.time()
    items = list(engine.generate_stream(prompt, max_tokens=9, timeout=300))
    t1 = time.time()
    assert all(type(i) is dict and set(i) == {"token"} for i in items[:-1])
    assert [i["token"] for i in items[:-1]] == whole["tokens"]
    done = items[-1]
    assert set(done) == {"done", "ttft_s", "latency_s", "num_tokens", "hops"}
    assert done["num_tokens"] == 9 and 0 < done["ttft_s"] <= done["latency_s"]
    assert set(whole) == {"tokens", "ttft_s", "latency_s"}
    hops = done["hops"]
    order = ["engine_enter", "first_push", "first_pickup", "done_push",
             "done_pickup"]
    assert set(hops) == set(order)
    stamps = [hops[k] for k in order]
    assert stamps == sorted(stamps) and t0 <= stamps[0] and stamps[-1] <= t1


def test_hops_of_the_way_in_reach_the_done_record(engine):
    from ray_tpu.serve import replica

    token = replica._request_hops.set({"proxy_recv": 1.0, "router_submit": 2.0,
                                       "replica_enter": 3.0})
    try:
        done = list(engine.generate_stream([2, 3], max_tokens=2, timeout=300))[-1]
    finally:
        replica._request_hops.reset(token)
    assert done["hops"]["proxy_recv"] == 1.0 and done["hops"]["replica_enter"] == 3.0
    assert replica.current_request_hops() is None


def test_span_is_null_without_jax_and_profile_keeps_its_buffer():
    import subprocess
    import sys

    code = ("import sys; from ray_tpu import profiling as p; s = p.span('x', n=1);"
            "assert s is p.span('y') and 'jax' not in sys.modules; "
            "exec('with s: pass')")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)

    from ray_tpu import profiling

    profiling.drain()
    with profiling.profile("user-span", extra={"k": 1}):
        pass
    spans = profiling.drain()
    assert [s["name"] for s in spans] == ["user-span"] and spans[0]["extra"] == {"k": 1}


def test_idle_iterations_enter_neither_iters_nor_the_ring(engine):
    before = engine.stats()
    deadline = time.time() + 5
    while engine.stats()["idle_ns"] == before["idle_ns"] and time.time() < deadline:
        threading.Event().wait(0.02)
    after = engine.stats()
    assert after["idle_ns"] > before["idle_ns"]
    assert after["iters"] == before["iters"] and after["iter_ns"] == before["iter_ns"]
    assert len(after["ring"]["rows"]) == len(before["ring"]["rows"])


def test_work_kinds_are_counted_where_the_work_happens(stepped):
    """A request of 9 tokens lives two iterations (first token and a chunk of
    4, then a chunk of 4 that ends it). The first admits it: the ONE program
    that makes its group's slots live (and the last request's slot dead) and
    the chunk's key split, the prefill and the decode launch. The second
    retires it, on the host alone: a key split. With no stream the only
    hand-over to another thread is the future's result."""
    first, last = step_through(stepped, request([1, 2, 3], 9))
    assert (first["admitted"], first["retired"]) == (1, 0)
    assert first["work_calls.slot_update"] == 1 + 1
    assert first["work_calls.launch"] == 2 and first["work_calls.notify"] == 0
    assert first["work_ns.notify"] == 0 and first["work_ns.pack"] > 0
    assert (last["admitted"], last["retired"]) == (0, 1)
    assert last["work_calls.slot_update"] == 1
    assert last["work_calls.launch"] == 1 and last["work_calls.notify"] == 1
    assert last["work_ns.pack"] == 0 and last["work_ns.notify"] > 0
    for it in (first, last):
        assert it["work_ns.launch"] > 0 and it["work_ns.slot_update"] > 0
        assert 0 <= it["work_calls.launch_waited"] <= it["work_calls.launch"]


def test_a_stream_is_notified_a_push_and_never_a_token(stepped):
    """First token and the chunk's four: two pushes; the next chunk's four
    and the end (tokens, sentinel and result in one hand-over): two."""
    first, last = step_through(stepped, request([4, 5, 6], 9), streamed=True)
    assert first["work_calls.notify"] == 2 and last["work_calls.notify"] == 2


def test_the_work_kinds_lie_inside_the_host_turn(engine, stepped):
    submit_together(engine, [request([2 + i] * 50, 9) for i in range(5)])
    for s in (engine.stats(), stepped.stats()):
        assert tuple(s["work_ns"]) == llm.WORK_KINDS
        assert tuple(s["work_calls"]) == llm.WORK_CALLS
        assert all(ns > 0 for ns in s["work_ns"].values())
        assert sum(s["work_ns"].values()) <= \
            s["iter_ns"] - s["phase_ns"]["device_get"]


def test_bringing_a_bucket_up_is_launches_of_its_own_kind(stepped):
    """A bucket met for the first time: a launch of every row count before
    the request's own, each with the program that writes per-slot state
    behind it on pad rows (new to the engine only at a row count's first
    bucket). The request lives this one iteration: its group's program and
    the key split."""
    stepped._buckets_up.discard(128)
    first = step_through(stepped, request([7, 8, 9], 2))[0]
    assert first["work_calls.launch"] == len(llm.PREFILL_ROWS) + 2
    assert first["work_calls.slot_update"] == len(llm.PREFILL_ROWS) + 1 + 1
    assert 128 in stepped._buckets_up


def test_phase_cpu_splits_the_loop_threads_cpu_time(stepped):
    step_through(stepped, request([3, 4, 5], 9))
    s = stepped.stats()
    assert tuple(s["phase_cpu_ns"]) == llm.PHASES
    assert sum(s["phase_cpu_ns"].values()) == s["loop_cpu_ns"] > 0
    assert s["loop_cpu_ns"] <= s["iter_ns"] * 1.05
    # the process's clock counts every thread, the loop thread among them
    assert s["process_cpu_ns"] >= 0.9 * s["loop_cpu_ns"]


def test_waiting_for_the_chip_is_wall_time_and_not_cpu_time(stepped):
    """A decode program that the chip delivers 0.3 s late: the fetch waits
    for it asleep."""
    real = stepped._decode

    class Late(Result):
        def __array__(self):
            time.sleep(0.3)
            return np.asarray(self.array)

    def late(*args):
        out = real(*args)
        return (Late(out[0]),) + tuple(out[1:])

    stepped._decode = late
    try:
        (it,) = step_through(stepped, request([6, 7, 8], 2))
    finally:
        stepped._decode = real
    assert it["phase_ns.device_get"] >= 0.3e9
    assert it["phase_cpu_ns.device_get"] < 0.1e9
    assert it["loop_cpu_ns"] < it["iter_ns"] - 0.2e9


@pytest.mark.parametrize("ready_after,waited,starved_s", [
    (0, 1, (0.4, 9.0)), (1, 0, (0.2, 0.35)), (None, 0, (0.0, 0.1))])
def test_the_chip_is_starved_from_an_empty_instant_to_the_next_launch(
        stepped, ready_after, waited, starved_s):
    """Two requests in one prefill group; between the prefill launch and the
    decode launch the loop issues the group's program and the key split, 0.2 s
    each on the host here. A launch that returns with its result ready has
    waited the program out: both operations are starvation. A program the
    FIRST operation waits out leaves the second as starvation: the loop
    learns that the chip is empty from the operation that returns and finds
    the result ready. Behind a program that is unfinished until the fetch,
    none of it is."""
    real_prefill, real_decode = stepped._prefill_firsts, stepped._decode
    real_update, real_jax = stepped._update, stepped._jax

    def prefill(args):
        firsts = real_prefill(args)
        firsts.block_until_ready()
        return Result(firsts, ready_after)

    def update(table, tokens, positions, active, retired, firsts, placed):
        time.sleep(0.2)
        firsts.ops_behind += 1
        return real_update(table, tokens, positions, active, retired,
                           firsts.array, placed)

    def decode(*args):
        out = real_decode(*args)
        return (Result(out[0]),) + tuple(out[1:])

    stepped._prefill_firsts, stepped._decode = prefill, decode
    stepped._update, stepped._jax = update, SlowSplit(real_jax, 0.2)
    reqs = [request([9, 8, 7], 2), request([6, 5, 4], 2)]
    for r in reqs:
        stepped._submit(r)
    stepped._chip_empty(time.perf_counter_ns())  # observed empty: now
    before = stepped.stats()
    try:
        stepped._step()
    finally:
        stepped._prefill_firsts, stepped._decode = real_prefill, real_decode
        stepped._update, stepped._jax = real_update, real_jax
    it = rise(before, stepped.stats())
    assert all(r.future.done() for r in reqs) and it["admitted"] == 2
    assert it["work_calls.launch"] == 2
    assert it["work_calls.launch_waited"] == waited
    assert it["work_ns.slot_update"] >= 0.4e9
    assert starved_s[0] * 1e9 <= it["starved_ns"] < starved_s[1] * 1e9
    assert it["starved_ns"] <= it["iter_ns"]
    # the fetch saw everything done: the chip is empty from then on
    assert stepped._empty_since > 0 and stepped._in_flight is None


def test_between_ns_is_the_time_from_one_busy_iteration_to_the_next(stepped):
    """A request of 9 tokens lives two iterations; 0.1 s pass between them
    here. An idle poll ends the run of busy iterations: nothing is counted
    across it."""
    stepped._step()  # an idle poll: no busy iteration stands before the next
    req = request([2, 7, 1], 9)
    stepped._submit(req)
    before = stepped.stats()
    stepped._step()
    time.sleep(0.1)
    stepped._step()
    assert req.future.done()
    it = rise(before, stepped.stats())
    assert it["iters"] == 2 and 0.1e9 <= it["between_ns"] < 0.2e9
    time.sleep(0.1)
    stepped._step()  # idle
    time.sleep(0.1)
    before = stepped.stats()
    step_through(stepped, request([2, 7, 2], 2))
    assert rise(before, stepped.stats())["between_ns"] == 0


def test_an_idle_poll_raises_idle_ns_alone(stepped):
    """Thirty idle polls between two requests are 0.3 s of ``idle_ns`` and no
    starvation: the stretch from the last fetch to the next launch is
    counted without them."""
    step_through(stepped, request([1, 1, 2], 2))
    before = stepped.stats()
    for _ in range(30):
        stepped._step()
    polled = rise(before, stepped.stats())
    assert polled.pop("idle_ns") >= 0.3e9
    assert not any(polled.values())
    (it,) = step_through(stepped, request([3, 5, 8], 2))
    assert 0 < it["starved_ns"] < 0.15e9


# ----- the program that writes per-slot state, through every family -----
a_request = request  # for the fixture, whose ``request`` is pytest's
FAMILIES = ("tiny", "nemotron_h_tiny", "laguna_tiny", "phi4flash_tiny")
FAMILY_SHAPES = dict(num_slots=4, decode_chunk=4, max_seq_len=128,
                     prefill_buckets=[64], page_size=16)


@pytest.fixture(scope="module", params=FAMILIES)
def family(request):
    """A preset's family in float32 on an engine whose loop thread has
    stopped (a test calls ``_step``). Once the constructor, a bucket's
    bring-up and a decode call by hand, key split and all (the decode program
    is jitted at its first call), are done, the engine's FIRST request runs here, from admission
    to retirement: ``first_request_compiles`` is what ``host_events.compiles``
    rose by over it."""
    import jax
    import jax.numpy as jnp

    preset = llm.model_presets()[request.param]()
    config = type(preset).tiny(dtype=jnp.float32, attention_impl="reference")
    eng = LLMEngine(config, **FAMILY_SHAPES)
    eng.stop()
    eng._bring_up(64)
    eng._key, sub = jax.random.split(eng._key)
    _, _, _, eng.cache, *_ = eng._decode(
        eng.params, eng.cache, eng._tokens, eng._positions, eng._active,
        eng._table, sub)
    compiles = eng.stats()["compiles"]
    (only,) = step_through(eng, a_request([5, 6, 7], 2))
    assert (only["admitted"], only["retired"]) == (1, 1)
    eng.first_request_compiles = eng.stats()["compiles"] - compiles
    return eng


def test_no_request_meets_a_compile_of_the_slot_program(family):
    assert family.first_request_compiles == 0


def test_staggered_requests_through_reused_slots_answer_as_alone(family):
    """Seven requests of different lengths over four slots: a slot that
    retires is admitted again in the next iteration, with the pages the
    retired request gave back, while the other slots decode on. Each answer
    is what a fresh engine gives that request alone."""
    rng = np.random.default_rng(11)
    sizes = [(30, 3), (7, 9), (60, 6), (3, 13), (55, 5), (41, 8), (12, 4)]
    reqs = [request(rng.integers(1, 200, n).tolist(), m) for n, m in sizes]
    pages, iters, admit = {}, [], family._admit

    def admitting():
        groups = admit()
        pages.update((id(req), set(req_pages)) for chunk, _b, _s in groups
                     for req, _slot, req_pages, _ in chunk)
        return groups

    family._admit = admitting
    for r in reqs:
        family._submit(r)
    try:
        while not all(r.future.done() for r in reqs):
            before = family.stats()
            family._step()
            it = rise(before, family.stats())
            iters.append((it["admitted"], it["retired"]))
            assert it["work_calls.slot_update"] <= 3
    finally:
        family._admit = admit
    assert iters[0][0] == 4 and sum(a for a, _ in iters) == 7
    assert any(retired and admitted
               for (_, retired), (admitted, _) in zip(iters, iters[1:]))
    assert any(pages[id(late)] & pages[id(early)]
               for early in reqs[:4] for late in reqs[4:])
    fresh = LLMEngine(family.config, family.params, **FAMILY_SHAPES)
    fresh._prefill, fresh._decode = family._prefill, family._decode
    try:
        for r, (_, m) in zip(reqs, sizes):
            alone = fresh.generate(r.tokens, max_tokens=m, timeout=300)
            assert r.future.result()["tokens"] == alone["tokens"]
            assert len(alone["tokens"]) == m
    finally:
        fresh.stop()


@contextmanager
def recording(eng):
    """Yields the list that ``eng``'s launches (by program) and slot updates
    ("op") go into, in order, while the block runs."""
    events = []
    launch, update = eng._launch, eng._slot_update

    def launched(program, *args):
        events.append(program)
        return launch(program, *args)

    def updated(op, *args):
        events.append("op")
        return update(op, *args)

    eng._launch, eng._slot_update = launched, updated
    try:
        yield events
    finally:
        eng._launch, eng._slot_update = launch, update


def test_a_retirement_no_group_carries_goes_before_the_decode_launch(family):
    """Two requests; the short one ends an iteration before the other and
    nobody is admitted behind it: the next iteration makes its slot dead on
    the device (table row to the trash page) before it launches the chunk."""
    short, long = request([8, 1, 8], 6), request([2, 8, 1], 17)
    family._submit(short)
    family._submit(long)
    family._step()
    family._step()
    assert short.future.done() and not long.future.done()
    assert family._retiring[short.slot] and bool(family._active[short.slot])
    with recording(family) as events:
        family._step()
    assert events == ["op", "op", llm.DECODE]
    assert not family._retiring.any()
    assert not bool(family._active[short.slot])
    assert bool(family._active[long.slot])
    assert not np.asarray(family._table[short.slot]).any()
    assert np.asarray(family._table[long.slot]).any()
    while not long.future.done():
        family._step()


@pytest.mark.parametrize("n", [1, 2, 3, 4])
def test_two_operations_stand_between_a_prefill_and_the_decode_launch(
        family, n):
    """A group of ``n`` requests (1: the one-row program; 2-4: the four-row
    one, with pad rows): behind the prefill launch the loop issues the
    group's program and the key split, then launches the chunk."""
    reqs = [request([3 + i, 1, 4], 9) for i in range(n)]
    for r in reqs:
        family._submit(r)
    before = family.stats()
    with recording(family) as events:
        family._step()
    assert calls_by_rows(before, family.stats()) == \
        {1: int(n == 1), 4: int(n > 1)}
    assert events == [llm.PREFILL, "op", "op", llm.DECODE]
    assert sorted(r.slot for r in reqs) == list(range(n))
    while not all(r.future.done() for r in reqs):
        family._step()
