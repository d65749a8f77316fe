"""The readers of the engine's counters, flight recorder and hops, each on a
hand-made ``ctx``; and the benchmark's own fast CPU tests, imported so that
tier-1 guards its yardstick (rates, traffic, roofline counts, the trace
reducer, the manifest, the reference) against an edit. The rehearsal, which
starts a cluster a cell, stays under ``benchmarks/tests`` only."""
import pytest

from benchmarks.harness.loadgen import RequestRecord
from benchmarks.readers import (
    engine_counters, engine_longest_iter, engine_queue_wait, hops_percentile)
from benchmarks.tests.test_kimi_k2_family import *  # noqa: F401,F403
from benchmarks.tests.test_laguna_family import *  # noqa: F401,F403
from benchmarks.tests.test_ling_family import *  # noqa: F401,F403
from benchmarks.tests.test_manifest import *  # noqa: F401,F403
from benchmarks.tests.test_mellum_family import *  # noqa: F401,F403
from benchmarks.tests.test_nemotron_h_family import *  # noqa: F401,F403
from benchmarks.tests.test_olmo_hybrid_family import *  # noqa: F401,F403
from benchmarks.tests.test_phi4flash_family import *  # noqa: F401,F403
from benchmarks.tests.test_rates import *  # noqa: F401,F403
from benchmarks.tests.test_readers import *  # noqa: F401,F403
from benchmarks.tests.test_reference import *  # noqa: F401,F403
from benchmarks.tests.test_roofline import *  # noqa: F401,F403
from benchmarks.tests.test_stall_readers import *  # noqa: F401,F403
from benchmarks.tests.test_trace_reduce import *  # noqa: F401,F403
from benchmarks.tests.test_traffic import *  # noqa: F401,F403

EDGES = [1e-3 * 10 ** (i / 5) for i in range(25)]
COLUMNS = ["start", "admit", "prefill_dispatch", "decode_dispatch",
           "device_get", "emit", "retire", "active", "admitted", "retired"]


def stats(iters, iter_ms, get_ms, prefill_ms=0.0, real=0, padded=0, compiles=0,
          gc_ms=0.0, waits=None, ring=None, slow=()):
    counts = [0] * (len(EDGES) + 1)
    for bucket, n in (waits or {}).items():
        counts[bucket] = n
    return {
        "decode_steps": 8 * iters, "active": 28, "iters": iters,
        "iter_ns": int(iter_ms * 1e6),
        "phase_ns": {"device_get": int(get_ms * 1e6),
                     "prefill_dispatch": int(prefill_ms * 1e6)},
        "prefill_tokens_real": real, "prefill_tokens_padded": padded,
        "compiles": compiles, "gc_pause_ns": int(gc_ms * 1e6),
        "queue_wait_hist": {"edges_s": EDGES, "counts": counts},
        "ring": {"columns": COLUMNS, "rows": ring or []},
        "slow_iters": list(slow),
    }


def row(start, total):
    return [start, 0.0, 0.25 * total, 0.05 * total, 0.6 * total, 0.1 * total,
            0.0, 28.0, 0.0, 0.0]


@pytest.fixture
def polled_ctx():
    """Window 100-150 on the runner's clock (wall = clock + 1000). Polls at
    110 and 120, the profiler's call 121-127 with its polls left out, polls at
    130 and 140: two segments of 10 s, 10 + 12 iterations."""
    polls = [
        (110.0, stats(100, 80_000, 36_000, 30_000, 1000, 4000, 5, 40.0, {10: 7})),
        (120.0, stats(110, 88_500, 39_900, 33_300, 1800, 6000, 5, 41.0, {10: 9, 16: 1})),
        (124.0, stats(113, 95_000, 40_500, 33_500, 1900, 6200, 9, 900.0, {10: 9, 16: 5})),
        (130.0, stats(118, 99_000, 43_000, 35_000, 2000, 7000, 9, 950.0, {10: 9, 16: 6})),
        (140.0, stats(130, 109_200, 47_560, 39_440, 2600, 10000, 9, 952.0,
                      {10: 17, 16: 8})),
    ]
    return {"marks": {"open": 100.0, "close": 150.0, "open_wall": 1100.0,
                      "trace_call": (121.0, 127.0), "polls": polls}}


@pytest.mark.parametrize("params,expected", [
    # (8500 - 3900) + (10200 - 4560) ms over 10 + 12 iterations
    ({"plus": ["iter_ns"], "minus": ["phase_ns.device_get"], "over": "iters",
      "scale": 1e-6}, (4600 + 5640) / 22),
    ({"plus": ["phase_ns.device_get"], "over": "iters", "scale": 1e-6},
     (3900 + 4560) / 22),
    ({"plus": ["phase_ns.prefill_dispatch"], "over": "iters", "scale": 1e-6},
     (3300 + 4440) / 22),
    # 1 - (800 + 600) / (2000 + 3000)
    ({"plus": ["prefill_tokens_padded"], "minus": ["prefill_tokens_real"],
      "over": "prefill_tokens_padded", "scale": 100.0}, 72.0),
    # the profiler's own four compiles and its 0.9 s collection are left out
    ({"plus": ["compiles"]}, 0.0),
    ({"plus": ["gc_pause_ns"], "scale": 1e-6}, 3.0),
])
def test_counter_differences_leave_out_the_traced_seconds(polled_ctx, params,
                                                          expected):
    assert engine_counters.read(polled_ctx, params) == pytest.approx(expected)


def test_counter_readers_read_nothing_from_a_program_without_counters(polled_ctx):
    for _t, s in polled_ctx["marks"]["polls"]:
        del s["iters"], s["phase_ns"], s["queue_wait_hist"]
    assert engine_counters.read(polled_ctx, {
        "plus": ["phase_ns.device_get"], "over": "iters"}) is None
    assert engine_counters.read(polled_ctx, {"plus": ["iters"]}) is None
    assert engine_queue_wait.read(polled_ctx, {"q": 0.9}) is None
    assert engine_counters.read({"marks": {"polls": []}}, {"plus": ["iters"]}) is None


# PR 41's metrics of the host's turn: what each reads from a ``stats()`` that
# counts, per iteration, 3 ms starved, 5 ms of loop-thread CPU (1 of it in
# the fetch), 9 ms of process CPU, 0.5 / 2 / 1.5 / 0.25 ms of pack / launch /
# slot_update / notify, 2 launches (1 waited out) and 7 slot operations, and
# 6 ms between one iteration and the next
HOST_TURN = {
    "starved_ns": 3e6, "loop_cpu_ns": 5e6, "process_cpu_ns": 9e6,
    "between_ns": 6e6,
    "phase_cpu_ns": {"device_get": 1e6},
    "work_ns": {"pack": 0.5e6, "launch": 2e6, "slot_update": 1.5e6,
                "notify": 0.25e6},
    "work_calls": {"launch": 2, "launch_waited": 1, "slot_update": 7},
}
# the BEGINNING of the saturated cells' list, in the order they came: a later
# PR's saturated cell is appended after these
SATURATED = ["serve_longprompt", "serve_hybrid_longreply",
             "serve_window_longctx", "serve_yoco_longctx", "serve_chat_sat",
             "serve_mla_longdoc", "serve_kda_longdoc"]


@pytest.mark.parametrize("name,unit,expected", [
    ("engine_starved_per_iter", "ms", 3.0),
    ("engine_starved_per_iter.chat", "ms", 3.0),
    ("engine_host_cpu_per_iter", "ms", 4.0),
    ("engine_host_cpu_per_iter.chat", "ms", 4.0),
    ("engine_get_cpu_per_iter", "ms", 1.0),
    ("engine_get_cpu_per_iter.chat", "ms", 1.0),
    ("engine_slot_update_per_iter", "ms", 1.5),
    ("engine_slot_update_per_iter.chat", "ms", 1.5),
    ("engine_slot_updates_per_iter", "ops", 7.0),
    ("engine_launch_per_iter", "ms", 2.0),
    ("engine_launch_waited_share", "%", 50.0),
    ("engine_pack_per_iter", "ms", 0.5),
    ("engine_notify_per_iter", "ms", 0.25),
    ("engine_process_cpu_per_iter", "ms", 9.0),
    ("engine_between_iters_per_iter", "ms", 6.0),
    ("engine_between_iters_per_iter.chat", "ms", 6.0),
])
def test_host_turn_metrics_read_the_change_and_nothing_from_a_parent(
        polled_ctx, name, unit, expected):
    from benchmarks.harness import manifest

    listed = next(m for m in manifest.load_manifest()["per_layer"]
                  if m["name"] == name)
    chat = name.endswith(".chat")
    cells = listed.pop("workloads")
    assert listed == {
        "name": name, "unit": unit, "better": "lower",
        "source": "program_counter", "layer": "Engine",
        "moves": "tpot_p50" if chat else "serve_tokens_per_s"}
    assert cells == ["serve_chat"] if chat \
        else cells[:len(SATURATED)] == SATURATED
    spec = manifest.metric_file(name)
    assert spec["reader"] == "engine_counters"
    # a parent's stats() has none of the counters: nothing is read
    assert engine_counters.read(polled_ctx, spec["params"]) is None
    for _t, s in polled_ctx["marks"]["polls"]:
        s.update({key: {k: v * s["iters"] for k, v in value.items()}
                  if isinstance(value, dict) else value * s["iters"]
                  for key, value in HOST_TURN.items()})
    assert engine_counters.read(polled_ctx, spec["params"]) == \
        pytest.approx(expected)


def test_queue_wait_percentile_of_the_histogram_difference(polled_ctx):
    # the difference: 10 waits in bucket 10 (63-100 ms), 3 in bucket 16
    # (1.0-1.58 s); rank 0.9 x 13 = 11.7 lies 1.7 of 3 into the latter
    p90 = engine_queue_wait.read(polled_ctx, {"q": 0.9})
    assert p90 == pytest.approx(1e3 * EDGES[15] * (EDGES[16] / EDGES[15]) ** (1.7 / 3))
    p50 = engine_queue_wait.read(polled_ctx, {"q": 0.5})
    assert 1e3 * EDGES[9] < p50 < 1e3 * EDGES[10]


def test_longest_iteration_inside_the_window_and_outside_the_profile(polled_ctx):
    polls = polled_ctx["marks"]["polls"]
    polls[1][1]["ring"]["rows"] = [row(1090.0, 9.0), row(1105.0, 0.8),
                                   row(1112.0, 1.4)]
    polls[3][1]["ring"]["rows"] = [row(1112.0, 1.4), row(1122.5, 6.0),
                                   row(1128.5, 0.9)]
    polled_ctx["device_report"] = {"engine": stats(
        140, 0, 0, ring=[row(1128.5, 0.9), row(1152.0, 7.0)],
        slow=[{"at": 1141.0, "total_s": 4.5}, {"at": 1050.0, "total_s": 30.0}])}
    # 9.0 and 30.0 began before the window, 7.0 after it, 6.0 inside the
    # profiler's call; the ring's 1.4 loses to the slow record the ring has
    # already dropped
    assert engine_longest_iter.read(polled_ctx, {}) == pytest.approx(4.5)
    polled_ctx["device_report"]["engine"]["slow_iters"] = []
    assert engine_longest_iter.read(polled_ctx, {}) == pytest.approx(1.4)


def test_longest_iteration_is_none_not_zero_when_none_was_recorded(polled_ctx):
    assert engine_longest_iter.read(polled_ctx, {}) is None
    polled_ctx["device_report"] = {"engine": {"decode_steps": 5, "active": 1}}
    assert engine_longest_iter.read(polled_ctx, {}) is None
    assert engine_longest_iter.read({}, {}) is None


def test_hops_percentile_reads_the_done_record():
    def rec(i, way_in_ms, back_ms, measured=True, error=None, hops=True):
        r = RequestRecord(i, 0.0, 1500, 64, measured)
        r.error = error
        r.done = {"done": True, "latency_s": 7.0}
        if hops:
            r.done["hops"] = {
                "proxy_recv": 1000.0, "engine_enter": 1000.0 + way_in_ms / 1e3,
                "first_push": 1005.0, "first_write": 1005.0 + back_ms / 1e3}
        return r

    records = [rec(0, 4.0, 300.0), rec(1, 6.0, 500.0), rec(2, 9.0, 900.0),
               rec(3, 500.0, 5000.0, measured=False),
               rec(4, 700.0, 7000.0, error="timeout"), rec(5, 0, 0, hops=False)]
    way_in = {"from": "proxy_recv", "to": "engine_enter", "q": 0.5}
    back = {"from": "first_push", "to": "first_write", "q": 0.5}
    assert hops_percentile.read({"records": records}, way_in) == pytest.approx(6.0)
    assert hops_percentile.read({"records": records}, back) == pytest.approx(500.0)
    # a parent commit's done record has no hops: nothing to read, no error
    assert hops_percentile.read({"records": [rec(0, 0, 0, hops=False)]}, way_in) is None
    assert hops_percentile.read({}, way_in) is None


def test_every_line_of_text_in_the_manifest_is_one_the_driver_takes():
    # test_manifest holds a cell's ``why`` to 200 characters; the driver holds
    # a configuration's ``why`` and ``source`` and a metric's ``layer`` to the
    # same rule (PR 44's first check was refused over a ``why`` of 203)
    import json
    import pathlib
    manifest = json.loads(
        (pathlib.Path(__file__).parent.parent / "BENCHMARK.json").read_text())
    lines = [(c["name"], c[key]) for c in manifest["configs"] for key in ("source", "why")]
    lines += [(w["name"], w["why"]) for w in manifest["workloads"]]
    lines += [(m["name"], m["layer"]) for m in manifest["per_layer"]]
    lines += [("command", word) for word in manifest["command"]]
    for name, text in lines:
        assert 1 <= len(text) <= 200 and text.isprintable(), (name, len(text))
