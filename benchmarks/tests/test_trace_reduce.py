"""The reduction from a trace to numbers: busy union, idle share, per-op
self time, gap ranking; on hand-made events and on a small recorded trace."""
import json
import os

import pytest

from benchmarks.harness import trace_reduce as tr

US = 1000  # ns


def synthetic():
    ops = [
        ["while.1", 0, 100 * US],          # encloses the next two
        ["fusion.a", 10 * US, 30 * US],
        ["paged_attention.3", 50 * US, 40 * US],
        ["copy.9", 150 * US, 50 * US],     # after a 50 us gap
        ["fusion.a", 230 * US, 20 * US],   # after a 30 us gap
    ]
    modules = [["jit_step(1)", 0, 100 * US], ["jit_step(1)", 150 * US, 100 * US]]
    host = [["engine", "device_get", 101 * US, 45 * US],
            ["engine", "tiny", 205 * US, 2 * US]]
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "host": host}


def test_busy_union_idle_and_self_times():
    s = tr.reduce(synthetic())
    assert s["planes"] == 1
    assert s["window_s"] == pytest.approx(250e-6)
    assert s["busy_s"] == pytest.approx(170e-6)          # 100 + 50 + 20
    assert s["op_self_s"]["while.1"] == pytest.approx(30e-6)   # 100 - 30 - 40
    assert s["op_self_s"]["fusion.a"] == pytest.approx(50e-6)
    assert s["op_self_s"]["paged_attention.3"] == pytest.approx(40e-6)
    assert sum(s["op_self_s"].values()) == pytest.approx(s["busy_s"])
    assert s["op_count"]["fusion.a"] == 2
    assert s["module_s"]["jit_step(1)"] == pytest.approx(200e-6)
    assert s["module_count"]["jit_step(1)"] == 2
    assert s["device_ops"][0] == ["fusion.a", pytest.approx(50e-6)] or \
        s["device_ops"][0][0] == "copy.9"


def test_gaps_are_ranked_and_attributed():
    s = tr.reduce(synthetic())
    assert [g[1] for g in s["idle_gaps"]] == [pytest.approx(50e-6), pytest.approx(30e-6)]
    assert s["idle_gaps"][0][0] == "engine:device_get"   # covers 45 of 50 us
    assert s["idle_gaps"][1][0] == "unattributed"        # 2 of 30 us is not a cause


def test_two_planes_average_busy_and_sum_ops():
    t = synthetic()
    t["devices"]["/device:TPU:1"] = {"ops": [["fusion.a", 0, 250 * US]], "modules": []}
    s = tr.reduce(t)
    assert s["planes"] == 2
    assert s["busy_s"] == pytest.approx((170e-6 + 250e-6) / 2)
    assert s["op_self_s"]["fusion.a"] == pytest.approx(300e-6)


def test_no_device_plane_reads_nothing():
    assert tr.reduce({"devices": {}, "host": []}) == {"planes": 0}
    assert tr.sanitize("a b,c/d") == "a_b_c_d"


RECORDED = os.path.join(os.path.dirname(__file__), "data", "small_trace.json")


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_recorded_trace_from_the_chip():
    with open(RECORDED) as f:
        rec = json.load(f)
    s = tr.reduce(rec["sample"])
    assert s["planes"] >= 1 and 0 < s["busy_s"] <= s["window_s"]
    assert sum(s["op_self_s"].values()) == pytest.approx(s["busy_s"] * s["planes"], rel=1e-6)
    assert s["device_ops"] == sorted(s["device_ops"], key=lambda x: -x[1])
    assert any("paged_attention" in n or "flash" in n or "fusion" in n
               for n, _ in s["device_ops"])
    assert len(s["idle_gaps"]) <= 10
    assert [g[1] for g in s["idle_gaps"]] == sorted((g[1] for g in s["idle_gaps"]), reverse=True)
    for name, expected in rec.get("expected", {}).items():
        assert s[name] == pytest.approx(expected, rel=1e-9)
