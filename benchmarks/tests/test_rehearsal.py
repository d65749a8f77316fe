"""The command end to end at tiny widths on the CPU backend: the same
control flow as a chip run (cluster, serve.run / TpuTrainer, HTTP streaming,
the Data feed, the reference check, teardown), refused as a measurement."""
import json
import os
import subprocess
import sys
import time

import pytest

from benchmarks.harness import manifest as mf
from benchmarks.harness import procs


def rehearse(workload, seconds, trace=0):
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", workload, "--seed",
         "3000000019", "--seconds", str(seconds), "--trace", str(trace),
         "--rehearse"], capture_output=True, text=True, cwd=mf.ROOT, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    return json.loads(lines[-1]), lines


SECONDS = {"serve": 5, "train": 3}


def cells():
    """Every cell of ``BENCHMARK.json`` with its configuration's ``kind``: a
    new cell is rehearsed at its ``rehearsal`` widths without an edit here."""
    manifest = mf.load_manifest()
    out = []
    for w in manifest["workloads"]:
        with open(mf.resolve_cell(manifest, w["name"])["config_file"]) as f:
            out.append((w["name"], json.load(f)["kind"]))
    return out


@pytest.mark.parametrize("workload,kind", cells())
def test_rehearsal_runs_and_reports_no_device_metric(workload, kind):
    out, lines = rehearse(workload, SECONDS[kind])
    assert set(out) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert out["rehearsal"] is True and out["metrics"] == {}
    assert out["device"]["platform"] == "cpu"
    assert out["attempted"] > 0 and out["failed"] == 0
    assert any(line.startswith("check ") for line in lines)
    check = out["observed"]["check"]
    if kind == "train":
        assert check["loss_abs_diff"] < 0.02 and check["grad_norm_rel_diff"] < 0.02
    else:
        assert check["decisions"] > 0 and check["mean_gap"] < 0.01


def test_without_a_chip_there_is_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("RAY_TPU_FAKE_TPU_CHIPS", None)
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "train_4k", "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=mf.ROOT, env=env, timeout=300)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_reaping_finds_marked_processes():
    token = procs.mark_environment()
    child = subprocess.Popen([sys.executable, "-c", "import time; time.sleep(60)"],
                             start_new_session=True)
    try:
        assert child.pid in procs.marked_pids(token)
        assert procs.reap_all(token, grace_s=0.2, limit_s=20) == []
        assert child.poll() is not None
    finally:
        child.kill()
        os.environ.pop(procs.MARKER, None)


def test_reaping_waits_for_a_process_whose_first_thread_is_gone():
    """``ps``: Zl, as a killed chip holder is for the seconds its last
    thread takes to leave libtpu. No environment finds it then: the snapshot
    before the teardown does, as a descendant, and it counts as running
    while ``stat`` counts a second thread."""
    token = procs.mark_environment()
    child = subprocess.Popen([sys.executable, "-c", (
        "import ctypes, threading, time\n"
        "threading.Thread(target=time.sleep, args=(60,)).start()\n"
        "ctypes.CDLL(None).pthread_exit(None)\n")], start_new_session=True)
    try:
        time.sleep(2.0)  # the first thread has left, the other sleeps on
        with open(f"/proc/{child.pid}/stat") as f:
            assert f.read().rsplit(")", 1)[1].split()[0] == "Z"
        assert child.pid not in procs.marked_pids(token)
        assert procs.running(child.pid)
        known = procs.snapshot(token)
        assert child.pid in known
        assert procs.reap_all(token, grace_s=0.2, limit_s=20, known=known) == []
        assert child.poll() is not None
    finally:
        child.kill()
        os.environ.pop(procs.MARKER, None)
