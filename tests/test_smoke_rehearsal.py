"""``chip_smoke.py --rehearse``: the chip script's whole control flow (cluster,
chip lease and hand-over, TpuTrainer, serve.run over HTTP) at a tiny size on
the CPU backend with a fake chip. Over a minute, so it has a file of its own.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_rehearsal_passes_and_never_says_tpu():
    out = subprocess.run(
        [sys.executable, "chip_smoke.py", "--rehearse"], cwd=REPO, text=True,
        capture_output=True, timeout=600)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    records = [json.loads(ln) for ln in out.stdout.splitlines() if ln.strip()]
    assert [r.get("phase") for r in records] == [
        "store", "resources", "train", "serve", "done", None]
    last = records[-1]
    assert last["ok"] is True and last["rehearsal"] is True
    assert last["device"]["platform"] == "cpu"
    assert all(r.get("platform", "cpu") != "tpu" for r in records)
    pids = {records[2]["worker_pid"], records[3]["replica_pid"],
            records[4]["driver_pid"]}
    assert len(pids) == 3  # train and serve ran in their own workers
    assert records[4]["driver_jax_backend_initialised"] is False
