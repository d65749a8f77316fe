"""Dead-owner shm-arena reclamation (VERDICT r5 weak #4: SIGKILLed clusters
leaked /dev/shm/rtpu-arena-* files forever — multi-GB of shm pinned until
reboot). Every agent/cluster startup sweeps arenas whose recorded owner pid
is gone."""

import os
import subprocess
import sys
import time

import pytest

from ray_tpu.core.shm_store import (
    arena_owner_alive,
    find_orphan_arenas,
    sweep_dead_arenas,
    write_arena_pidfile,
)

pytestmark = pytest.mark.skipif(
    not os.path.isdir("/dev/shm"), reason="no /dev/shm on this platform"
)


def _dead_pid() -> int:
    p = subprocess.Popen([sys.executable, "-c", "pass"])
    p.wait()
    return p.pid


def _fake_arena(name: str, pid: int) -> str:
    path = f"/dev/shm/rtpu-arena-{name}"
    write_arena_pidfile(path, pid=pid)
    with open(path, "wb") as f:
        f.write(b"\0" * 128)
    return path


def test_sweep_reclaims_dead_owner_keeps_live_owner():
    dead = _fake_arena("deadbeef", _dead_pid())
    live = _fake_arena("cafebabe", os.getpid())
    try:
        assert not arena_owner_alive(dead)
        assert arena_owner_alive(live)
        assert dead in find_orphan_arenas()
        removed = sweep_dead_arenas()
        assert dead in removed
        assert not os.path.exists(dead)
        assert not os.path.exists(dead + ".pid")
        # the live arena (this test process owns it) must survive the sweep
        assert os.path.exists(live) and os.path.exists(live + ".pid")
    finally:
        for p in (dead, live, dead + ".pid", live + ".pid"):
            try:
                os.unlink(p)
            except OSError:
                pass


def test_arena_without_pidfile_counts_as_orphan():
    path = "/dev/shm/rtpu-arena-nopidfil"
    with open(path, "wb") as f:
        f.write(b"\0" * 64)
    try:
        assert not arena_owner_alive(path)
        sweep_dead_arenas()
        assert not os.path.exists(path)
    finally:
        try:
            os.unlink(path)
        except OSError:
            pass


@pytest.mark.timeout_s(120)  # 2 s beside 12 CPU burners
def test_sigkilled_cluster_arenas_reclaimed_by_next_cluster():
    """Chaos: SIGKILL a whole cluster (agents never run cleanup()), then
    assert the NEXT cluster's startup reclaims its arena files."""
    from ray_tpu.cluster import Cluster
    from ray_tpu.core.rpc import SyncRpcClient

    c = Cluster(initialize_head=True, head_node_args={"num_cpus": 1})
    try:
        gcs = SyncRpcClient(c.gcs_address)
        try:
            prefixes = [n["NodeID"][:8] for n in gcs.call("get_nodes")]
        finally:
            gcs.close()
        assert prefixes
        # segments backend (no native lib) creates no arena: fabricate one
        # owned by the real (about-to-die) agent so the sweep path is
        # exercised either way
        arena_paths = []
        for prefix, node in zip(prefixes, c.nodes):
            path = f"/dev/shm/rtpu-arena-{prefix}"
            if not os.path.exists(path):
                write_arena_pidfile(path, pid=node.proc.pid)
                with open(path, "wb") as f:
                    f.write(b"\0" * 128)
            arena_paths.append(path)
    except BaseException:
        c.shutdown()
        raise

    # SIGKILL everything — no graceful shutdown, no cleanup()
    for node in c.nodes:
        node.kill()
        node.proc.wait()  # reap: a zombie pid still counts as alive
    c.kill_gcs()
    time.sleep(0.2)
    for path in arena_paths:
        # gone already = another suite's cluster started on this machine
        # meanwhile (xdist runs six): its startup is a janitor as well
        assert not os.path.exists(path) or not arena_owner_alive(path)

    # next cluster's startup is the janitor
    c2 = Cluster(initialize_head=True, head_node_args={"num_cpus": 1})
    try:
        for path in arena_paths:
            assert not os.path.exists(path), (
                f"new cluster did not reclaim orphaned arena {path}"
            )
    finally:
        c2.shutdown()


@pytest.mark.timeout_s(120)  # 12 s alone: shutdown waits out its GCS lookup
def test_shutdown_with_a_dead_gcs_takes_its_own_arenas():
    """Cluster.shutdown() learns its nodes' ids from the GCS; when that is
    dead it still takes the arenas whose pidfile names one of ITS agents,
    a removed node's too, and leaves nothing to a later cluster's start."""
    import glob

    from ray_tpu.cluster import Cluster
    from ray_tpu.core.shm_store import arena_owner

    c = Cluster(initialize_head=True, head_node_args={"num_cpus": 1})
    try:
        second = c.add_node(num_cpus=1)
        agents = {n.proc.pid for n in c.nodes}
        mine = [p for p in glob.glob("/dev/shm/rtpu-arena-*")
                if not p.endswith(".pid") and arena_owner(p) in agents]
        if not mine:
            pytest.skip("segments backend: the agents made no arena")
        assert len(mine) == 2, mine
        c.remove_node(second)
        c.kill_gcs()
    finally:
        c.shutdown()
    left = [p for p in mine if os.path.exists(p) or os.path.exists(p + ".pid")]
    assert not left, left
