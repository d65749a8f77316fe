"""GCS crash-restart recovery (core/recovery/): chaos + reconstruction tests.

Reference capability: test_gcs_fault_tolerance.py — SIGKILL the head's GCS
under live load, the cluster must reconnect, resync, and finish with correct
results. The in-process tests drive the GCS server + transfer batcher
directly so the park/resync/window paths are hit deterministically.
"""

import asyncio
import os
import threading
import time
from types import SimpleNamespace

import pytest

import ray_tpu
from ray_tpu.cluster import Cluster
from ray_tpu.core.config import config
from ray_tpu.core.gcs.server import GcsServer
from ray_tpu.core.rpc import RpcClient, SyncRpcClient

OID_A = "aa" * 16
OID_B = "bb" * 16
NODE_1 = "11" * 16
NODE_2 = "22" * 16


# --------------------------------------------------------------------------- #
# end-to-end: SIGKILL the GCS under live task + actor load
# --------------------------------------------------------------------------- #
@pytest.mark.chaos
def test_gcs_sigkill_under_task_and_actor_load():
    """Kill -9 the persistent GCS mid-workload: tasks AND actor calls keep
    completing (epoch-aware retry on the driver, full resync on the agent),
    and the final results are exactly what a no-kill run produces."""
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    os.environ["RAY_TPU_RPC_RETRY_ATTEMPT_TIMEOUT_S"] = "1.0"
    try:
        c = Cluster(initialize_head=True, head_node_args={"num_cpus": 2},
                    gcs_persist=True)
        ray_tpu.init(address=c.gcs_address)

        @ray_tpu.remote
        def cube(x):
            return x ** 3

        @ray_tpu.remote
        class Counter:
            def __init__(self):
                self.total = 0

            def add(self, x):
                self.total += x
                return self.total

        counter = Counter.remote()
        results, actor_results, errors = [], [], []

        def work():
            for i in range(30):
                try:
                    results.append(ray_tpu.get(cube.remote(i), timeout=120))
                    actor_results.append(
                        ray_tpu.get(counter.add.remote(1), timeout=120))
                except Exception as e:  # noqa: BLE001
                    errors.append(e)

        t = threading.Thread(target=work)
        t.start()
        time.sleep(1.5)  # snapshot interval is 1.0s: state is on disk
        c.restart_gcs()  # SIGKILL + same-port restart
        t.join(timeout=300)
        assert not t.is_alive(), "workload wedged across GCS SIGKILL"
        assert not errors, errors[:3]
        assert sorted(results) == [i ** 3 for i in range(30)]
        # the actor survived (same process, monotonic counter: no lost or
        # double-applied calls)
        assert actor_results == list(range(1, 31))

        # the new incarnation advertises a bumped epoch, and the agent's
        # full re-registration lands on its next heartbeat epoch observation
        gcs = SyncRpcClient(c.gcs_address)
        try:
            dbg = gcs.call("debug_state")
            assert dbg["gcs_epoch"] >= 2
            deadline = time.monotonic() + 30
            while dbg["recovery"]["resyncs"] < 1 and time.monotonic() < deadline:
                time.sleep(0.2)
                dbg = gcs.call("debug_state")
            assert dbg["recovery"]["resyncs"] >= 1
        finally:
            gcs.close()
    finally:
        try:
            ray_tpu.shutdown()
            c.shutdown()
        except Exception:  # noqa: BLE001
            pass
        os.environ.pop("RAY_TPU_RPC_RETRY_ATTEMPT_TIMEOUT_S", None)


# --------------------------------------------------------------------------- #
# in-process: GCS restart mid-register_objects drain (transfer batcher)
# --------------------------------------------------------------------------- #
@pytest.mark.chaos
def test_gcs_restart_mid_registration_drain(tmp_path, monkeypatch):
    """The transfer-plane registration batcher is mid-drain when the GCS
    dies: the batch must PARK and land on the restarted incarnation instead
    of failing its waiters' pulls."""
    from ray_tpu.core.node.transfer import _RegistrationBatcher

    # short per-call timeout so the dead-GCS call fails fast into the park
    # loop instead of riding the 60s built-in retry window
    monkeypatch.setattr(config, "rpc_call_timeout_s", 1.0)
    monkeypatch.setattr(config, "rpc_retry_attempt_timeout_s", 0.3)

    async def scenario():
        gcs = GcsServer("127.0.0.1", 0, persist_dir=str(tmp_path))
        host, port = await gcs.start()
        client = await RpcClient(f"{host}:{port}").connect()
        batcher = _RegistrationBatcher(SimpleNamespace(gcs=client))
        await gcs.stop()  # dies before the drain's RPC can land

        reg = asyncio.ensure_future(
            batcher.register(object_id=OID_A, size=3, node_id=NODE_1))
        await asyncio.sleep(1.0)  # drain fired and is now parked
        assert not reg.done(), "batch failed instead of parking"

        gcs2 = GcsServer("127.0.0.1", port, persist_dir=str(tmp_path))
        await gcs2.start()
        try:
            await asyncio.wait_for(reg, timeout=30)
            info = await client.call("lookup_object", object_id=OID_A)
            assert NODE_1 in info["locations"]
            assert gcs2.gcs_epoch >= 2  # snapshot carried the old epoch
        finally:
            await client.close()
            await gcs2.stop()

    asyncio.run(scenario())


# --------------------------------------------------------------------------- #
# reconstruction window: stale snapshot locations vs agent re-reports
# --------------------------------------------------------------------------- #
@pytest.mark.chaos
def test_reconstruction_window_corrects_stale_holders(tmp_path, monkeypatch):
    """The restored snapshot claims objects on two nodes; only one node
    re-registers after the restart. While the window is open, loss is
    suppressed (no spurious lineage storms); once it closes, lookups return
    only live holders, the silent node is dead, and the object whose every
    copy vanished reports lost with its lineage intact for reconstruction."""
    monkeypatch.setattr(config, "gcs_reconstruction_window_s", 1.0)

    async def scenario():
        # incarnation 1: two nodes, A on both, B only on the doomed node
        gcs = GcsServer("127.0.0.1", 0, persist_dir=str(tmp_path))
        host, port = await gcs.start()
        for node in (NODE_1, NODE_2):
            await gcs.rpc_register_node(node, f"127.0.0.1:{port}", {"CPU": 1}, {})
        await gcs.rpc_register_objects(regs=[
            {"object_id": OID_A, "size": 8, "node_id": NODE_1},
            {"object_id": OID_A, "size": 8, "node_id": NODE_2},
            {"object_id": OID_B, "size": 8, "node_id": NODE_2},
        ])
        spec = {"task_id": "t1", "returns": [OID_B], "deps": []}
        await gcs.rpc_pin_task(task_holder=f"task:t1@{NODE_2}", deps=[],
                               returns=[OID_B], spec=spec)
        gcs._write_snapshot(gcs._snapshot_state())
        await gcs.stop()

        # incarnation 2: only NODE_1 comes back
        gcs2 = GcsServer("127.0.0.1", port, persist_dir=str(tmp_path))
        await gcs2.start()
        try:
            assert gcs2.recovery_window is not None
            assert gcs2.recovery_window.open
            # window open: B has zero confirmed copies but must NOT be lost
            info = await gcs2.rpc_lookup_object(OID_B)
            assert info["lost"] is False
            await gcs2.rpc_register_node(NODE_1, f"127.0.0.1:{port}",
                                         {"CPU": 1}, {})
            await gcs2.rpc_register_objects(regs=[
                {"object_id": OID_A, "size": 8, "node_id": NODE_1}])

            deadline = time.monotonic() + 10
            while gcs2.recovery_window.open and time.monotonic() < deadline:
                await asyncio.sleep(0.05)
            assert not gcs2.recovery_window.open

            # the sweep dropped NODE_2's unconfirmed provisional locations
            info_a = await gcs2.rpc_lookup_object(OID_A)
            assert info_a["locations"] == [NODE_1]
            info_b = await gcs2.rpc_lookup_object(OID_B)
            assert info_b["locations"] == []
            assert info_b["lost"] is True  # pullers fall back to lineage
            assert await gcs2.rpc_get_lineage(OID_B) == spec
            assert gcs2.nodes[NODE_2]["Alive"] is False
            dbg = await gcs2.rpc_debug_state()
            assert dbg["recovery"]["window_open"] is False
            assert dbg["recovery"]["provisional"] == 0
        finally:
            await gcs2.stop()

    asyncio.run(scenario())


@pytest.mark.chaos
def test_reconstruction_window_converges_early(tmp_path, monkeypatch):
    """Every provisional pair confirmed + every node re-registered closes
    the window well before the deadline (bench measures this as
    time-to-directory-converged)."""
    monkeypatch.setattr(config, "gcs_reconstruction_window_s", 30.0)

    async def scenario():
        gcs = GcsServer("127.0.0.1", 0, persist_dir=str(tmp_path))
        host, port = await gcs.start()
        await gcs.rpc_register_node(NODE_1, f"127.0.0.1:{port}", {"CPU": 1}, {})
        await gcs.rpc_register_objects(regs=[
            {"object_id": OID_A, "size": 8, "node_id": NODE_1}])
        gcs._write_snapshot(gcs._snapshot_state())
        await gcs.stop()

        gcs2 = GcsServer("127.0.0.1", port, persist_dir=str(tmp_path))
        await gcs2.start()
        try:
            assert gcs2.recovery_window.open
            start = time.monotonic()
            await gcs2.rpc_register_node(NODE_1, f"127.0.0.1:{port}",
                                         {"CPU": 1}, {})
            await gcs2.rpc_register_objects(regs=[
                {"object_id": OID_A, "size": 8, "node_id": NODE_1}])
            while gcs2.recovery_window.open and time.monotonic() - start < 10:
                await asyncio.sleep(0.02)
            assert not gcs2.recovery_window.open
            assert time.monotonic() - start < 5.0  # early, not the 30s deadline
            info = await gcs2.rpc_lookup_object(OID_A)
            assert info["locations"] == [NODE_1]
        finally:
            await gcs2.stop()

    asyncio.run(scenario())


@pytest.mark.chaos
def test_recovery_tasks_visible_in_stack_dump(tmp_path, monkeypatch):
    """dump_stacks must show a live recovery task by coroutine name, so a
    wedged reconstruction window is diagnosable from `ray_tpu stack`."""
    monkeypatch.setattr(config, "gcs_reconstruction_window_s", 30.0)

    async def scenario():
        gcs = GcsServer("127.0.0.1", 0, persist_dir=str(tmp_path))
        host, port = await gcs.start()
        await gcs.rpc_register_node(NODE_1, f"127.0.0.1:{port}", {"CPU": 1}, {})
        gcs._write_snapshot(gcs._snapshot_state())
        await gcs.stop()

        gcs2 = GcsServer("127.0.0.1", port, persist_dir=str(tmp_path))
        await gcs2.start()
        try:
            assert gcs2.recovery_window.open  # NODE_1 not yet re-registered
            dump = await gcs2.rpc_dump_stacks()
            assert "ReconstructionWindow.run" in dump
        finally:
            await gcs2.stop()

    asyncio.run(scenario())
