"""Spill-under-pressure + many-small-objects (reference: test_object_spilling*.py)."""

import json
import os
import socket
import threading
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.cluster import Cluster
from ray_tpu.core.rpc import SyncRpcClient


@pytest.fixture(scope="module")
def small_store_cluster():
    # 2 MB store: a handful of 512 KB arrays forces LRU spill
    c = Cluster(initialize_head=True,
                head_node_args={"num_cpus": 2, "object_store_memory": 2 * 1024 * 1024})
    ray_tpu.init(address=c.gcs_address)
    yield c
    ray_tpu.shutdown()
    c.shutdown()


def test_spill_under_pressure_and_restore(small_store_cluster):
    arrays = [np.full(128 * 1024, i, dtype=np.float32) for i in range(8)]  # 512KB each
    refs = [ray_tpu.put(a) for a in arrays]  # 4 MB total >> 2 MB capacity

    # the store never exceeds its budget: older objects spilled to disk
    agent = SyncRpcClient(small_store_cluster.nodes[0].address)
    try:
        usage = agent.call("node_info")["store"]
        assert usage["used"] <= usage["capacity"], usage
        assert usage.get("spilled", 0) > 0 or usage["used"] <= usage["capacity"]
    finally:
        agent.close()

    # every object restores transparently on get, LRU or not
    for i, ref in enumerate(refs):
        out = ray_tpu.get(ref, timeout=60)
        np.testing.assert_array_equal(out, arrays[i])


def test_many_small_objects_batched_get(small_store_cluster):
    """BASELINE envelope: a get() over hundreds of refs is one batched agent
    RPC, not a per-ref round-trip."""
    refs = [ray_tpu.put(i) for i in range(300)]
    t0 = time.perf_counter()
    vals = ray_tpu.get(refs, timeout=120)
    dt = time.perf_counter() - t0
    assert vals == list(range(300))
    assert dt < 30, f"batched get of 300 small objects took {dt:.1f}s"




def test_shuffle_larger_than_store_spills(small_store_cluster):
    """Distributed shuffle of a dataset larger than the 2MB object store:
    block data never aggregates on the driver and the store spills instead
    of failing (reference: test_object_spilling + exchange shuffle)."""
    from ray_tpu import data as rd

    # ~4MB of tensor rows across 8 blocks >> 2MB store
    ds = rd.range_tensor(4096, shape=(128,), parallelism=8).random_shuffle(seed=3)
    assert ds.count() == 4096


@pytest.mark.timeout_s(120)  # 6 s alone; without the spill it never ends
def test_return_blocked_by_its_own_pinned_dep_requeues_spills_and_fits():
    """The agent's busy requeue, end to end: a task's return has the bytes
    and no contiguous room because the task's OWN argument sits pinned in
    the middle of the arena. The store says so (`arena fragmented`), the
    agent sends the argument to spill before the requeue, the next dispatch
    restores it first-fit at the low end, and the same return fits. A
    requeue that left the argument where it was pinned it in the same place
    and failed the same way for ever."""
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    mb = 1024 * 1024
    c = Cluster(initialize_head=True,
                head_node_args={"num_cpus": 1, "object_store_memory": 2 * mb})
    ray_tpu.init(address=c.gcs_address)
    agent = SyncRpcClient(c.nodes[0].address)
    try:
        if agent.call("node_info")["store"]["backend"] != "arena":
            pytest.skip("segments backend: no arena to fragment")
        e = 2 * mb // 8
        low = ray_tpu.put(bytes(3 * e - 8192))
        dep = ray_tpu.put(bytes(e - 8192))
        low_hex = low.id.hex()
        del low  # [hole 3/8][dep 1/8][hole 4/8] once the GCS has freed it
        deadline = time.monotonic() + 30
        while agent.call("object_info", object_id=low_hex) is not None:
            assert time.monotonic() < deadline, "the first put was never freed"
            time.sleep(0.1)
        before = agent.call("node_info")["store"]

        @ray_tpu.remote
        def grow(x):
            return bytes(5 * len(x))  # 5/8 of the arena: neither hole holds it

        out = ray_tpu.get(grow.remote(dep), timeout=90)
        assert len(out) == 5 * (e - 8192)
        after = agent.call("node_info")["store"]
        assert after["spill_count"] > before["spill_count"], (before, after)
        assert after["restored_bytes"] > before["restored_bytes"], (before, after)
    finally:
        agent.close()
        ray_tpu.shutdown()
        c.shutdown()
