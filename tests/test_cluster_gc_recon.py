"""Distributed GC + lineage reconstruction on a real multi-process cluster.

Reference analogues: python/ray/tests/test_object_reconstruction.py (lineage
re-execution after node loss) and test_reference_counting.py (cluster-wide
release once every holder is gone).
"""

import os
import time

import pytest

import ray_tpu
from ray_tpu import exceptions
from ray_tpu.cluster import Cluster
from ray_tpu.core.resources import NodeAffinitySchedulingStrategy
from ray_tpu.core.rpc import SyncRpcClient

GRACE_S = 0.5


@pytest.fixture(scope="module")
def cluster():
    os.environ["RAY_TPU_OBJECT_REF_GRACE_S"] = str(GRACE_S)
    os.environ["RAY_TPU_REF_SYNC_INTERVAL_S"] = "0.02"
    os.environ["RAY_TPU_HEALTH_CHECK_PERIOD_MS"] = "200"
    try:
        c = Cluster(initialize_head=True, head_node_args={"num_cpus": 2})
        ray_tpu.init(address=c.gcs_address)
        yield c
        ray_tpu.shutdown()
        c.shutdown()
    finally:
        for k in ("RAY_TPU_OBJECT_REF_GRACE_S", "RAY_TPU_REF_SYNC_INTERVAL_S",
                  "RAY_TPU_HEALTH_CHECK_PERIOD_MS"):
            os.environ.pop(k, None)


def _gcs_debug(cluster):
    client = SyncRpcClient(cluster.gcs_address)
    try:
        return client.call("debug_state")
    finally:
        client.close()


def _object_exists(cluster, oid_hex: str):
    client = SyncRpcClient(cluster.gcs_address)
    try:
        rec = client.call("lookup_object", object_id=oid_hex)
        return bool(rec and rec["locations"])
    finally:
        client.close()


def _wait_sealed(cluster, oid_hex: str, timeout=60):
    """Wait until the object is registered in the directory WITHOUT pulling
    it anywhere (a get() would copy it to the head node and defeat the
    node-loss scenarios)."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if _object_exists(cluster, oid_hex):
            return
        time.sleep(0.05)
    raise TimeoutError(f"object {oid_hex[:12]} never sealed")


def _node_id_of(cluster, handle):
    client = SyncRpcClient(cluster.gcs_address)
    try:
        for info in client.call("get_nodes"):
            if info["NodeManagerAddress"] == handle.address and info["Alive"]:
                return info["NodeID"]
    finally:
        client.close()
    return None


# --------------------------------------------------------------- distributed GC
def test_release_frees_object_cluster_wide(cluster):
    ref = ray_tpu.put(list(range(1000)))
    oid = ref.id.hex()
    assert ray_tpu.get(ref, timeout=30) == list(range(1000))
    assert _object_exists(cluster, oid)
    del ref
    deadline = time.monotonic() + GRACE_S * 8 + 5
    while time.monotonic() < deadline:
        if not _object_exists(cluster, oid):
            return
        time.sleep(0.1)
    pytest.fail("object still registered after all refs dropped + grace")


def test_task_return_freed_after_drop(cluster):
    @ray_tpu.remote
    def produce():
        return "x" * 10_000

    ref = produce.remote()
    assert ray_tpu.get(ref, timeout=60) == "x" * 10_000
    oid = ref.id.hex()
    del ref
    deadline = time.monotonic() + GRACE_S * 8 + 5
    while time.monotonic() < deadline:
        if not _object_exists(cluster, oid):
            return
        time.sleep(0.1)
    pytest.fail("task return still registered after ref drop + grace")


def test_borrowed_ref_keeps_object_alive(cluster):
    @ray_tpu.remote
    class Holder:
        def __init__(self):
            self.ref = None

        def keep(self, refs):
            self.ref = refs[0]  # nested ref arrives as a BORROWED ObjectRef
            return True

        def read(self):
            return ray_tpu.get(self.ref, timeout=30)

    h = Holder.remote()
    ref = ray_tpu.put([1, 2, 3])
    oid = ref.id.hex()
    assert ray_tpu.get(h.keep.remote([ref]), timeout=60)
    del ref  # the driver's holder goes away; the actor's borrow must pin it
    time.sleep(GRACE_S * 4)
    assert _object_exists(cluster, oid), "borrowed object was freed prematurely"
    assert ray_tpu.get(h.read.remote(), timeout=30) == [1, 2, 3]


def test_args_pinned_through_queued_execution(cluster):
    @ray_tpu.remote
    def slow_identity(x):
        time.sleep(GRACE_S * 3)  # outlive the grace window while running
        return x

    inner = ray_tpu.put("payload")
    out = slow_identity.remote(inner)
    del inner  # only the task pin keeps the arg alive now
    assert ray_tpu.get(out, timeout=60) == "payload"


def test_nested_ref_pinned_by_container(cluster):
    """`return ray.put(x)`: the inner object's only long-term protector is
    the containment edge from the outer result object (the worker process
    drops its own holder when the task ends)."""
    @ray_tpu.remote
    def make_nested():
        inner = ray_tpu.put("inner-data")
        return [inner]

    outer = make_nested.remote()
    _wait_sealed(cluster, outer.id.hex())
    time.sleep(GRACE_S * 5)  # well past the worker-drop grace window
    inner_list = ray_tpu.get(outer, timeout=30)
    inner_oid = inner_list[0].id.hex()
    assert _object_exists(cluster, inner_oid), "nested ref freed prematurely"
    assert ray_tpu.get(inner_list[0], timeout=30) == "inner-data"
    # cascade: dropping the outer (and our borrowed inner ref) frees BOTH
    outer_oid = outer.id.hex()
    del outer, inner_list
    deadline = time.monotonic() + GRACE_S * 10 + 5
    while time.monotonic() < deadline:
        if not _object_exists(cluster, outer_oid) and not _object_exists(cluster, inner_oid):
            return
        time.sleep(0.1)
    pytest.fail("container/contained objects not freed after drop")


# ------------------------------------------------------- lineage reconstruction
def test_lost_object_is_reconstructed(cluster):
    node = cluster.add_node(num_cpus=2)
    cluster.wait_for_nodes(2)
    target = _node_id_of(cluster, node)
    assert target

    @ray_tpu.remote
    def produce(tag):
        return {"tag": tag, "pid": os.getpid()}

    strat = NodeAffinitySchedulingStrategy(node_id=target, soft=False)
    ref = produce.options(scheduling_strategy=strat).remote("recon")
    # wait for the seal WITHOUT get(): fetching would copy the object to the
    # head node and nothing would be lost with the kill
    _wait_sealed(cluster, ref.id.hex())

    cluster.remove_node(node)  # SIGKILL: all copies on that node are gone
    # wait until the GCS notices the death and purges locations
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        if not _object_exists(cluster, ref.id.hex()):
            break
        time.sleep(0.1)

    again = ray_tpu.get(ref, timeout=90)  # transparently re-executes produce
    assert again["tag"] == "recon"


def test_lost_actor_return_raises_object_lost(cluster):
    """A store-resident actor return (above the inline threshold, so it
    lives only in the producer node's arena) dies with its node: no
    lineage for actor tasks, so get() must raise, not hang."""
    node = cluster.add_node(num_cpus=1)
    cluster.wait_for_nodes(2)
    target = _node_id_of(cluster, node)
    assert target

    @ray_tpu.remote
    class P:
        def make(self):
            return "x" * (64 * 1024)  # > inline threshold: arena-resident

    strat = NodeAffinitySchedulingStrategy(node_id=target, soft=False)
    p = P.options(scheduling_strategy=strat).remote()
    ref = p.make.remote()
    _wait_sealed(cluster, ref.id.hex())

    cluster.remove_node(node)
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        if not _object_exists(cluster, ref.id.hex()):
            break
        time.sleep(0.1)

    with pytest.raises((exceptions.ObjectLostError, exceptions.GetTimeoutError)):
        ray_tpu.get(ref, timeout=20)


def test_small_actor_return_survives_producer_node_loss(cluster):
    """Pipelined protocol upgrade: a SMALL actor return rides inline in the
    completion to the caller, so losing the producer node after completion
    does not lose the value (the reference inlines small returns to the
    owner the same way)."""
    node = cluster.add_node(num_cpus=1)
    cluster.wait_for_nodes(2)
    target = _node_id_of(cluster, node)
    assert target

    @ray_tpu.remote
    class P:
        def make(self):
            return "actor-data"

    strat = NodeAffinitySchedulingStrategy(node_id=target, soft=False)
    p = P.options(scheduling_strategy=strat).remote()
    ref = p.make.remote()
    assert ray_tpu.get(ref, timeout=60) == "actor-data"  # completion absorbed

    cluster.remove_node(node)
    time.sleep(0.5)
    assert ray_tpu.get(ref, timeout=20) == "actor-data"


def test_reconstruction_with_lost_dependency_chain(cluster):
    node = cluster.add_node(num_cpus=2)
    cluster.wait_for_nodes(2)
    target = _node_id_of(cluster, node)
    assert target

    strat = NodeAffinitySchedulingStrategy(node_id=target, soft=False)

    @ray_tpu.remote
    def base():
        return 10

    @ray_tpu.remote
    def double(x):
        return x * 2

    a = base.options(scheduling_strategy=strat).remote()
    b = double.options(scheduling_strategy=strat).remote(a)
    _wait_sealed(cluster, b.id.hex())

    cluster.remove_node(node)
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        if not _object_exists(cluster, b.id.hex()):
            break
        time.sleep(0.1)

    # b reconstructs, which requires re-running base() for the lost dep too
    assert ray_tpu.get(b, timeout=90) == 20


def _lose_a_result_whose_argument_was_freed(cluster):
    """b = twice(base()) on a second node; base()'s result dropped by its
    only holder and freed by the GCS, lineage and all; then the node goes.
    Returns b, of which no copy is left."""
    node = cluster.add_node(num_cpus=2)
    cluster.wait_for_nodes(2)
    strat = NodeAffinitySchedulingStrategy(
        node_id=_node_id_of(cluster, node), soft=False)

    @ray_tpu.remote
    def base():
        return bytes(20_000)  # over inline_max_bytes: it lives in the store

    @ray_tpu.remote
    def twice(x):
        return x + x

    a = base.options(scheduling_strategy=strat).remote()
    b = twice.options(scheduling_strategy=strat).remote(a)
    _wait_sealed(cluster, b.id.hex())
    a_hex = a.id.hex()
    del a
    gcs = SyncRpcClient(cluster.gcs_address)
    try:
        deadline = time.monotonic() + 30
        while gcs.call("lookup_object", object_id=a_hex) is not None:
            assert time.monotonic() < deadline, "the argument was never freed"
            time.sleep(0.1)
    finally:
        gcs.close()

    cluster.remove_node(node)
    deadline = time.monotonic() + 30
    while _object_exists(cluster, b.id.hex()) and time.monotonic() < deadline:
        time.sleep(0.1)
    return b


@pytest.mark.timeout_s(150)
def test_reconstruction_refuses_when_an_argument_was_freed(cluster):
    """Lineage is one level deep unless the arguments are held: the GCS
    frees an unheld object with its lineage. A lost object whose producing
    task's argument went that way is reported lost, with the reason, where
    the re-run would wait for the argument for ever. The error is a STOPGAP
    for the recovery the next test asks for, and goes when that one
    passes."""
    b = _lose_a_result_whose_argument_was_freed(cluster)
    with pytest.raises(exceptions.ObjectLostError, match="was freed"):
        ray_tpu.get(b, timeout=60)


@pytest.mark.timeout_s(150)
@pytest.mark.xfail(strict=True, raises=exceptions.ObjectLostError,
                   reason="owed (ROADMAP Design 10 (b)): the GCS drops an "
                          "unheld object's lineage while a retained spec "
                          "still names it as an argument")
def test_reconstruction_reaches_an_argument_that_was_freed(cluster):
    """What a user's shuffle with unheld sources needs when it loses a node
    (test_data_shuffle.py's kill test holds its sources to get by): a
    retained spec keeps its arguments' lineage, so the lost object comes
    back by running base() and then twice() again."""
    b = _lose_a_result_whose_argument_was_freed(cluster)
    assert ray_tpu.get(b, timeout=60) == bytes(40_000)
