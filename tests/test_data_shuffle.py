"""Streaming distributed shuffle subsystem (ray_tpu/data/shuffle/).

Covers the ISSUE 9 acceptance surface: streaming-vs-barrier A/B equality
(same ShuffleSpec partition functions drive both), seeded-shuffle
determinism under out-of-order map completion, empty-partition schema
preservation, spill-aware reduce admission, an out-of-core sort whose
working set exceeds the arena, and a chaos run that SIGKILLs a partition
holder mid-shuffle and finishes through lineage re-execution."""

import os
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu import data as rd


# ------------------------------------------------------------------ local mode
@pytest.fixture
def local(ray_tpu_local):
    yield


def _ids(rows):
    return [r["id"] for r in rows]


def test_streaming_matches_barrier_for_every_exchange(local, monkeypatch):
    """RTPU_STREAMING_SHUFFLE must change scheduling, never data: sort,
    seeded shuffle, repartition and groupby produce identical results in
    both modes (the spec's partition fns are shared)."""
    def run_all():
        sort = _ids(rd.range(300, parallelism=6).sort("id", descending=True)
                    .take_all())
        shuf = _ids(rd.range(300, parallelism=6).random_shuffle(seed=11)
                    .take_all())
        rep = _ids(rd.range(101, parallelism=4).repartition(7).take_all())
        grp = sorted(
            (r["id"], r["count()"]) for r in
            rd.from_items([{"id": i % 5} for i in range(60)])
            .groupby("id").count().take_all())
        return sort, shuf, rep, grp

    monkeypatch.setenv("RTPU_STREAMING_SHUFFLE", "1")
    streaming = run_all()
    monkeypatch.setenv("RTPU_STREAMING_SHUFFLE", "0")
    barrier = run_all()
    assert streaming == barrier
    assert streaming[0] == sorted(range(300), reverse=True)
    assert streaming[2] == list(range(101))  # repartition preserves order


def test_seeded_shuffle_deterministic_under_out_of_order_maps(local):
    """Map RNGs derive from the block INDEX (spec.derive_rng), so two runs
    with identical seeds match even though map tasks complete in different
    orders across runs (stragglers injected via a jittery upstream map)."""
    def jitter(b):
        time.sleep(0.001 * int(b["id"][0]) % 3)
        return b

    def run():
        return _ids(rd.range(400, parallelism=8).map_batches(jitter)
                    .random_shuffle(seed=13).take_all())

    a, b = run(), run()
    assert a == b
    assert sorted(a) == list(range(400))
    assert a != list(range(400))


def test_empty_partitions_preserve_schema(local):
    """More reducers than rows: empty output partitions must still carry
    the schema (a column-less block breaks downstream column refs)."""
    out = rd.from_items([{"a": 1, "b": "x"}, {"a": 2, "b": "y"}]) \
        .repartition(8).take_all()
    assert out == [{"a": 1, "b": "x"}, {"a": 2, "b": "y"}]
    schema = rd.from_items([{"a": 1, "b": "x"}]).repartition(4).schema()
    assert schema is not None and set(schema.names) == {"a", "b"}
    # sort with empty partitions keeps schema + global order
    ds = rd.from_items([{"v": 3}, {"v": 1}]).sort("v")
    assert [r["v"] for r in ds.take_all()] == [1, 3]
    # shuffle of an empty-ish dataset survives
    assert rd.range(1, parallelism=1).random_shuffle(seed=0).count() == 1


def test_shuffle_stats_surface_in_dataset_stats(local):
    ds = rd.range(200, parallelism=4).random_shuffle(seed=5)
    assert ds.count() == 200
    report = ds.stats()
    assert "shuffle_map(random_shuffle)" in report
    assert "shuffle_reduce(random_shuffle)" in report
    assert "exchange_bytes" in report
    rows = ds.stats_rows()
    reduce_row = next(r for r in rows if "shuffle_reduce" in r["operator"])
    extra = reduce_row["extra"]
    assert extra["maps"] == 4 and extra["reduces"] == 4
    assert extra["exchange_bytes"] > 0
    assert extra["admission_stall_s"] >= 0.0


def test_reduce_admission_defers_under_tiny_budget(local, monkeypatch):
    """An admission budget far below one partition set must DEFER reduces
    (spill-aware admission) yet still complete via the one-in-flight
    liveness guarantee."""
    monkeypatch.setenv("RAY_TPU_SHUFFLE_ADMISSION_MEMORY_FRACTION", "1e-9")
    ds = rd.range(2000, parallelism=8).random_shuffle(seed=3)
    assert ds.count() == 2000
    rows = ds.stats_rows()
    extra = next(r for r in rows if "shuffle_reduce" in r["operator"])["extra"]
    assert extra["admission_deferrals"] > 0
    assert extra["admission_stall_s"] > 0.0


def test_exchange_ops_participate_in_memory_budget(local):
    """Satellite: exchange/reduce outputs no longer bypass the per-op
    ResourceManager accounting that backpressures every other operator."""
    from ray_tpu.data.execution.operators import AllToAllOp
    from ray_tpu.data.execution.planner import build_physical_plan
    from ray_tpu.data.execution.resource_manager import ResourceManager
    from ray_tpu.data.shuffle.operators import ShuffleMapOp, ShuffleReduceOp

    ds = rd.range(64, parallelism=4).random_shuffle(seed=1)
    ops = build_physical_plan(ds._source_fn, ds._stages)
    assert any(isinstance(op, ShuffleMapOp) for op in ops)
    reduce_op = next(op for op in ops if isinstance(op, ShuffleReduceOp))
    rm = ResourceManager(ops, memory_budget_bytes=1 << 20, cpu_total=8)
    assert id(reduce_op) in rm._reserved  # reserves budget like any task op
    barrier = AllToAllOp("x", lambda refs: iter(()))
    assert barrier.in_memory_budget()
    rm2 = ResourceManager([barrier], memory_budget_bytes=1 << 20, cpu_total=8)
    assert id(barrier) in rm2._reserved


def test_streaming_shuffle_env_fallback_compiles_barrier(local, monkeypatch):
    from ray_tpu.data.execution.operators import AllToAllOp
    from ray_tpu.data.execution.planner import build_physical_plan

    ds = rd.range(64, parallelism=4).sort("id")
    monkeypatch.setenv("RTPU_STREAMING_SHUFFLE", "0")
    ops = build_physical_plan(ds._source_fn, ds._stages)
    assert any(isinstance(op, AllToAllOp) for op in ops)
    monkeypatch.setenv("RTPU_STREAMING_SHUFFLE", "1")
    ops = build_physical_plan(ds._source_fn, ds._stages)
    assert not any(isinstance(op, AllToAllOp) for op in ops)


# ---------------------------------------------------------------- cluster mode
@pytest.fixture
def shuffle_cluster():
    """Head-only cluster with a deliberately tiny (2 MB) arena: any real
    shuffle working set exceeds it, exercising spill-aware admission."""
    from ray_tpu.cluster import Cluster

    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    c = Cluster(initialize_head=True,
                head_node_args={"num_cpus": 2,
                                "object_store_memory": 2 * 1024 * 1024})
    ray_tpu.init(address=c.gcs_address)
    yield c
    ray_tpu.shutdown()
    c.shutdown()


@pytest.mark.timeout_s(120)  # 8 s alone, 7-15 s beside 12 CPU burners
def test_out_of_core_sort_completes_with_spill(shuffle_cluster):
    """A sort whose working set (~4 MB input + partitions + outputs) far
    exceeds the 2 MB arena completes through spill-aware admission, emits
    globally ordered blocks, and actually spilled."""
    n = 4096
    ds = rd.range_tensor(n, shape=(128,), parallelism=8)

    def keyed(b):
        # mix the ids so the sort has real work: descending key
        return {"k": (n - 1) - b["data"][:, 0], "data": b["data"]}

    sorted_ds = ds.map_batches(keyed).sort("k")
    prev = -1
    total = 0
    for ref in sorted_ds.iter_internal_refs():
        block = ray_tpu.get(ref, timeout=120)
        col = block.column("k").to_numpy()
        if len(col) == 0:
            continue
        assert np.all(np.diff(col) >= 0), "block not internally sorted"
        assert col[0] >= prev, "blocks not globally ordered"
        prev = int(col[-1])
        total += len(col)
    assert total == n

    from ray_tpu.core.rpc import SyncRpcClient

    agent = SyncRpcClient(shuffle_cluster.nodes[0].address)
    try:
        usage = agent.call("node_info")["store"]
        assert usage["spilled_bytes"] > 0, usage  # out-of-core actually spilled
        assert usage["used"] <= usage["capacity"], usage
    finally:
        agent.close()


@pytest.fixture
def chaos_cluster():
    os.environ["RAY_TPU_HEALTH_CHECK_PERIOD_MS"] = "200"
    try:
        from ray_tpu.cluster import Cluster

        if ray_tpu.is_initialized():
            ray_tpu.shutdown()
        c = Cluster(initialize_head=True, head_node_args={"num_cpus": 2})
        ray_tpu.init(address=c.gcs_address)
        yield c
        ray_tpu.shutdown()
        c.shutdown()
    finally:
        os.environ.pop("RAY_TPU_HEALTH_CHECK_PERIOD_MS", None)


@pytest.mark.timeout_s(180)  # 12 s alone, 17-22 s beside 12 CPU burners
def test_kill_partition_holder_mid_shuffle_lineage_recovers(chaos_cluster):
    """SIGKILL a node holding map partition blocks after the reduce phase
    has started: surviving reduces must re-materialize their lost inputs
    through lineage re-execution (split tasks re-run from their retained
    specs) and the shuffle must deliver every row."""
    node = chaos_cluster.add_node(num_cpus=2)
    chaos_cluster.wait_for_nodes(2, timeout=60)

    n = 1200
    # The source blocks are HELD for the length of the shuffle: a split task
    # can run again only while its argument is alive or has lineage, and the
    # GCS frees an unheld block, lineage and all, object_ref_grace_s (2 s)
    # after the map stage drops it. Unheld, which is what a user's shuffle
    # is, this passes when the kill falls inside that grace or takes nothing
    # a split made, and otherwise ends in the agent's refusal (a stopgap:
    # ObjectLostError inside a TaskError). That a freed
    # argument SHOULD come back is ROADMAP Design 10 (b), and its witness,
    # which fails every time until then, is
    # test_cluster_gc_recon.py::test_reconstruction_reaches_an_argument_that_was_freed.
    src = rd.range(n, parallelism=8).materialize()
    ds = src.random_shuffle(seed=9)
    it = ds.iter_internal_refs()
    first = ray_tpu.get(next(it), timeout=120)  # reduce phase has begun
    seen = first.num_rows
    ids = list(first.column("id").to_numpy())

    chaos_cluster.remove_node(node)  # SIGKILL: partitions on it are gone

    for ref in it:
        block = ray_tpu.get(ref, timeout=180)
        seen += block.num_rows
        ids.extend(block.column("id").to_numpy())
    assert seen == n
    assert sorted(ids) == list(range(n))


# ----------------------------------------------------------------- slow bench
@pytest.mark.slow
def test_multi_gb_shuffle_smoke(shutdown_only):
    """Multi-GB-scale shuffle (slow tier only): the bench-sized workload
    tools/bench_shuffle.py drives, as a correctness smoke."""
    ray_tpu.init(num_cpus=8)
    n = 200_000
    ds = rd.range_tensor(n, shape=(64,), parallelism=16).random_shuffle(seed=1)
    assert ds.count() == n


# ----------------------------------------------------- columnar exchange (17)
def _exchange_results():
    sort = _ids(rd.range(300, parallelism=6).sort("id", descending=True)
                .take_all())
    shuf = _ids(rd.range(300, parallelism=6).random_shuffle(seed=11)
                .take_all())
    rep = _ids(rd.range(101, parallelism=4).repartition(7).take_all())
    grp = sorted(
        (r["id"], r["count()"]) for r in
        rd.from_items([{"id": i % 5} for i in range(60)])
        .groupby("id").count().take_all())
    return sort, shuf, rep, grp


def test_columnar_exchange_ab_identical_all_exchanges(local, monkeypatch):
    """RTPU_COLUMNAR_EXCHANGE flips the partition/merge kernels (argsort
    scatter + map pre-sort/k-way merge vs n-scan takes + full re-sort) but
    may never change results: all four exchanges are byte-identical in both
    columnar modes and both exchange modes."""
    out = {}
    for columnar in ("1", "0"):
        monkeypatch.setenv("RTPU_COLUMNAR_EXCHANGE", columnar)
        for streaming in ("1", "0"):
            monkeypatch.setenv("RTPU_STREAMING_SHUFFLE", streaming)
            out[(columnar, streaming)] = _exchange_results()
    assert len(set(map(repr, out.values()))) == 1
    assert out[("1", "1")][0] == sorted(range(300), reverse=True)


def test_sort_skew_bounded_under_duplicate_keys(local, monkeypatch):
    """Regression for range-sort skew: with 90% of rows sharing one key,
    boundary dedupe + round-robin tie spreading must keep every reducer
    partition well below the naive all-ties-in-one-reducer 90%."""
    rng = np.random.default_rng(0)
    n = 4000
    keys = np.where(rng.random(n) < 0.9, 7, rng.integers(0, 100, n))
    rows = [{"k": int(k), "i": i} for i, k in enumerate(keys)]
    for columnar in ("1", "0"):
        monkeypatch.setenv("RTPU_COLUMNAR_EXCHANGE", columnar)
        ds = rd.from_items(rows, parallelism=8).sort("k")
        sizes, ks = [], []
        for ref in ds.iter_internal_refs():
            block = ray_tpu.get(ref)
            sizes.append(block.num_rows)
            ks.extend(block.column("k").to_numpy())
        assert sum(sizes) == n
        assert ks == sorted(ks)
        assert max(sizes) < 0.5 * n, (columnar, sizes)


def test_concat_blocks_empty_keeps_schema():
    import pyarrow as pa

    from ray_tpu.data.block import concat_blocks
    from ray_tpu.data.shuffle.spec import _schema_preserving_concat

    schema = pa.schema([("a", pa.int64()), ("b", pa.string())])
    empty = concat_blocks([], schema=schema)
    assert empty.num_rows == 0 and empty.schema.equals(schema)
    assert concat_blocks([]).num_rows == 0  # schema-less still works
    # reduce-side: all-empty partition list keeps the spec's schema
    out = _schema_preserving_concat([], schema=schema)
    assert out.schema.equals(schema)
    # and an empty part next to a real one doesn't poison the concat
    real = pa.table({"a": [1], "b": ["x"]})
    out = _schema_preserving_concat([pa.table({}), real])
    assert out.num_rows == 1 and out.schema.equals(schema)


def test_iter_batches_through_empty_partitions(local, monkeypatch):
    """dataset._batch_iterator carries a remainder block between output
    partitions; empty exchange partitions (8 reducers, 3 rows) must not
    break the carry concat with a schema-less block."""
    for columnar in ("1", "0"):
        monkeypatch.setenv("RTPU_COLUMNAR_EXCHANGE", columnar)
        ds = rd.from_items([{"a": 1}, {"a": 2}, {"a": 3}]).repartition(8)
        batches = list(ds.iter_batches(batch_size=2, batch_format="numpy"))
        got = sorted(int(v) for b in batches for v in b["a"])
        assert got == [1, 2, 3]


def test_mixed_tensor_pyobj_block_through_columnar_sort(local, monkeypatch):
    """Blocks mixing a fast (tensor) column with a pyobj column take the
    vectorized scatter but fall back off the comparison merge only when the
    KEY itself isn't fast — here the key is fast, the payload is not, and
    both must survive the exchange intact."""
    monkeypatch.setenv("RTPU_COLUMNAR_EXCHANGE", "1")

    class Tag:
        def __init__(self, v):
            self.v = v

    rows = [{"k": (97 * i) % 50, "vec": np.arange(4) + i, "obj": Tag(i)}
            for i in range(120)]
    out = rd.from_items(rows, parallelism=5).sort("k").take_all()
    assert [r["k"] for r in out] == sorted(r["k"] for r in rows)
    for r in out:
        assert isinstance(r["obj"], Tag)
        assert r["vec"][0] == r["obj"].v
    # pyobj SORT KEY: comparison kernels must bail to pc.sort_indices
    str_rows = [{"k": f"key-{i % 7}", "i": i} for i in range(40)]
    got = [r["k"] for r in rd.from_items(str_rows, parallelism=3)
           .sort("k").take_all()]
    assert got == sorted(r["k"] for r in str_rows)


def test_table_ipc_serializer_roundtrip(monkeypatch):
    """Unit: under the flag a pa.Table pickles as ONE out-of-band IPC
    buffer; decode over the payload is zero-copy for fast columns (buffer
    addresses alias the payload) and the decode stats split fast vs
    fallback bytes. Flag off falls back to the default Table pickle."""
    import pyarrow as pa

    from ray_tpu.core import serialization as ser
    from ray_tpu.data.block import block_from_rows

    monkeypatch.setenv("RTPU_COLUMNAR_EXCHANGE", "1")
    t = pa.table({"k": np.arange(256, dtype=np.int64)})
    payload, _refs = ser.pack(t)
    before = ser.arrow_decode_snapshot()
    out = ser.unpack(memoryview(payload), zero_copy=True)
    assert out.equals(t)
    buf = out.column("k").chunk(0).buffers()[1]
    pb = pa.py_buffer(payload)
    assert pb.address <= buf.address < pb.address + pb.size
    after = ser.arrow_decode_snapshot()
    assert after["zero_copy_bytes"] - before["zero_copy_bytes"] == 256 * 8
    # pyobj columns decode but count as copied bytes
    t2 = block_from_rows([{"o": object()} for _ in range(3)],
                         object_columns={"o"})
    p2, _ = ser.pack(t2)
    before = ser.arrow_decode_snapshot()
    out2 = ser.unpack(memoryview(p2), zero_copy=True)
    assert out2.schema.equals(t2.schema) and out2.num_rows == 3
    assert ser.arrow_decode_snapshot()["copied_bytes"] > before["copied_bytes"]
    # flag off: default pickle path round-trips too (A/B hatch)
    monkeypatch.setenv("RTPU_COLUMNAR_EXCHANGE", "0")
    p3, _ = ser.pack(t)
    assert ser.unpack(memoryview(p3), zero_copy=True).equals(t)


def test_bench_shuffle_smoke_asserts_equality(shutdown_only):
    """tools/bench_shuffle.py --smoke runs both columnar settings and
    asserts every (streaming, columnar) combo emits identical output
    sequences — wired into tier-1 so the A/B harness itself stays green."""
    import json as _json
    import subprocess
    import sys as _sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("RTPU_COLUMNAR_EXCHANGE", None)
    p = subprocess.run(
        [_sys.executable, os.path.join(repo, "tools", "bench_shuffle.py"),
         "--smoke"],
        capture_output=True, text=True, timeout=300, env=env, cwd=repo)
    assert p.returncode == 0, p.stdout + p.stderr
    lines = [_json.loads(l) for l in p.stdout.splitlines()
             if l.startswith("{")]
    assert any(l.get("result_equality") == "ok" for l in lines)
    metrics = {l["metric"] for l in lines if "metric" in l}
    assert "shuffle_sort_streaming_gbps_per_node" in metrics
    assert "shuffle_sort_streaming_legacy_gbps_per_node" in metrics


def test_worker_arg_table_aliases_arena(monkeypatch):
    """Cluster: a task's pa.Table argument decodes as views over the shm
    ARENA itself (not a heap copy) — the pinned-args zero-copy path. Only
    ObjectRef args ride the object plane (plain args travel in-band in the
    task spec), so the table is put() first — exactly how shuffle blocks
    travel. The assertion compares the column buffer address against the
    worker's own arena mapping; skipped on the segments backend (no stable
    mapping)."""
    import ctypes

    from ray_tpu.cluster import Cluster

    monkeypatch.setenv("RTPU_COLUMNAR_EXCHANGE", "1")
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    c = Cluster(initialize_head=True, head_node_args={"num_cpus": 2})
    ray_tpu.init(address=c.gcs_address)
    try:
        import pyarrow as pa

        @ray_tpu.remote
        def probe(t):
            import ctypes as _ct

            from ray_tpu import api as _api
            from ray_tpu.core.shm_store import attach_arena

            addr = t.column("k").chunk(0).buffers()[1].address
            node_hex = _api.global_worker().runtime.node_hex
            try:
                arena = attach_arena(node_hex)
            except (FileNotFoundError, OSError):
                return {"backend": "segments"}
            base = _ct.addressof(arena._buf)
            return {"backend": "arena", "sum": int(t.column("k").to_numpy().sum()),
                    "aliased": base <= addr < base + arena.capacity}

        table = pa.table({"k": np.arange(50_000, dtype=np.int64)})
        out = ray_tpu.get(probe.remote(ray_tpu.put(table)), timeout=60)
        if out["backend"] == "segments":
            pytest.skip("arena backend unavailable (segments fallback)")
        assert out["aliased"] is True
        assert out["sum"] == int(np.arange(50_000, dtype=np.int64).sum())
    finally:
        ray_tpu.shutdown()
        c.shutdown()
