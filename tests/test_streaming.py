"""Streaming generators: num_returns="streaming" + ObjectRefGenerator.

Reference capability: python/ray/_raylet.pyx:281 (ObjectRefGenerator),
:1206,1263 (per-item report paths); python/ray/tests/test_streaming_generator.py
is the model for the scenarios. Done-criteria (VERDICT r2 item 1): a remote
generator yields 1,000 items consumed incrementally with flat memory.
"""

import threading
import time

import pytest

import ray_tpu
from ray_tpu.cluster import Cluster
from ray_tpu.core.rpc import SyncRpcClient


# --------------------------------------------------------------------- local


def test_streaming_basic(ray_tpu_local):
    @ray_tpu.remote(num_returns="streaming")
    def gen(n):
        for i in range(n):
            yield i * 10

    g = gen.remote(5)
    assert isinstance(g, ray_tpu.ObjectRefGenerator)
    vals = [ray_tpu.get(r) for r in g]
    assert vals == [0, 10, 20, 30, 40]
    assert g.completed()


def test_streaming_empty_and_dynamic_alias(ray_tpu_local):
    @ray_tpu.remote(num_returns="dynamic")
    def empty():
        if False:
            yield 1

    assert list(empty.remote()) == []


def test_streaming_error_mid_stream(ray_tpu_local):
    @ray_tpu.remote(num_returns="streaming")
    def boom():
        yield 1
        yield 2
        raise ValueError("mid-stream")

    it = iter(boom.remote())
    assert ray_tpu.get(next(it)) == 1
    assert ray_tpu.get(next(it)) == 2
    with pytest.raises(ValueError, match="mid-stream"):
        ray_tpu.get(next(it))
    with pytest.raises(StopIteration):
        next(it)


def test_streaming_not_a_generator(ray_tpu_local):
    @ray_tpu.remote(num_returns="streaming")
    def notgen():
        return 42

    it = iter(notgen.remote())
    with pytest.raises(Exception, match="generator"):
        ray_tpu.get(next(it))


def test_streaming_backpressure_blocks_producer(ray_tpu_local):
    produced = []

    @ray_tpu.remote(num_returns="streaming", _generator_backpressure=4)
    def gen():
        for i in range(50):
            produced.append(i)  # local mode: closure shared in-process
            yield i

    it = iter(gen.remote())
    first = ray_tpu.get(next(it))
    assert first == 0
    time.sleep(0.5)  # give the producer time to run ahead if unbounded
    # consumer at index 1: producer may be at most backpressure items ahead
    assert len(produced) <= 1 + 4 + 1, produced
    rest = [ray_tpu.get(r) for r in it]
    assert rest == list(range(1, 50))
    assert len(produced) == 50


def test_streaming_early_close_stops_producer(ray_tpu_local):
    produced = []
    stopped = threading.Event()

    @ray_tpu.remote(num_returns="streaming", _generator_backpressure=2)
    def gen():
        try:
            for i in range(10_000):
                produced.append(i)
                yield i
        finally:
            stopped.set()

    g = gen.remote()
    it = iter(g)
    ray_tpu.get(next(it))
    g.close()
    assert stopped.wait(5.0), "producer did not stop after close()"
    assert len(produced) < 100


def test_streaming_actor_sync(ray_tpu_local):
    @ray_tpu.remote
    class Streamer:
        def tokens(self, n):
            for i in range(n):
                yield f"tok{i}"

        def plain(self):
            return "ok"

    a = Streamer.remote()
    toks = [ray_tpu.get(r) for r in a.tokens.options(num_returns="streaming").remote(5)]
    assert toks == [f"tok{i}" for i in range(5)]
    # non-streaming calls on the same actor still work
    assert ray_tpu.get(a.plain.remote()) == "ok"


def test_streaming_actor_async(ray_tpu_local):
    @ray_tpu.remote
    class AsyncStreamer:
        async def tokens(self, n):
            for i in range(n):
                yield i + 100

    a = AsyncStreamer.remote()
    vals = [ray_tpu.get(r) for r in a.tokens.options(num_returns="streaming").remote(4)]
    assert vals == [100, 101, 102, 103]


def test_streaming_async_iteration(ray_tpu_local):
    import asyncio

    @ray_tpu.remote(num_returns="streaming")
    def gen(n):
        for i in range(n):
            yield i

    async def consume():
        out = []
        async for ref in gen.remote(6):
            out.append(ray_tpu.get(ref))
        return out

    assert asyncio.run(consume()) == list(range(6))


def test_streaming_refs_usable_out_of_order(ray_tpu_local):
    @ray_tpu.remote(num_returns="streaming")
    def gen():
        yield "a"
        yield "b"
        yield "c"

    refs = list(gen.remote())
    # collected first, resolved later, in any order
    assert ray_tpu.get(refs[2]) == "c"
    assert ray_tpu.get(refs[0]) == "a"
    assert ray_tpu.get(refs[1]) == "b"


# -------------------------------------------------------------------- cluster


def test_cluster_streaming_preexec_failure_surfaces():
    """A task that fails BEFORE its generator runs (here: 3 chips is not a
    valid chip subset on a 4-chip host) must surface the error to the
    streaming consumer as item 0 + end-of-stream, not hang."""
    import os

    from ray_tpu.core import accelerators

    os.environ[accelerators.FAKE_CHIPS_ENV] = "4"
    try:
        c = Cluster(initialize_head=True, head_node_args={"num_cpus": 2})
        ray_tpu.init(address=c.gcs_address)

        @ray_tpu.remote(num_returns="streaming", num_tpus=3)  # invalid subset
        def needs_tpu():
            yield 1

        it = iter(needs_tpu.remote())
        with pytest.raises(Exception, match="TPU"):
            ray_tpu.get(next(it))
        ray_tpu.shutdown()
        c.shutdown()
    finally:
        del os.environ[accelerators.FAKE_CHIPS_ENV]



@pytest.fixture(scope="module")
def stream_cluster():
    c = Cluster(initialize_head=True,
                head_node_args={"num_cpus": 4,
                                "object_store_memory": 64 * 1024 * 1024})
    ray_tpu.init(address=c.gcs_address)
    yield c
    ray_tpu.shutdown()
    c.shutdown()


@pytest.mark.timeout_s(120)  # 4 s beside 12 CPU burners
def test_cluster_streaming_1000_items_flat_memory(stream_cluster):
    """VERDICT done-criterion: 1,000 items consumed incrementally with flat
    memory — the 64 MB store moves 1000 × 128 KB = 125 MB of stream data only
    because backpressure + watermark-driven release keep the working set
    small (consumed items free on a short grace)."""
    item_bytes = 128 * 1024

    @ray_tpu.remote(num_returns="streaming")
    def torrent(n):
        for i in range(n):
            yield bytes([i % 256]) * item_bytes

    agent = SyncRpcClient(stream_cluster.nodes[0].address)
    try:
        n_seen = 0
        peak_used = 0
        for i, ref in enumerate(torrent.remote(1000)):
            data = ray_tpu.get(ref)
            assert len(data) == item_bytes and data[0] == i % 256
            del ref, data  # release: holder removed, item freeable
            n_seen += 1
            if i % 100 == 0:
                peak_used = max(peak_used, agent.call("node_info")["store"]["used"])
        assert n_seen == 1000
        # flat memory: working set stays a small multiple of the backpressure
        # window, nowhere near the 250 MB total streamed
        assert peak_used < 32 * 1024 * 1024, peak_used
    finally:
        agent.close()


def test_cluster_streaming_error_and_stop(stream_cluster):
    @ray_tpu.remote(num_returns="streaming")
    def boom():
        yield 7
        raise RuntimeError("cluster mid-stream")

    it = iter(boom.remote())
    assert ray_tpu.get(next(it)) == 7
    with pytest.raises(Exception, match="cluster mid-stream"):
        ray_tpu.get(next(it))
    with pytest.raises(StopIteration):
        next(it)


def test_cluster_streaming_actor(stream_cluster):
    @ray_tpu.remote
    class Streamer:
        def tokens(self, n):
            for i in range(n):
                yield {"token": i}

    a = Streamer.remote()
    out = [ray_tpu.get(r)["token"]
           for r in a.tokens.options(num_returns="streaming").remote(20)]
    assert out == list(range(20))


# ------------------------------------------- an actor's stream, read from its worker


@ray_tpu.remote(max_concurrency=4)
class _Source:
    """A generator actor that can be watched and told to die from its other
    call places."""

    def __init__(self):
        self.produced = 0
        self.stopped = False
        self.go = threading.Event()

    def counts(self):
        from ray_tpu import profiling

        ev = profiling._host_events  # noqa: SLF001 - the process's counters
        return ev.stream_items, ev.stream_items_inline

    def state(self):
        return self.produced, self.stopped

    def tokens(self, n):
        try:
            for i in range(n):
                self.produced += 1
                yield {"token": i}
        finally:
            self.stopped = True

    def mixed(self, big_bytes):
        yield "small"
        yield b"x" * big_bytes
        yield {"ref": ray_tpu.put([1, 2, 3])}
        yield "last"

    def three_then_die(self, how):
        yield from range(3)
        self.go.wait(30)
        if how == "exit":
            import os

            os._exit(1)
        raise RuntimeError("told to fail")

    def release(self):
        self.go.set()


def _stream(method, *args, **options):
    return method.options(num_returns="streaming", **options).remote(*args)


def test_cluster_actor_stream_1000_small_items_touch_no_agent_and_no_gcs(stream_cluster):
    """A small item goes from the worker's record to the caller in the
    long-poll's reply: nothing is sealed, the GCS hears of no item, and the
    store stays where it was."""
    a = _Source.remote()
    items0, inline0 = ray_tpu.get(a.counts.remote())
    agent = SyncRpcClient(stream_cluster.nodes[0].address)
    gcs = SyncRpcClient(stream_cluster.gcs_address)
    try:
        used0 = agent.call("node_info")["store"]["used"]
        gen = _stream(a.tokens, 1000)
        assert [ray_tpu.get(r)["token"] for r in gen] == list(range(1000))
        assert gen.completed()
        items1, inline1 = ray_tpu.get(a.counts.remote())
        assert (items1 - items0, inline1 - inline0) == (1000, 1000)
        state = gcs.call("stream_state", task_id=gen.task_id_hex)
        assert state["produced"] == 0 and not state["finished"]
        assert agent.call("node_info")["store"]["used"] - used0 < 64 * 1024
    finally:
        agent.close()
        gcs.close()


def test_cluster_actor_stream_large_and_ref_items_go_through_the_store(stream_cluster):
    """An item over the inline limit and an item that holds an ObjectRef
    are sealed and registered as ever, between small ones, in order."""
    from ray_tpu.core.config import inline_max_bytes

    a = _Source.remote()
    items0, inline0 = ray_tpu.get(a.counts.remote())
    big = inline_max_bytes() + 1
    small, large, holder, last = (
        ray_tpu.get(r) for r in _stream(a.mixed, big))
    assert (small, large, last) == ("small", b"x" * big, "last")
    assert ray_tpu.get(holder["ref"]) == [1, 2, 3]
    items1, inline1 = ray_tpu.get(a.counts.remote())
    assert (items1 - items0, inline1 - inline0) == (4, 2)


def test_cluster_actor_stream_ref_kept_after_the_end_resolves_elsewhere(stream_cluster):
    """A ref that leaves the consumer's process is promoted to the store
    first, as an actor call's inline result is."""
    @ray_tpu.remote
    def read(box):
        return ray_tpu.get(box[0])["token"]

    a = _Source.remote()
    refs = list(_stream(a.tokens, 5))
    assert ray_tpu.get(read.remote([refs[3]]), timeout=60) == 3
    assert ray_tpu.get(refs[3])["token"] == 3


def test_cluster_actor_stream_backpressure_blocks_producer(stream_cluster):
    a = _Source.remote()
    it = iter(_stream(a.tokens, 50, _generator_backpressure=2))
    assert ray_tpu.get(next(it))["token"] == 0
    time.sleep(0.5)  # the producer's chance to run ahead
    produced, _ = ray_tpu.get(a.state.remote())
    # the consumer asked for item 0 only: at most two items ahead of that
    assert produced <= 2, produced
    assert [ray_tpu.get(r)["token"] for r in it] == list(range(1, 50))
    assert ray_tpu.get(a.state.remote()) == (50, True)


def test_cluster_actor_stream_close_stops_generator(stream_cluster):
    a = _Source.remote()
    gen = _stream(a.tokens, 10_000, _generator_backpressure=2)
    it = iter(gen)
    assert [ray_tpu.get(next(it))["token"] for _ in range(3)] == [0, 1, 2]
    gen.close()
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        produced, stopped = ray_tpu.get(a.state.remote())
        if stopped:
            break
        time.sleep(0.05)
    assert stopped and produced < 100, (produced, stopped)
    with pytest.raises(StopIteration):
        next(it)


@pytest.mark.parametrize("how", ["exit", "raise"])
def test_cluster_actor_stream_failure_is_the_next_item(stream_cluster, how):
    """The actor's process gone mid-stream raises at the next item; so does
    the generator's own exception, and then the stream ends."""
    from ray_tpu import exceptions

    a = _Source.remote()
    it = iter(_stream(a.three_then_die, how))
    assert [ray_tpu.get(next(it)) for _ in range(3)] == [0, 1, 2]
    a.release.remote()
    expected = ((exceptions.ActorDiedError, exceptions.ActorUnavailableError)
                if how == "exit" else RuntimeError)
    with pytest.raises(expected):
        ray_tpu.get(next(it), timeout=60)
    with pytest.raises(StopIteration):
        next(it)


def test_cluster_actor_stream_second_execution_repeats_no_item(stream_cluster, tmp_path):
    """The actor dies mid-stream and restarts, the call runs again from its
    first item: the consumer, which asks by index, gets every item once."""
    marker = str(tmp_path / "died-once")

    # retries: the first ones may still be routed to the dead worker's
    # address, until the GCS has seen it die
    @ray_tpu.remote(max_restarts=1, max_task_retries=5)
    class Phoenix:
        def tokens(self, n):
            import os

            for i in range(n):
                if i == 6 and not os.path.exists(marker):
                    open(marker, "w").close()
                    os._exit(1)
                yield i

    a = Phoenix.remote()
    out = [ray_tpu.get(r, timeout=60) for r in _stream(a.tokens, 12)]
    assert out == list(range(12))
