"""Serve streaming: chunked HTTP responses + handle streaming + LLM tokens.

Reference capability: serve/_private/proxy.py:542 (streaming
send_request_to_replica), serve/handle.py stream=True
(DeploymentResponseGenerator). Done-criterion (VERDICT r2 items 1/3): an HTTP
client sees chunks ARRIVING BEFORE the replica's generator finishes.
"""

import json
import socket
import time

import pytest

import ray_tpu
from ray_tpu import serve


@pytest.fixture
def serve_session(ray_tpu_local):
    serve.start(http_port=0)
    yield
    serve.shutdown()


@serve.deployment(stream=True)
class SlowStreamer:
    """Yields one record every `delay`; lets the client prove incremental
    arrival by timestamping each chunk."""

    def __init__(self, delay: float = 0.15, n: int = 5):
        self._delay = delay
        self._n = n

    def __call__(self, request=None):
        for i in range(self._n):
            yield {"i": i, "t": time.time()}
            time.sleep(self._delay)


def _http_stream_chunks(host: str, port: int, path: str, body: bytes = b""):
    """Minimal chunked-transfer client: yields (chunk_bytes, arrival_time)."""
    s = socket.create_connection((host, port), timeout=30)
    try:
        req = (
            f"POST {path} HTTP/1.1\r\nHost: {host}\r\n"
            f"Content-Length: {len(body)}\r\nContent-Type: application/json\r\n"
            f"Connection: close\r\n\r\n"
        ).encode() + body
        s.sendall(req)
        f = s.makefile("rb")
        status = f.readline()
        assert b"200" in status, status
        headers = {}
        while True:
            line = f.readline().strip()
            if not line:
                break
            k, _, v = line.partition(b":")
            headers[k.strip().lower()] = v.strip()
        assert headers.get(b"transfer-encoding") == b"chunked", headers
        while True:
            size_line = f.readline().strip()
            size = int(size_line, 16)
            if size == 0:
                break
            data = f.read(size)
            f.read(2)  # trailing CRLF
            yield data, time.time()
    finally:
        s.close()


def test_http_chunks_arrive_before_generation_finishes(serve_session):
    app = SlowStreamer.bind(delay=0.15, n=5)
    serve.run(app, name="slow")
    addr = serve.http_address()
    host, port = addr.replace("http://", "").split(":")

    chunks = list(_http_stream_chunks(host, int(port), "/slow"))
    assert len(chunks) == 5
    records = [json.loads(c.decode()) for c, _ in chunks]
    assert [r["i"] for r in records] == list(range(5))
    # incremental: the first chunk must arrive well before the last record
    # was even PRODUCED by the replica (0.6s later) — i.e. before generation
    # finished, not buffered until the end
    first_arrival = chunks[0][1]
    last_produced = records[-1]["t"]
    assert first_arrival < last_produced, (
        f"first chunk arrived {first_arrival - last_produced:.3f}s AFTER the "
        f"last record was produced — response was buffered, not streamed"
    )


def test_handle_streaming_values(serve_session):
    @serve.deployment(stream=True)
    def counter(request=None):
        for i in range(4):
            yield i * 2

    handle = serve.run(counter.bind(), name="counter")
    vals = list(handle.options(stream=True).remote(None))
    assert vals == [0, 2, 4, 6]


def test_handle_streaming_non_generator_single_item(serve_session):
    @serve.deployment
    class Plain:
        def __call__(self, request=None):
            return {"answer": 42}

    handle = serve.run(Plain.bind(), name="plain")
    vals = list(handle.options(stream=True).remote(None))
    assert vals == [{"answer": 42}]


def test_llm_token_streaming(ray_tpu_local):
    """Tokens stream out of the engine before generation completes."""
    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.serve.llm import LLMEngine

    engine = LLMEngine(LlamaConfig.tiny(), num_slots=2, decode_chunk=4,
                       max_seq_len=128)
    try:
        seen = []
        arrivals = []
        for rec in engine.generate_stream([1, 2, 3], max_tokens=24):
            arrivals.append(time.perf_counter())
            seen.append(rec)
        assert seen[-1]["done"] is True
        tokens = [r["token"] for r in seen[:-1]]
        assert len(tokens) == seen[-1]["num_tokens"]
        assert len(tokens) >= 24 - 4  # eos-free tiny model decodes to budget
        # streaming, not batch-delivered: arrivals must span multiple decode
        # chunks, so the spread between first and last token is non-trivial
        assert arrivals[-1] - arrivals[0] > 0, arrivals
        # sanity vs blocking path: same model produces same-shaped result
        blocking = engine.generate([1, 2, 3], max_tokens=8)
        assert len(blocking["tokens"]) == 8
    finally:
        engine.stop()


def test_llm_stream_abandon_frees_slot(ray_tpu_local):
    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.serve.llm import LLMEngine

    engine = LLMEngine(LlamaConfig.tiny(), num_slots=1, decode_chunk=4,
                       max_seq_len=256)
    try:
        gen = engine.generate_stream([1, 2, 3], max_tokens=200)
        next(gen)   # first token arrived; request occupies the only slot
        gen.close()  # abandon: slot must retire
        deadline = time.time() + 10
        while time.time() < deadline:
            if engine.stats()["active"] == 0:
                break
            time.sleep(0.05)
        assert engine.stats()["active"] == 0, "abandoned stream kept its slot"
        # the freed slot serves the next request
        out = engine.generate([4, 5], max_tokens=4, timeout=30)
        assert len(out["tokens"]) == 4
    finally:
        engine.stop()


# ---------------------------------------------------------------- coalescing
def test_a_stream_that_falls_behind_travels_together_and_arrives_one_by_one():
    """A consumer that found 32 items in a row ready when it asked (it is
    slower than the generator, as the object plane is: a seal, a report and
    a long-poll an object) gets what waits as one ``StreamBatch`` from then
    on; the router's ``_items`` gives the generator's items back, in
    order."""
    from ray_tpu.serve.replica import StreamBatch, _coalesced
    from ray_tpu.serve.router import _items

    objects = []
    for obj in _coalesced(i for i in range(200)):
        objects.append(obj)
        time.sleep(0.002)
    assert [i for o in objects for i in _items(o)] == list(range(200))
    assert len(objects) < 100
    assert not any(type(o) is StreamBatch for o in objects[:32])
    assert any(type(o) is StreamBatch for o in objects)


def test_a_stream_that_keeps_up_goes_item_by_item():
    """Bursts of 9 (the LLM engine's largest: a chunk of 8 tokens and the
    first token or the closing record) that drain before the next one: every
    item is its own object and no pump thread runs, as before there was a
    ``StreamBatch``."""
    import threading

    from ray_tpu.serve.replica import _coalesced

    def bursts():
        for burst in range(4):
            for i in range(9):
                yield {"i": 9 * burst + i}
            time.sleep(0.2)

    objects = []
    for obj in _coalesced(bursts()):
        objects.append(obj)
        assert not any(t.name == "replica-stream-pump"
                       for t in threading.enumerate())
        time.sleep(0.005)
    assert objects == [{"i": i} for i in range(36)]


def test_coalesced_stream_raises_after_its_items_and_closes_when_abandoned():
    from ray_tpu.serve.replica import _coalesced
    from ray_tpu.serve.router import _items

    def failing():
        yield 1
        yield 2
        raise ValueError("mid-stream")

    seen = []
    with pytest.raises(ValueError, match="mid-stream"):
        for obj in _coalesced(failing()):
            seen += _items(obj)
    assert seen == [1, 2]

    closed = []

    def endless():
        try:
            i = 0
            while True:
                yield i
                i += 1
        finally:
            closed.append(True)

    stream = _coalesced(endless())
    next(stream)
    stream.close()  # while it keeps up: closed with its consumer
    assert closed == [True]
    stream = _coalesced(endless())
    for _ in range(60):  # behind by now: a pump thread runs the generator
        next(stream)
    stream.close()
    deadline = time.time() + 5
    while len(closed) < 2 and time.time() < deadline:
        time.sleep(0.01)
    assert closed == [True, True], "the abandoned stream's generator was never closed"


def test_a_burst_reaches_the_http_client_whole_and_in_order(serve_session):
    @serve.deployment(stream=True)
    def burst(request=None):
        for i in range(300):
            yield {"token": i}
        yield {"done": True}

    handle = serve.run(burst.bind(), name="burst")
    host, port = serve.http_address().replace("http://", "").split(":")
    lines = b"".join(c for c, _ in _http_stream_chunks(
        host, int(port), "/burst")).splitlines()
    records = [json.loads(line) for line in lines]
    assert [r["token"] for r in records[:-1]] == list(range(300))
    assert records[-1] == {"done": True}
    vals = list(handle.options(stream=True).remote(None))
    assert [v["token"] for v in vals[:-1]] == list(range(300))
