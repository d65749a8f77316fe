"""Streaming generators: ``num_returns="streaming"`` and ObjectRefGenerator.

Reference capability: python/ray/_raylet.pyx:281 (ObjectRefGenerator),
:1206,1263 (per-item report paths) — a remote generator task/actor method
yields items that are sealed into the object plane ONE AT A TIME; the caller
iterates ObjectRefs as they are produced, with consumer-driven backpressure
so an unbounded producer cannot flood the store.

TPU-first redesign: a stream has ONE directory (index -> item, the end marker,
the consumer's watermark), and the consumer's ``next`` is a single long-poll
that doubles as the consumed watermark (asking for item *i* acknowledges items
< *i*), which is what the producer's backpressure gate waits on. No extra RPC
per consumed item. Where the directory lives depends on who can reach whom:

- An ACTOR's streaming call: in the worker that runs the generator
  (``WorkerStream``, kept by ``core/node/worker_main.py``). The caller already
  holds a connection to that worker for the call itself, so its long-poll
  (``actor_stream_next``) goes there and one reply carries every item ready,
  payloads inline; they land in the caller's inline cache as an actor call's
  small results do, and a ref that escapes is promoted to the store then. An
  item costs no agent call and no GCS call, and nothing is sealed. An item
  whose payload is over ``inline_max_bytes()`` or holds ObjectRefs is still
  sealed and registered (and pinned at the GCS under the stream's holder until
  the watermark passes it); the worker's record carries its id in place of a
  payload.
- A TASK's stream (the caller has no connection to the worker a task lands
  on), and the stream of a caller whose spec carries no ``inline_max`` (the
  C++ client): beside the GCS's object directory, as before. Every item is a normal object (sealed
  and location-registered through the existing paths) plus one
  stream-directory append at the GCS.
"""

from __future__ import annotations

import asyncio
import threading
import time
from typing import Any, Dict, List, Optional, TYPE_CHECKING

from ray_tpu.core.config import config
from ray_tpu.core.ids import ObjectID, TaskID
from ray_tpu.core.object_ref import ObjectRef

if TYPE_CHECKING:
    from ray_tpu.core.runtime import CoreRuntime

STREAMING = "streaming"

_STOP = object()  # sentinel: end-of-stream across executor boundaries


def stream_item_id(task_hex: str, index: int) -> ObjectID:
    """Object id of stream item ``index`` (0-based): return slot index+1."""
    return ObjectID.for_task_return(TaskID(bytes.fromhex(task_hex)), index + 1)


class ObjectRefGenerator:
    """Iterator over the ObjectRefs produced by a streaming task.

    Sync (``for ref in gen``) and async (``async for ref in gen``) iteration;
    each yielded ObjectRef resolves through the normal ``get`` path. Dropping
    the generator early closes the stream: the producer is unblocked (and told
    to stop) and unconsumed items are released.
    """

    def __init__(self, task_hex: str, runtime: "CoreRuntime"):
        self._task_hex = task_hex
        self._runtime = runtime
        self._index = 0
        self._total: Optional[int] = None
        self._closed = False

    @property
    def task_id_hex(self) -> str:
        return self._task_hex

    def __iter__(self) -> "ObjectRefGenerator":
        return self

    def __next__(self) -> ObjectRef:
        return self._next_internal(timeout=None)

    def _next_internal(self, timeout: Optional[float]) -> ObjectRef:
        if self._total is not None and self._index >= self._total:
            raise StopIteration
        if self._closed:
            raise StopIteration
        kind, value = self._runtime.stream_next(self._task_hex, self._index, timeout)
        if kind == "end":
            self._total = value
            if self._index >= value:
                raise StopIteration
            # items can land before the end marker is observed: retry the index
            return self._next_internal(timeout)
        self._index += 1
        return ObjectRef(ObjectID.from_hex(value))

    def __aiter__(self) -> "ObjectRefGenerator":
        return self

    async def __anext__(self) -> ObjectRef:
        loop = asyncio.get_running_loop()

        def step():  # StopIteration cannot cross a Future boundary
            try:
                return self.__next__()
            except StopIteration:
                return _STOP

        ref = await loop.run_in_executor(None, step)
        if ref is _STOP:
            raise StopAsyncIteration
        return ref

    def completed(self) -> bool:
        return self._total is not None and self._index >= self._total

    def close(self) -> None:
        """Stop consuming: unblocks (and stops) the producer, releases
        unconsumed items."""
        if not self._closed:
            self._closed = True
            try:
                self._runtime.stream_close(self._task_hex)
            except Exception:  # noqa: BLE001 - runtime may already be down
                pass

    def __del__(self) -> None:
        try:
            if self._total is None or self._index < self._total:
                self.close()
        except Exception:  # noqa: BLE001
            pass

    def __repr__(self) -> str:
        return f"ObjectRefGenerator(task={self._task_hex[:16]}, next={self._index})"


def iter_async_gen(agen):
    """Drain an async generator from a sync context on a private event loop
    (used when a streaming task/actor method is an async generator)."""
    loop = asyncio.new_event_loop()
    try:
        while True:
            try:
                yield loop.run_until_complete(agen.__anext__())
            except StopAsyncIteration:
                return
    finally:
        loop.run_until_complete(agen.aclose())
        loop.close()


class LocalStreamState:
    """In-process stream directory entry (LocalRuntime backend)."""

    __slots__ = ("items", "finished", "total", "consumed", "delivered",
                 "closed", "cond")

    def __init__(self) -> None:
        self.items: dict = {}          # index -> oid hex
        self.finished = False
        self.total = 0
        self.consumed = 0              # consumer watermark: next index wanted
        self.delivered = 0             # indices actually handed out via next()
        self.closed = False
        self.cond = threading.Condition()

    # -- producer side ------------------------------------------------------
    def put(self, index: int, oid_hex: str, backpressure: int) -> bool:
        """Record item ``index``; block while too far ahead of the consumer.
        Returns False when the consumer closed the stream (producer should
        stop)."""
        with self.cond:
            self.items[index] = oid_hex
            self.cond.notify_all()
            while (
                backpressure > 0
                and (index + 1) - self.consumed >= backpressure
                and not self.closed
            ):
                self.cond.wait(0.05)
            return not self.closed

    def end(self, total: int) -> None:
        with self.cond:
            self.finished = True
            self.total = total
            self.cond.notify_all()

    # -- consumer side ------------------------------------------------------
    def next(self, index: int, timeout: Optional[float]):
        with self.cond:
            if index > self.consumed:
                self.consumed = index
                self.cond.notify_all()
            deadline = None
            if timeout is not None:
                import time as _time

                deadline = _time.monotonic() + timeout
            while True:
                if index in self.items:
                    self.delivered = max(self.delivered, index + 1)
                    return ("item", self.items[index])
                if self.finished:
                    return ("end", self.total)
                if deadline is not None:
                    import time as _time

                    remaining = deadline - _time.monotonic()
                    if remaining <= 0:
                        raise TimeoutError(
                            f"stream item {index} not produced within {timeout}s"
                        )
                    self.cond.wait(min(remaining, 0.1))
                else:
                    self.cond.wait(0.1)

    def close(self) -> None:
        with self.cond:
            self.closed = True
            self.cond.notify_all()


class WorkerStream:
    """Stream directory entry of ONE actor streaming call, in the worker
    that runs its generator. The producer is a thread (``put`` / ``end``),
    the consumer's long-polls are coroutines on the worker's event loop
    (``poll``); both sides meet under ``cond``, which no one holds while
    waiting. An entry is ``{"payload", "is_error"}`` (travels in the reply)
    or ``{"object_id"}`` (sealed in the store). Entries stay until the
    watermark passes them, so a poll that is sent again after a lost frame
    gets the same answer."""

    MAX_REPLY_ITEMS = 256

    def __init__(self, loop: asyncio.AbstractEventLoop) -> None:
        self._loop = loop
        self.items: Dict[int, Dict[str, Any]] = {}
        self.finished = False
        self.total = 0
        self.consumed = 0              # consumer watermark: next index wanted
        self.closed = False
        self.produced = 0              # first index the generator has not put
        self.sealed_any = False        # some item went through the store
        self._passed: List[str] = []   # sealed items the watermark has passed
        self.cond = threading.Condition()
        self._waiters: List[asyncio.Future] = []
        self.polled = time.monotonic()  # the consumer's last sign of life

    def _wake(self) -> None:
        """Under ``cond``: release the parked long-polls and the producer."""
        self.cond.notify_all()
        waiters, self._waiters = self._waiters, []
        if waiters:
            self._loop.call_soon_threadsafe(_resolve_all, waiters)

    # -- producer side ------------------------------------------------------
    def put(self, index: int, entry: Dict[str, Any], backpressure: int) -> bool:
        """Record item ``index``; block while ``backpressure`` items ahead of
        the consumer. False once the consumer closed the stream."""
        with self.cond:
            if index >= self.consumed:  # a second execution: already read
                self.items[index] = entry
            self.produced = max(self.produced, index + 1)
            if "object_id" in entry:
                self.sealed_any = True
            self._wake()
            while (backpressure > 0 and not self.closed
                   and (index + 1) - self.consumed >= backpressure):
                self.cond.wait(0.5)
                if self.abandoned():
                    self.closed = True
            return not self.closed

    def end(self, total: int) -> None:
        with self.cond:
            self.finished = True
            self.total = total
            self._wake()

    # -- consumer side (event loop) -----------------------------------------
    async def poll(self, index: int, timeout_s: Optional[float]) -> Dict[str, Any]:
        """Every entry ready from ``index`` on (and the end marker once the
        generator is done), or ``{"timeout": True}``."""
        deadline = None if timeout_s is None else time.monotonic() + timeout_s
        while True:
            with self.cond:
                self.polled = time.monotonic()
                if index > self.consumed:
                    for j in range(self.consumed, index):
                        gone = self.items.pop(j, None)
                        if gone is not None and "object_id" in gone:
                            self._passed.append(gone["object_id"])
                    self.consumed = index
                    self.cond.notify_all()  # a producer waiting on capacity
                ready = []
                while index + len(ready) in self.items \
                        and len(ready) < self.MAX_REPLY_ITEMS:
                    ready.append(self.items[index + len(ready)])
                if ready or self.finished:
                    reply: Dict[str, Any] = {"items": ready}
                    if self.finished:
                        reply["end"] = self.total
                    return reply
                remaining = None if deadline is None \
                    else deadline - time.monotonic()
                if remaining is not None and remaining <= 0:
                    return {"timeout": True}
                fut: asyncio.Future = self._loop.create_future()
                self._waiters.append(fut)
            try:
                await asyncio.wait_for(
                    fut, 5.0 if remaining is None else min(remaining, 5.0))
            except (asyncio.TimeoutError, TimeoutError):
                with self.cond:
                    if fut in self._waiters:
                        self._waiters.remove(fut)

    def close(self) -> None:
        with self.cond:
            self.closed = True
            self.items.clear()
            self._wake()

    def abandoned(self) -> bool:
        """No poll for ten holder leases: the consumer is gone."""
        return (time.monotonic() - self.polled
                > 10 * config.object_holder_lease_s)

    def take_passed(self) -> List[str]:
        """Ids of the sealed items the watermark passed since the last call:
        their pin under the stream's holder can go."""
        with self.cond:
            ids, self._passed = self._passed, []
            return ids


def _resolve_all(waiters: List[asyncio.Future]) -> None:
    for fut in waiters:
        if not fut.done():
            fut.set_result(None)
