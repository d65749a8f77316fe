"""Replica actor: hosts one copy of a deployment's callable.

Reference capability: serve/_private/replica.py (Replica.__init__:518,
handle_request:533 — user-code execution with ongoing-request accounting,
health checks, graceful shutdown). Runs as a max_concurrency actor; each
request is one actor task. Queue-length accounting backs both the pow-2
router (probe path) and autoscaling (controller scrapes stats).
"""

from __future__ import annotations

import asyncio
import contextvars
import inspect
import queue
import threading
import time
from typing import Any, Dict, Optional

from ray_tpu import exceptions as exc

_request_hops: contextvars.ContextVar = contextvars.ContextVar(
    "serve_request_hops", default=None)


def current_request_hops() -> Optional[Dict[str, float]]:
    """Inside a streaming request: the wall instants (``time.time()``, one
    host) at which it passed the proxy (``proxy_recv``), the router
    (``router_submit``) and this replica's handler (``replica_enter``). A
    deployment that ends its stream with a record holding ``"hops"`` (the
    LLM engine's done record) copies them there, and the proxy adds its
    own. None outside a streaming request."""
    return _request_hops.get()


class StreamBatch(list):
    """Items of one stream that travel as ONE item (until PR 39 an item cost
    the object plane a seal, a report and a long-poll through the GCS, and
    one replica carried 590 a second whatever the number of streams;
    PERF.md section 6, PR 29 and PR 39: an item now rides the reply of the
    caller's long-poll to this worker, ``core/streaming.py``).
    ``Router.call_streaming`` hands them on one by one, in order: a consumer
    sees the generator's items and never a batch."""


_STREAM_AHEAD = 256  # items a coalesced generator may run in front of its consumer
# a stream is BEHIND once its generator had this many items in a row ready
# when it was asked: four of the LLM engine's bursts (a chunk of 8 tokens, 9
# with the first token or the closing record) without once waiting for the
# engine; two bursts run into each other whenever an iteration is short.
# READY = within a millisecond
_STREAM_BEHIND = 32
_STREAM_READY_S = 1e-3


def _coalesced(gen):
    """The generator's items, one object each, by the parent's own path
    (this thread runs ``gen`` and the runtime seals what it yields) for as
    long as the object plane keeps up with the generator. Once
    ``_STREAM_BEHIND`` items in a row were ready the moment they were asked
    for, the stream is behind, and from then on a pump thread runs ``gen``
    (in the caller's context: request hops, multiplexed model id; at most
    ``_STREAM_AHEAD`` items in front) while this one sends whatever waits
    together, as a ``StreamBatch``. Nothing waits for a batch to fill, so an
    item is never later than it was.

    A stream that keeps up is exactly what it was: coalescing every stream
    from its first item was measured too, and in a closed loop whose engine
    is the limit it moved 0.13-0.24 s of a request's cycle from the tail of
    its reply to the queue in front of the engine; a pump thread for every
    stream cost that cell 1% (PERF.md section 6, PR 29). Closing this
    generator closes ``gen``: at once while it keeps up, at its next item
    once pumped, as the runtime's own close does."""
    ready = 0
    try:
        while ready < _STREAM_BEHIND:
            asked = time.perf_counter()
            try:
                item = next(gen)
            except StopIteration:
                return
            ready = ready + 1 if time.perf_counter() - asked < _STREAM_READY_S else 0
            yield item
    except GeneratorExit:
        gen.close()
        raise

    items: "queue.Queue" = queue.Queue(maxsize=_STREAM_AHEAD)
    abandoned = threading.Event()

    def put(entry) -> None:
        while not abandoned.is_set():
            try:
                return items.put(entry, timeout=0.5)
            except queue.Full:
                continue

    def pump() -> None:
        try:
            for item in gen:
                put((True, item))
                if abandoned.is_set():
                    break
            put((False, None))
        except BaseException as e:  # noqa: BLE001 - raised again by the consumer
            put((False, e))
        finally:
            gen.close()

    threading.Thread(target=contextvars.copy_context().run, args=(pump,),
                     daemon=True, name="replica-stream-pump").start()
    try:
        while True:
            entries = [items.get()]  # (True, item) or (False, None | error)
            try:
                while entries[-1][0]:
                    entries.append(items.get_nowait())
            except queue.Empty:
                pass
            batch = [value for is_item, value in entries if is_item]
            if batch:
                yield batch[0] if len(batch) == 1 else StreamBatch(batch)
            is_item, error = entries[-1]
            if not is_item:
                if error is not None:
                    raise error
                return
    finally:
        abandoned.set()


class ReplicaOverloadedError(exc.RayTpuError):
    """Rejected: the replica is at max_ongoing_requests (the router should
    retry on another replica — reference: back-pressure in replica_scheduler)."""


class Replica:
    """Generic replica wrapper. Instantiated as an actor by the controller:
    ``Replica.options(max_concurrency=...).remote(serialized_deployment, ...)``.
    """

    def __init__(self, deployment_def: bytes, init_args: tuple, init_kwargs: dict,
                 replica_id: str = ""):
        import cloudpickle

        dep = cloudpickle.loads(deployment_def)
        self._deployment = dep
        self._replica_id = replica_id
        self._max_ongoing = int(dep.max_ongoing_requests)
        self._ongoing = 0
        self._total = 0
        self._lock = threading.Lock()
        self._started_at = time.time()
        target = dep.func_or_class
        self._is_function = not inspect.isclass(target)
        if self._is_function:
            # function deployment: the function IS __call__
            self._callable = target
        else:
            self._callable = target(*init_args, **init_kwargs)
        if dep.user_config is not None:
            reconfigure = getattr(self._callable, "reconfigure", None)
            if reconfigure is not None:
                reconfigure(dep.user_config)

    # ------------------------------------------------------------- requests
    def handle_request(self, method: str, args: tuple, kwargs: dict,
                       multiplexed_model_id: str = "") -> Any:
        from ray_tpu.serve.multiplex import (
            _reset_request_model_id, _set_request_model_id,
        )

        with self._lock:
            if self._ongoing >= self._max_ongoing:
                raise ReplicaOverloadedError(
                    f"replica {self._replica_id} at max_ongoing_requests="
                    f"{self._max_ongoing}"
                )
            self._ongoing += 1
            self._total += 1
        mux_token = _set_request_model_id(multiplexed_model_id)
        try:
            if self._is_function:
                if method != "__call__":
                    raise AttributeError(
                        f"function deployment '{self._deployment.name}' only "
                        f"supports __call__, not '{method}'"
                    )
                fn = self._callable
            else:
                fn = getattr(self._callable, method, None)
                if fn is None:
                    raise AttributeError(
                        f"deployment '{self._deployment.name}' has no method '{method}'"
                    )
            result = fn(*args, **kwargs)
            if inspect.iscoroutine(result):
                result = _run_coro(result)
            return result
        finally:
            _reset_request_model_id(mux_token)
            with self._lock:
                self._ongoing -= 1

    def handle_request_streaming(self, method: str, args: tuple, kwargs: dict,
                                 multiplexed_model_id: str = "",
                                 hops: Optional[Dict[str, float]] = None):
        """Streaming variant: a generator method, invoked by routers with
        ``num_returns="streaming"`` so each yielded item is sealed and
        consumable before the request finishes (reference:
        serve/_private/proxy.py:542 streaming send_request_to_replica +
        replica.py:533 handle_request_streaming). Non-generator results
        stream as a single item; a stream that falls behind the object plane
        sends what waits together (``_coalesced``). ``hops``: see
        ``current_request_hops``."""
        from ray_tpu.serve.multiplex import (
            _reset_request_model_id, _set_request_model_id,
        )

        hops_token = _request_hops.set(
            {**(hops or {}), "replica_enter": time.time()})
        with self._lock:
            if self._ongoing >= self._max_ongoing:
                raise ReplicaOverloadedError(
                    f"replica {self._replica_id} at max_ongoing_requests="
                    f"{self._max_ongoing}"
                )
            self._ongoing += 1
            self._total += 1
        mux_token = _set_request_model_id(multiplexed_model_id)
        try:
            if self._is_function:
                fn = self._callable
            else:
                fn = getattr(self._callable, method, None)
                if fn is None:
                    raise AttributeError(
                        f"deployment '{self._deployment.name}' has no method '{method}'"
                    )
            result = fn(*args, **kwargs)
            if inspect.iscoroutine(result):
                result = _run_coro(result)
            if inspect.isgenerator(result):
                yield from _coalesced(result)
            elif inspect.isasyncgen(result):
                from ray_tpu.core.streaming import iter_async_gen

                yield from _coalesced(iter_async_gen(result))
            else:
                yield result
        finally:
            _request_hops.reset(hops_token)
            _reset_request_model_id(mux_token)
            with self._lock:
                self._ongoing -= 1

    # ---------------------------------------------------------------- stats
    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "replica_id": self._replica_id,
                "ongoing": self._ongoing,
                "total": self._total,
                "max_ongoing": self._max_ongoing,
                "uptime_s": time.time() - self._started_at,
            }

    def check_health(self) -> bool:
        user_check = getattr(self._callable, "check_health", None)
        if user_check is not None:
            user_check()
        return True

    def reconfigure(self, user_config: Dict[str, Any]) -> bool:
        fn = getattr(self._callable, "reconfigure", None)
        if fn is not None:
            fn(user_config)
        return True

    def prepare_for_shutdown(self) -> bool:
        """Run user cleanup before the controller kills the worker
        (reference: replica graceful shutdown calls the callable's
        __del__)."""
        fn = getattr(self._callable, "__del__", None)
        if fn is not None:
            try:
                fn()
            except Exception:  # noqa: BLE001 - cleanup must not block kill
                pass
        return True


def _run_coro(coro):
    """Execute a coroutine returned by user code (replica methods run on
    executor threads, so a fresh loop per call is the simple correct thing)."""
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()
