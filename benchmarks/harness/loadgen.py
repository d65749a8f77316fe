"""The load generator: one thread, one asyncio loop, raw HTTP/1.1 with
chunked ndjson responses. Every streamed record gets the host clock of its
arrival. Open loop (send at due instants, whatever the system does) and
closed loop (C callers, each sends its next when the last is answered)."""

from __future__ import annotations

import asyncio
import json
import time
from typing import Any, Callable, Dict, List, Optional


class RequestRecord:
    __slots__ = ("index", "due", "sent", "arrivals", "tokens", "done", "error",
                 "prompt_len", "max_tokens", "measured", "finished")

    def __init__(self, index, due, prompt_len, max_tokens, measured):
        self.index, self.due = index, due
        self.sent: Optional[float] = None
        self.arrivals: List[float] = []
        self.tokens: List[int] = []
        self.done: Optional[Dict[str, Any]] = None
        self.error: Optional[str] = None
        self.prompt_len, self.max_tokens = prompt_len, max_tokens
        self.measured = measured
        self.finished: Optional[float] = None


async def _post_stream(host: str, port: int, path: str, body: Dict[str, Any],
                       rec: RequestRecord, clock: Callable[[], float],
                       timeout: float) -> None:
    reader = writer = None
    try:
        reader, writer = await asyncio.wait_for(
            asyncio.open_connection(host, port), timeout)
        payload = json.dumps(body).encode()
        writer.write(
            f"POST {path} HTTP/1.1\r\nHost: {host}\r\n"
            "Content-Type: application/json\r\nConnection: close\r\n"
            f"Content-Length: {len(payload)}\r\n\r\n".encode() + payload)
        rec.sent = clock()
        await writer.drain()
        status = await asyncio.wait_for(reader.readline(), timeout)
        chunked = False
        length = None
        while True:
            h = (await asyncio.wait_for(reader.readline(), timeout)).decode("latin1").strip()
            if not h:
                break
            k, _, v = h.partition(":")
            if k.lower() == "transfer-encoding" and "chunked" in v.lower():
                chunked = True
            if k.lower() == "content-length":
                length = int(v)
        if b"200" not in status:
            detail = await reader.read(length or 2000)
            rec.error = f"{status.decode('latin1').strip()}: {detail[:300]!r}"
            return
        if not chunked:
            data = await asyncio.wait_for(reader.readexactly(length or 0), timeout)
            now = clock()
            for line in data.splitlines():
                _take(rec, json.loads(line), now)
            return
        buf = b""
        while True:
            size_line = await asyncio.wait_for(reader.readline(), timeout)
            size = int(size_line.strip() or b"0", 16)
            if size == 0:
                break
            data = await asyncio.wait_for(reader.readexactly(size + 2), timeout)
            now = clock()
            buf += data[:-2]
            while b"\n" in buf:
                line, buf = buf.split(b"\n", 1)
                if line:
                    _take(rec, json.loads(line), now)
    except (OSError, asyncio.TimeoutError, asyncio.IncompleteReadError,
            ValueError) as e:
        rec.error = f"{type(e).__name__}: {e}"
    finally:
        rec.finished = clock()
        if writer is not None:
            writer.close()


def _take(rec: RequestRecord, item: Dict[str, Any], now: float) -> None:
    if "token" in item:
        rec.tokens.append(int(item["token"]))
        rec.arrivals.append(now)
    elif item.get("done"):
        rec.done = item
    elif "error" in item:
        rec.error = str(item["error"])[:300]
    elif "tokens" in item:  # non-streamed answer
        rec.tokens = [int(t) for t in item["tokens"]]
        rec.arrivals = [now] * len(rec.tokens)
        rec.done = item


class LoadResult:
    def __init__(self, records, t_open, t_close, drained_at):
        self.records: List[RequestRecord] = records
        self.t_open, self.t_close, self.drained_at = t_open, t_close, drained_at

    @property
    def measured(self) -> List[RequestRecord]:
        return [r for r in self.records if r.measured]

    @staticmethod
    def ok(rec: RequestRecord) -> bool:
        return rec.error is None and rec.done is not None \
            and len(rec.tokens) == rec.max_tokens


def run_open_loop(address: str, path: str, plan: Dict[str, Any],
                  on_window_open: Callable[[], None],
                  on_window_close: Callable[[], None],
                  clock: Callable[[], float] = time.perf_counter) -> LoadResult:
    """plan["requests"]: [{"due": s relative to the window's opening (negative
    in the ramp), "tokens", "max_tokens", "measured"}], sorted by due."""
    host, port = address.replace("http://", "").split(":")
    reqs = plan["requests"]
    seconds, drain_s = plan["seconds"], plan["drain_limit_s"]
    timeout = plan.get("request_timeout_s", 300.0)

    async def main():
        lead = -min(0.0, reqs[0]["due"]) + 0.2
        t_open = clock() + lead
        records, tasks = [], []
        opened = False
        for i, rq in enumerate(reqs):
            due = t_open + rq["due"]
            if not opened and rq["due"] >= 0:
                await asyncio.sleep(max(0.0, t_open - clock()))
                on_window_open()
                opened = True
            delay = due - clock()
            if delay > 0:
                await asyncio.sleep(delay)
            rec = RequestRecord(i, due, len(rq["tokens"]), rq["max_tokens"],
                                rq["measured"])
            records.append(rec)
            body = {"tokens": rq["tokens"], "max_tokens": rq["max_tokens"],
                    "stream": True, "timeout": timeout}
            tasks.append(asyncio.ensure_future(_post_stream(
                host, int(port), path, body, rec, clock, timeout)))
        await asyncio.sleep(max(0.0, t_open + seconds - clock()))
        t_close = clock()
        on_window_close()
        if tasks:
            _done, pending = await asyncio.wait(tasks, timeout=drain_s)
            for t in pending:
                t.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)
        return LoadResult(records, t_open, t_close, clock())

    return asyncio.run(main())


def run_closed_loop(address: str, path: str, plan: Dict[str, Any],
                    on_window_open: Callable[[], None],
                    on_window_close: Callable[[], None],
                    clock: Callable[[], float] = time.perf_counter) -> LoadResult:
    """plan: callers, ramp_seconds (callers start evenly spread over it),
    seconds, next_request(i) -> {"tokens", "max_tokens"}. A request is
    measured if it was sent inside the window; callers stop sending at its
    end and the run waits ``drain_limit_s`` for what is in flight."""
    host, port = address.replace("http://", "").split(":")
    callers, ramp, seconds = plan["callers"], plan["ramp_seconds"], plan["seconds"]
    drain_s = plan["drain_limit_s"]
    timeout = plan.get("request_timeout_s", 300.0)
    next_request = plan["next_request"]

    async def main():
        t_start = clock() + 0.2
        t_open = t_start + ramp
        t_close = t_open + seconds
        records: List[RequestRecord] = []
        counter = [0]

        async def caller(k):
            await asyncio.sleep(max(0.0, t_start + ramp * k / callers - clock()))
            while clock() < t_close:
                i = counter[0]
                counter[0] += 1
                rq = next_request(i)
                now = clock()
                rec = RequestRecord(i, now, len(rq["tokens"]), rq["max_tokens"],
                                    t_open <= now < t_close)
                records.append(rec)
                body = {"tokens": rq["tokens"], "max_tokens": rq["max_tokens"],
                        "stream": True, "timeout": timeout}
                await _post_stream(host, int(port), path, body, rec, clock, timeout)
                if rec.error is not None:
                    await asyncio.sleep(0.2)  # do not spin on a failing server

        async def marks():
            await asyncio.sleep(max(0.0, t_open - clock()))
            on_window_open()
            await asyncio.sleep(max(0.0, t_close - clock()))
            on_window_close()

        tasks = [asyncio.ensure_future(caller(k)) for k in range(callers)]
        mark = asyncio.ensure_future(marks())
        await mark
        _done, pending = await asyncio.wait(tasks, timeout=drain_s)
        for t in pending:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
        return LoadResult(records, t_open, t_close, clock())

    return asyncio.run(main())
