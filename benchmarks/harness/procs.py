"""A run leaves nothing behind: every process it started is found again by
a marker in its environment, ended, and waited for."""

from __future__ import annotations

import os
import signal
import time
from typing import Dict, List, Optional

MARKER = "RTPU_BENCH_OWNER"


def mark_environment() -> str:
    token = f"{os.getpid()}-{int(time.time() * 1000)}"
    os.environ[MARKER] = token
    return token


def _stat(pid: int) -> List[str]:
    """``/proc/<pid>/stat`` from the state on: [0] state, [1] the parent,
    [17] the count of threads, [19] the start time in clock ticks. Empty
    once it is gone."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(")", 1)[1].split()
    except OSError:
        return []


def running(pid: int) -> bool:
    """Not yet dead. A killed chip holder's first thread is a zombie at once
    while its last takes seconds to leave libtpu (``ps`` shows ``Zl``): the
    process still holds the chip. That thread is in no ``task`` listing any
    more; only the count of threads shows it (read on the chip, PR 56: state
    Z with 2 threads for 3.6 s after the SIGKILL, then 1)."""
    stat = _stat(pid)
    return bool(stat) and (stat[0] not in "ZX" or int(stat[17]) > 1)


def _started(pid: int) -> str:
    stat = _stat(pid)
    return stat[19] if stat else ""


def marked_pids(token: str) -> List[int]:
    needle = f"{MARKER}={token}".encode()
    me, out = os.getpid(), []
    for entry in os.listdir("/proc"):
        if not entry.isdigit() or int(entry) == me:
            continue
        try:
            with open(f"/proc/{entry}/environ", "rb") as f:
                env = f.read()
        except OSError:
            continue
        if needle in env.split(b"\0") and running(int(entry)):
            out.append(int(entry))
    return out


def snapshot(token: str) -> Dict[int, str]:
    """{pid: start time} of the marked processes and of this process's
    descendants, taken before the teardown: a dying process has no
    environment left to find it by (its ``environ`` reads empty once its
    threads have let go of their memory, and a chip holder's last thread
    leaves libtpu seconds after that), and one that a runner has already
    ended is found only as a child of a child."""
    parent = {}
    for entry in os.listdir("/proc"):
        stat = _stat(int(entry)) if entry.isdigit() else []
        if stat:
            parent[int(entry)] = int(stat[1])
    found = set(marked_pids(token))
    for pid in parent:
        above = parent.get(pid)
        while above not in (None, 0, 1) and above != os.getpid():
            above = parent.get(above)
        if above == os.getpid() and running(pid):
            found.add(pid)
    return {pid: _started(pid) for pid in found}


def reap_all(token: str, grace_s: float = 5.0, limit_s: float = 90.0,
             known: Optional[Dict[int, str]] = None) -> List[int]:
    """Wait for marked processes, and those of ``known`` (a ``snapshot``
    from before the teardown), to end; SIGKILL what outlives ``grace_s``.
    A process inside libtpu takes about 5 s to die after SIGKILL (PR 21).
    Returns the pids still alive at ``limit_s`` (a failure)."""
    t0 = time.monotonic()
    killed = False
    seen = dict(known or {})  # pid -> start time: a reused pid is not ours
    while True:
        for _ in range(64):  # collect our own children so none stays a zombie
            try:
                pid, _ = os.waitpid(-1, os.WNOHANG)
            except ChildProcessError:
                break
            if pid == 0:
                break
        for pid in marked_pids(token):
            seen.setdefault(pid, _started(pid))
        alive = sorted(pid for pid, born in seen.items()
                       if running(pid) and _started(pid) == born)
        if not alive:
            return []
        waited = time.monotonic() - t0
        if waited > limit_s:
            return alive
        if waited > grace_s and not killed:
            for pid in alive:
                try:
                    os.kill(pid, signal.SIGKILL)
                except OSError:
                    pass
            killed = True
        time.sleep(0.1)
