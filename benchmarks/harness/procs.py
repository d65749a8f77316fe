"""A run leaves nothing behind: every process it started is found again by
a marker in its environment, ended, and waited for."""

from __future__ import annotations

import os
import signal
import time
from typing import List

MARKER = "RTPU_BENCH_OWNER"


def mark_environment() -> str:
    token = f"{os.getpid()}-{int(time.time() * 1000)}"
    os.environ[MARKER] = token
    return token


def marked_pids(token: str) -> List[int]:
    needle = f"{MARKER}={token}".encode()
    me, out = os.getpid(), []
    for entry in os.listdir("/proc"):
        if not entry.isdigit() or int(entry) == me:
            continue
        try:
            with open(f"/proc/{entry}/environ", "rb") as f:
                env = f.read()
            with open(f"/proc/{entry}/stat") as f:
                state = f.read().rsplit(")", 1)[1].split()[0]
        except OSError:
            continue
        if needle in env.split(b"\0") and state != "Z":
            out.append(int(entry))
    return out


def reap_all(token: str, grace_s: float = 5.0, limit_s: float = 90.0) -> List[int]:
    """Wait for marked processes to end; SIGKILL what outlives ``grace_s``.
    A process inside libtpu takes about 5 s to die after SIGKILL (PR 21).
    Returns the pids still alive at ``limit_s`` (a failure)."""
    t0 = time.monotonic()
    killed = False
    while True:
        for _ in range(64):  # collect our own children so none stays a zombie
            try:
                pid, _ = os.waitpid(-1, os.WNOHANG)
            except ChildProcessError:
                break
            if pid == 0:
                break
        alive = marked_pids(token)
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
