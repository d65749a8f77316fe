import os
import signal
import threading

# The suite runs on a virtual 8-device CPU mesh: multi-chip logic runs on
# host devices, and no test may open a real chip.
#
# HARD-set (not setdefault): a machine with a chip exports JAX_PLATFORMS for
# it, and spawned cluster agents/workers inherit os.environ, so a setdefault
# here would leave every subprocess on the real chip. accelerators.
# detect_num_chips also reads it: with "cpu" a node advertises no TPU unless
# a test sets RAY_TPU_FAKE_TPU_CHIPS.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax

import pytest


# --------------------------------------------------------------------------- #
# Per-test liveness watchdog (VERDICT r4 #1): a wedged wait anywhere in a
# test — INCLUDING module-fixture setup/teardown — must dump every thread's
# stack and fail that test instead of hanging the whole suite. SIGALRM fires
# in the main thread (CPython interrupts lock/queue/socket waits there), so
# the TimeoutError surfaces exactly at the blocked frame.
# --------------------------------------------------------------------------- #
TEST_TIMEOUT_S = float(os.environ.get("RAY_TPU_TEST_TIMEOUT_S", "600"))
# the limit of the test that is running: TEST_TIMEOUT_S, or the smaller one
# its ``timeout_s`` marker names
_limit_s = TEST_TIMEOUT_S


class TestHangError(BaseException):
    # BaseException, NOT Exception: the raise lands at an arbitrary blocked
    # frame, and framework retry loops catch Exception broadly — a hang
    # inside one would swallow an Exception-derived timeout and wedge again
    pass


def _watchdog_fire(signum, frame):
    import faulthandler
    import sys

    print(
        f"\n=== ray_tpu test watchdog: test exceeded {_limit_s}s; "
        "all thread stacks follow ===",
        file=sys.stderr, flush=True,
    )
    faulthandler.dump_traceback(all_threads=True)
    # re-arm: if this raise IS somehow swallowed (except BaseException
    # somewhere), the next alarm gets another chance to break the test out
    signal.setitimer(signal.ITIMER_REAL, _limit_s)
    raise TestHangError(
        f"test exceeded {_limit_s}s (stacks dumped to stderr)"
    )


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: multi-GB / long-running benches excluded from the tier-1 "
        "run (-m 'not slow')",
    )
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection tests (SIGKILLed components, dropped "
        "frames). Tier-1 — selectable with -m chaos for focused runs.",
    )
    config.addinivalue_line(
        "markers",
        "timeout_s(seconds): this test's own watchdog limit, for cluster "
        "tests that have hung before: a hang then costs the run this many "
        "seconds and not RAY_TPU_TEST_TIMEOUT_S (which still caps it).",
    )


@pytest.hookimpl(wrapper=True)
def pytest_runtest_protocol(item, nextitem):
    if (
        not hasattr(signal, "setitimer")
        or threading.current_thread() is not threading.main_thread()
    ):
        return (yield)
    global _limit_s
    own = item.get_closest_marker("timeout_s")
    _limit_s = min(TEST_TIMEOUT_S, float(own.args[0])) if own else TEST_TIMEOUT_S
    old = signal.signal(signal.SIGALRM, _watchdog_fire)
    signal.setitimer(signal.ITIMER_REAL, _limit_s)
    try:
        return (yield)
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old)


@pytest.hookimpl(trylast=True)
def pytest_sessionfinish(session, exitstatus):
    """Two exit-liveness layers (the interpreter can hang AFTER the last
    test: concurrent.futures' atexit joins EVERY executor thread ever
    created, so one worker parked in an unbounded wait wedges finalization):

    1. report non-daemon straggler threads with stacks (diagnosis);
    2. arm an escape-hatch timer: if finalization is still running 60s
       after the summary, dump all stacks and _exit with the session's
       status — a wedged teardown must cost a minute, not the whole run.
    """
    import sys
    import time
    import traceback

    def report(only_nondaemon: bool = True) -> None:
        threads = [t for t in threading.enumerate()
                   if t is not threading.main_thread()
                   and (not t.daemon or not only_nondaemon)]
        if not threads:
            return
        print(f"\n=== straggler threads: {[t.name for t in threads]} ===",
              file=sys.stderr, flush=True)
        frames = sys._current_frames()
        for t in threads:
            f = frames.get(t.ident)
            if f is not None:
                print(f"--- {t.name} (daemon={t.daemon}) ---", file=sys.stderr)
                traceback.print_stack(f, file=sys.stderr)
        sys.stderr.flush()

    report(only_nondaemon=not os.environ.get("RAY_TPU_THREAD_REPORT"))

    def escape_hatch() -> None:
        time.sleep(60)
        print("\n=== ray_tpu exit watchdog: interpreter finalization wedged "
              "60s after the summary; ALL thread stacks follow, then "
              "force-exit ===", file=sys.stderr, flush=True)
        report(only_nondaemon=False)
        os._exit(int(exitstatus) if isinstance(exitstatus, int) else 1)

    threading.Thread(target=escape_hatch, daemon=True,
                     name="exit-watchdog").start()


@pytest.fixture(scope="session", autouse=True)
def _arena_leak_guard():
    """Post-suite shm hygiene check: fail LOUDLY if the run leaves orphaned
    rtpu-arena-* files behind (a SIGKILLed test cluster whose janitor never
    ran — the live leak VERDICT r5 found pinning /dev/shm). It asks only
    about arenas whose pidfile names an agent of a Cluster THIS process
    started, so neither another xdist worker's clusters nor another one's
    janitor decide the answer: one such arena still there when its own
    shutdown() has returned is a leak, and so is one whose agent is dead at
    the end of the session."""
    try:
        from ray_tpu.cluster import Cluster
        from ray_tpu.core.shm_store import (arena_owner, find_orphan_arenas,
                                            sweep_dead_arenas)
    except Exception:
        yield
        return
    agents, leaked = set(), []

    def orphans_of(pids):
        return [p for p in find_orphan_arenas() if arena_owner(p) in pids]

    add_node, shutdown = Cluster.add_node, Cluster.shutdown

    def add_node_recorded(self, *args, **kwargs):
        node = add_node(self, *args, **kwargs)
        agents.add(node.proc.pid)
        return node

    def shutdown_checked(self):
        shutdown(self)
        leaked.extend(orphans_of(
            {n.proc.pid for n in self.nodes + self._removed}))

    Cluster.add_node, Cluster.shutdown = add_node_recorded, shutdown_checked
    try:
        yield
    finally:
        Cluster.add_node, Cluster.shutdown = add_node, shutdown
    orphans = sorted(set(leaked + orphans_of(agents)))
    if orphans:
        # reclaim them (next run must start clean), then fail the suite
        sweep_dead_arenas()
        raise RuntimeError(
            f"ORPHANED SHM ARENAS after test run: {orphans} — a cluster of "
            "this run was shut down and kept an arena, or was killed and "
            "no later start swept it. The files were reclaimed now, but "
            "the leaking test must be fixed."
        )


@pytest.fixture
def ray_tpu_local():
    """Fresh local runtime per test (analogue of the reference's
    ray_start_regular fixture, python/ray/tests/conftest.py:419)."""
    import ray_tpu

    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=8)
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.fixture
def shutdown_only():
    import ray_tpu

    yield ray_tpu
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()


# `kill -USR1 <pytest pid>` dumps all thread stacks (hang diagnosis on the
# single-core CI box; the cluster components get the same hook from
# setup_component_logging)
try:
    import faulthandler as _fh
    import signal as _sig

    # chain=False: SIGUSR1's DEFAULT action is process termination, so
    # chaining would kill pytest right after the dump (observed r5)
    _fh.register(_sig.SIGUSR1, all_threads=True, chain=False)
except (ImportError, ValueError, AttributeError):
    pass
