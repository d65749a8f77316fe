"""Where the persistent XLA compilation cache lives.

Every process that compiles for the chip calls ``enable_compile_cache()``
once before its first ``jit``: dedicated TPU workers at start-up, the serving
engine, the train-step factories and the bench scripts. A cold 1B train step
plus the serving programs is minutes of compilation; a worker that restarts,
or the next run of the same command, should read them back.

The directory is part of the cache's identity in practice: a cache that
moves never hits. So it is either what the operator pinned with
``JAX_COMPILATION_CACHE_DIR`` (then nothing is set in code; jax reads the
variable itself) or ``<checkout>/.jax_cache``, derived from this package's
location. Never the cwd: a cluster worker may run from a staged
runtime-env directory.

Every compile is kept, not only those over jax's default of one second: a
replica's small programs (a 128-token prefill bucket at each row count, the
eager programs around them) are most of what a warm start still compiled.

A process pinned to the CPU backend (``JAX_PLATFORMS=cpu``: the test suite,
``chip_smoke.py --rehearse``) gets no persistent cache from here. XLA:CPU
stores ahead-of-time code whose recorded machine features it then refuses to
match on load, even on the host that wrote it, with a SIGILL warning per
entry; and nobody waits minutes for a CPU compile.
"""

from __future__ import annotations

import os
from typing import Dict, Optional

from ray_tpu.core.accelerators import jax_pinned_to_cpu

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)

_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"
# process-wide by nature: jax.monitoring listeners are global and there is
# one compilation cache per process
_counts: Dict[str, int] = {"hits": 0, "misses": 0}
_listening = False


def _on_event(event: str, **_kwargs) -> None:
    if event == _HIT_EVENT:
        _counts["hits"] += 1
    elif event == _MISS_EVENT:
        _counts["misses"] += 1


def enable_compile_cache() -> Optional[str]:
    """Point jax's persistent compilation cache at its fixed place and start
    counting hits and misses. Idempotent. Returns the directory in use, or
    None where the process is pinned to the CPU backend."""
    global _listening
    import jax

    if not _listening:
        jax.monitoring.register_event_listener(_on_event)
        _listening = True
    # jax's default keeps only what took over a second to compile
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    pinned = os.environ.get(CACHE_DIR_ENV)
    if pinned:
        return pinned
    if jax_pinned_to_cpu():
        return None
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR


def compile_cache_stats() -> Dict[str, int]:
    """Persistent-cache hits and misses in this process since
    ``enable_compile_cache()`` (a miss is a program compiled and written)."""
    return dict(_counts)
