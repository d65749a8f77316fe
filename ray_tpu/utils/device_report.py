"""One process's view of the accelerator it holds.

Every result that is a statement about the device names the device: the
bench scripts put this in their JSON line, ``chip_smoke.py`` collects it from
inside each dedicated TPU worker, and ``LLMDeployment.runtime_report`` serves
it from a replica. Calling it initialises the jax backend, so only the
process that is meant to hold the chip may call it. ``"host"`` is the
process's side of a stall: ``profiling.stall_watch()``'s snapshot (pauses,
stall records, the lateness ring, what is in flight now) and every watched
loop by name (the engine's pulse; a train loop's whole ``StepRing``).
"""

from __future__ import annotations

import os
from typing import Any, Dict


def device_report() -> Dict[str, Any]:
    import jax

    from ray_tpu.profiling import stall_watch
    from ray_tpu.utils.compile_cache import compile_cache_stats

    devices = jax.devices()
    first = devices[0]
    per_device = []
    for d in devices:
        stats = d.memory_stats() or {}  # the CPU backend reports none
        per_device.append({
            "id": d.id,
            "bytes_in_use": stats.get("bytes_in_use"),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        })
    return {
        "pid": os.getpid(),
        "platform": first.platform,
        "kind": first.device_kind,
        "count": len(devices),
        "devices": per_device,
        "compile_cache": {
            "dir": jax.config.jax_compilation_cache_dir,
            **compile_cache_stats(),
        },
        "host": {"watch": stall_watch().snapshot(),
                 "loops": stall_watch().loops()},
    }
