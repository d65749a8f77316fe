"""The configuration file as it is run, and the key every family's seeded
weights are made from. The weights themselves are the family's
(``families/<name>.py`` ``make_weights``): one jitted program on the device.
"""

from __future__ import annotations

import json
from typing import Any, Dict


def seed_key(seed: int):
    """``--seed`` may exceed 31 bits; fold the high bits in instead of
    overflowing the int32 a PRNG key is made from."""
    import jax

    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def load_config_file(path: str, rehearse: bool = False) -> Dict[str, Any]:
    with open(path) as f:
        cfg = json.load(f)
    if rehearse:
        # tiny widths for the CPU rehearsal; never a measurement
        tiny = cfg["rehearsal"]
        dep = {**cfg["deployment"], **tiny.pop("deployment", {})}
        cfg = {**cfg, **tiny, "deployment": dep}
    return cfg
