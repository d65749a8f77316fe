"""TPU accelerator manager: chip detection, slice/pod resource model,
per-process chip visibility.

Reference capability: python/ray/_private/accelerators/tpu.py:71 (chip
detection via /dev/accel* | /dev/vfio), :155-195 (TPU_VISIBLE_CHIPS +
chips-per-host/host bounds so frameworks see a chip subset), and the
TPU-{type}-head resource convention used by the reference for slice-level
gang scheduling.

TPU-first differences:
- slice topology surfaces as NODE LABELS (ray_tpu.io/accelerator, /slice,
  /tpu-worker-id) that the GCS placement planner understands natively
  (STRICT_PACK = same slice), instead of string-parsed resources;
- chip subsets are handed to jax (the only framework here), so the env
  recipe targets libtpu directly.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional

TPU_VISIBLE_CHIPS_ENV = "TPU_VISIBLE_CHIPS"
TPU_CHIPS_PER_HOST_BOUNDS_ENV = "TPU_CHIPS_PER_HOST_BOUNDS"
TPU_HOST_BOUNDS_ENV = "TPU_HOST_BOUNDS"
# test/dev override: pretend this many chips exist
FAKE_CHIPS_ENV = "RAY_TPU_FAKE_TPU_CHIPS"
# set by the node agent on a dedicated TPU worker: the chip ids it was leased
WORKER_CHIPS_ENV = "RAY_TPU_WORKER_CHIPS"

SLICE_LABEL = "ray_tpu.io/slice"
ACCEL_LABEL = "ray_tpu.io/accelerator"
WORKER_ID_LABEL = "ray_tpu.io/tpu-worker-id"

_ACCEL_TYPE_RE = re.compile(r"^v\d+[a-zA-Z]*-\d+$")


def jax_pinned_to_cpu() -> bool:
    """The operator (or the test suite) set ``JAX_PLATFORMS=cpu``: no jax
    process of this deployment will open a chip. Read from the environment,
    without importing jax."""
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def detect_num_chips() -> int:
    """Chips on this host that a jax process could open. Counted from the
    device files (no jax import: the caller must stay off the chip), and
    zero when the operator pinned jax to the CPU with ``JAX_PLATFORMS=cpu``:
    a chip no process may open is not a schedulable resource."""
    fake = os.environ.get(FAKE_CHIPS_ENV)
    if fake:
        return int(fake)
    if jax_pinned_to_cpu():
        return 0
    accel = glob.glob("/dev/accel*")
    if accel:
        return len(accel)
    try:
        return len([e for e in os.listdir("/dev/vfio") if e.isdigit()])
    except FileNotFoundError:
        return 0


def accelerator_type() -> Optional[str]:
    """Normalized v{gen}-{chips} slice type (e.g. "v5e-8"), from the TPU VM
    environment (no GCE metadata calls: zero-egress environments)."""
    # RAY_TPU_* overrides take precedence: platform launchers (and this
    # repo's tests) may need to pin these in environments whose interpreter
    # startup rewrites the canonical TPU_* variables
    raw = (os.environ.get("RAY_TPU_ACCELERATOR_TYPE")
           or os.environ.get("TPU_ACCELERATOR_TYPE")
           or os.environ.get("ACCELERATOR_TYPE") or "")
    raw = raw.strip()
    if not raw:
        return None
    norm = raw.replace("litepod", "e")  # v5litepod-8 -> v5e-8
    return norm if _ACCEL_TYPE_RE.match(norm) else raw


def slice_name() -> Optional[str]:
    return os.environ.get("RAY_TPU_SLICE_NAME") or os.environ.get("TPU_NAME")


def tpu_worker_id() -> int:
    try:
        return int(os.environ.get("RAY_TPU_TPU_WORKER_ID")
                   or os.environ.get("TPU_WORKER_ID", "0"))
    except ValueError:
        return 0


def node_tpu_labels() -> Dict[str, str]:
    """Topology labels the GCS planner keys on (slice-aware gang placement)."""
    labels: Dict[str, str] = {}
    acc = accelerator_type()
    if acc:
        labels[ACCEL_LABEL] = acc
    sl = slice_name()
    if sl:
        labels[SLICE_LABEL] = sl
    if acc or sl:
        labels[WORKER_ID_LABEL] = str(tpu_worker_id())
    return labels


def node_tpu_resources(num_chips: Optional[int] = None) -> Dict[str, float]:
    """TPU resources for this host. Worker 0 of a slice also carries the
    slice-head resource (``TPU-v5e-8-head: 1``) so a single bundle can gang
    onto "one whole slice" by requesting the head (reference convention)."""
    chips = detect_num_chips() if num_chips is None else num_chips
    if chips <= 0:
        return {}
    res: Dict[str, float] = {"TPU": float(chips)}
    acc = accelerator_type()
    if acc and tpu_worker_id() == 0:
        res[f"TPU-{acc}-head"] = 1.0
    return res


def visible_chip_env(chip_ids: List[int], total_chips: int) -> Dict[str, str]:
    """Env vars that restrict a process to a chip subset (reference
    tpu.py:155-195 recipe; see google/jax#14977). Empty for the whole host:
    the worker then keeps whatever bounds the host's own environment set."""
    if len(chip_ids) >= total_chips:
        return {}
    env = {
        TPU_VISIBLE_CHIPS_ENV: ",".join(str(c) for c in chip_ids),
        # several processes of one host load libtpu at once, each on its own
        # chips; the node agent, not libtpu's host-wide lockfile, keeps two
        # of them off the same chip (jax's own multi-process TPU tests set
        # the same pair)
        "ALLOW_MULTIPLE_LIBTPU_LOAD": "1",
    }
    if len(chip_ids) == 1:
        env[TPU_CHIPS_PER_HOST_BOUNDS_ENV] = "1,1,1"
        env[TPU_HOST_BOUNDS_ENV] = "1,1,1"
    elif len(chip_ids) == 2:
        env[TPU_CHIPS_PER_HOST_BOUNDS_ENV] = "1,2,1"
        env[TPU_HOST_BOUNDS_ENV] = "1,1,1"
    elif len(chip_ids) == 4:
        env[TPU_CHIPS_PER_HOST_BOUNDS_ENV] = "2,2,1"
        env[TPU_HOST_BOUNDS_ENV] = "1,1,1"
    else:
        raise ValueError(
            f"no libtpu bounds recipe for a {len(chip_ids)}-chip subset of a "
            f"{total_chips}-chip host (supported: 1, 2, 4, or all)"
        )
    return env
