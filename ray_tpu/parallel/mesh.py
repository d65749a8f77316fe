"""Device mesh construction with ICI-topology awareness.

The TPU-native replacement for the reference's NCCL process groups
(reference: python/ray/util/collective/collective.py — group creation/
rendezvous): on TPU, parallelism axes live in ONE jax.sharding.Mesh over the
slice's devices, and XLA emits the collectives. This module standardizes the
axis vocabulary used across models/train/serve:

    dp    data parallel (pure replica)
    fsdp  data parallel with parameter sharding (ZeRO-3 style)
    tp    tensor (megatron) parallel — inside a host's ICI domain ideally
    sp    Ulysses sequence parallel (all-to-all head scattering;
          parallel/ulysses.py) — also reusable for norm/residual SP
    cp    context parallel (ring attention over sequence)
    ep    expert parallel (MoE)
    pp    pipeline parallel (stages)

Axis order in the mesh puts the fastest-varying (most-communicating) axis
last, which `mesh_utils.create_device_mesh` maps to adjacent ICI neighbors:
tp innermost, then cp/ep, then fsdp, then dp, then pp outermost (pp crosses
DCN first on multi-slice).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

AXIS_ORDER = ("pp", "dp", "fsdp", "ep", "cp", "sp", "tp")


@dataclass(frozen=True)
class MeshConfig:
    dp: int = 1
    fsdp: int = 1
    tp: int = 1
    cp: int = 1
    sp: int = 1
    ep: int = 1
    pp: int = 1
    # ---- multi-slice (DCN) factors --------------------------------------
    # A multi-slice job is N identical ICI slices joined by data-center
    # network. DCN factors multiply INTO the same logical axes (dp/pp), so
    # PartitionSpecs are unchanged and XLA's hierarchical collectives do
    # ring-reduce inside each slice over ICI and one cross-slice hop over
    # DCN (the "How to Scale Your Model" multislice recipe; the reference
    # has no multi-slice story — its NCCL groups are flat).
    dcn_dp: int = 1   # data-parallel replicas across slices (the default)
    dcn_pp: int = 1   # pipeline stages across slices (for weight-bound models)

    def axis_sizes(self) -> Dict[str, int]:
        """LOGICAL axis sizes (dcn factors folded into pp/dp)."""
        return {"pp": self.pp * self.dcn_pp, "dp": self.dp * self.dcn_dp,
                "fsdp": self.fsdp, "ep": self.ep, "cp": self.cp,
                "sp": self.sp, "tp": self.tp}

    def slice_axis_sizes(self) -> Dict[str, int]:
        """Per-slice (ICI) axis sizes."""
        return {"pp": self.pp, "dp": self.dp, "fsdp": self.fsdp,
                "ep": self.ep, "cp": self.cp, "sp": self.sp, "tp": self.tp}

    @property
    def num_slices(self) -> int:
        return self.dcn_dp * self.dcn_pp

    @property
    def devices_per_slice(self) -> int:
        return self.pp * self.dp * self.fsdp * self.ep * self.cp * self.sp * self.tp

    @property
    def num_devices(self) -> int:
        return self.devices_per_slice * self.num_slices

    def validate(self, available: int) -> None:
        if self.num_devices != available:
            raise ValueError(
                f"MeshConfig uses {self.num_devices} devices "
                f"({self.axis_sizes()}, {self.num_slices} slice(s)), "
                f"but {available} are available"
            )

    @classmethod
    def auto(cls, n_devices: int, tp: int = 1, cp: int = 1, sp: int = 1,
             ep: int = 1, pp: int = 1) -> "MeshConfig":
        """Fill the leftover factor into fsdp (the usual default for LLM
        pretraining: FSDP over everything not used by tp/cp/sp/ep/pp)."""
        used = tp * cp * sp * ep * pp
        if n_devices % used:
            raise ValueError(f"{n_devices} devices not divisible by tp*cp*sp*ep*pp={used}")
        return cls(dp=1, fsdp=n_devices // used, tp=tp, cp=cp, sp=sp, ep=ep, pp=pp)


def mesh_shape_for(config: MeshConfig) -> Tuple[Tuple[str, int], ...]:
    """(axis_name, size) pairs in ICI-friendly order, dropping size-1 axes is
    NOT done — keeping all axes makes PartitionSpecs uniform."""
    sizes = config.axis_sizes()
    return tuple((name, sizes[name]) for name in AXIS_ORDER)


def make_mesh(
    config: Optional[MeshConfig] = None,
    *,
    devices: Optional[Sequence] = None,
    allow_split_physical_axes: bool = True,
):
    """Build a jax.sharding.Mesh.

    Uses mesh_utils.create_device_mesh so the logical mesh maps onto the
    physical ICI torus (neighbor axes get neighbor links).
    """
    import jax

    devs = list(devices) if devices is not None else jax.devices()
    if config is None:
        config = MeshConfig.auto(len(devs))
    config.validate(len(devs))
    names_sizes = mesh_shape_for(config)
    names = tuple(n for n, _ in names_sizes)
    shape = tuple(s for _, s in names_sizes)
    if config.num_slices > 1:
        return jax.sharding.Mesh(
            _hybrid_mesh_array(config, devs, allow_split_physical_axes), names)
    from jax.experimental import mesh_utils

    # no reshape fallback: on a TPU a mesh that ignores the ICI layout still
    # computes, only slower, so a failure to map the topology must surface
    # (off-TPU create_device_mesh is itself a plain reshape)
    arr = mesh_utils.create_device_mesh(
        shape, devices=devs, allow_split_physical_axes=allow_split_physical_axes
    )
    return jax.sharding.Mesh(arr, names)


def _hybrid_mesh_array(config: MeshConfig, devs,
                       allow_split_physical_axes: bool = True):
    """Device array for a multi-slice mesh: DCN factors take the OUTER
    position of their logical axis, so index = slice_part * ici_size +
    ici_part and collectives decompose hierarchically (ICI ring inside each
    slice, one DCN hop across). Uses jax's hybrid mesh when the devices
    carry real slice_index metadata; otherwise groups devices contiguously
    into virtual slices (CPU-mesh testing)."""
    import numpy as np

    per = config.slice_axis_sizes()
    ici_shape = tuple(per[n] for n in AXIS_ORDER)
    dcn_shape = tuple(
        {"pp": config.dcn_pp, "dp": config.dcn_dp}.get(n, 1) for n in AXIS_ORDER
    )
    slice_ids = {getattr(d, "slice_index", None) for d in devs}
    if None not in slice_ids and len(slice_ids) > 1:
        # real multi-slice hardware: the config MUST match the physical
        # topology — grouping devices from different physical slices into
        # one "virtual slice" would silently run ICI collectives over DCN
        if len(slice_ids) != config.num_slices:
            raise ValueError(
                f"devices span {len(slice_ids)} physical slices but the "
                f"MeshConfig declares num_slices={config.num_slices} "
                f"(dcn_dp={config.dcn_dp}, dcn_pp={config.dcn_pp})"
            )
        from jax.experimental import mesh_utils

        return mesh_utils.create_hybrid_device_mesh(
            ici_shape, dcn_shape, devices=devs,
            allow_split_physical_axes=allow_split_physical_axes)
    # virtual slices: contiguous groups (process/device order is already
    # ICI-major under xla_force_host_platform_device_count)
    arr = np.asarray(devs).reshape(
        (config.dcn_pp, config.dcn_dp) + ici_shape)
    # (dcn_pp, dcn_dp, *ICI axes) -> (dcn_pp, pp, dcn_dp, dp, *rest):
    # each dcn factor moves adjacent-outer to its logical ICI axis, then the
    # pairs merge (dcn-major ordering = contiguous virtual slices)
    pp_pos = 2 + AXIS_ORDER.index("pp")
    dp_pos = 2 + AXIS_ORDER.index("dp")
    rest = [i for i in range(2, arr.ndim) if i not in (pp_pos, dp_pos)]
    arr = arr.transpose([0, pp_pos, 1, dp_pos] + rest)
    logical = config.axis_sizes()
    return arr.reshape(tuple(logical[n] for n in AXIS_ORDER))


def ici_topology_labels(device) -> Dict[str, str]:
    """Node labels describing a device's position in the slice (used by the
    cluster scheduler for slice-aware gang placement; reference analogue:
    accelerators/tpu.py GCE metadata probing)."""
    labels: Dict[str, str] = {}
    for attr, label in (
        ("platform", "ray_tpu.io/platform"),
        ("device_kind", "ray_tpu.io/device-kind"),
        ("process_index", "ray_tpu.io/process-index"),
        ("slice_index", "ray_tpu.io/slice-index"),
    ):
        val = getattr(device, attr, None)
        if val is not None:
            labels[label] = str(val)
    coords = getattr(device, "coords", None)
    if coords is not None:
        labels["ray_tpu.io/coords"] = ",".join(map(str, coords))
    return labels


def data_axes() -> Tuple[str, ...]:
    """Mesh axes that shard the batch dimension."""
    return ("dp", "fsdp")


def batch_sharding_spec():
    """PartitionSpec for a [batch, seq, ...] input batch: batch over dp+fsdp,
    sequence over cp (context parallel)."""
    import jax

    return jax.sharding.PartitionSpec(("dp", "fsdp"), "cp")
