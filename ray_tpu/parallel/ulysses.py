"""Ulysses sequence parallelism: all-to-all head scattering on the ``sp`` axis.

The reference has NO native sequence parallelism (SURVEY §5 — verified
absent; its posture is "bring your own engine"). Ring attention
(``parallel/ring_attention.py``) keeps the sequence sharded and rotates K/V;
Ulysses instead re-shards *heads*: each device exchanges its sequence shard
for a head shard with one all-to-all, runs ordinary full-sequence attention
on ``H/sp`` heads, and all-to-alls back. Two collectives per attention call
(vs ``sp`` ppermute rounds for the ring) — the better trade when heads are
plentiful and the interconnect favors large fused transfers (TPU ICI
all-to-all rides the same torus links as the ring but with one logical
phase; see pallas_guide.md on ICI collectives).

Layout contract:
- enter via ``shard_map`` with q/k/v sharded ``[B, S/sp, H, D]`` on the sp
  axis (``ulysses_attention``), or pass GLOBAL arrays to
  ``ulysses_attention_sharded`` which wraps the shard_map;
- requires ``H % sp == 0`` for queries and ``Hkv % sp == 0`` for K/V (GQA
  with fewer KV heads than sp would need KV replication — rejected loudly).
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax

from ray_tpu.ops.attention import reference_attention


def _all_to_all(x, axis_name: str, split_axis: int, concat_axis: int):
    # tiled=True: the named axis stays implicit (shard_map SPMD style);
    # x keeps rank, trading dim `split_axis` (shrinks sp-fold) for
    # dim `concat_axis` (grows sp-fold).
    return jax.lax.all_to_all(
        x, axis_name, split_axis=split_axis, concat_axis=concat_axis, tiled=True
    )


def ulysses_attention(
    q,
    k,
    v,
    axis_name: str = "sp",
    causal: bool = True,
    scale: Optional[float] = None,
    attn_fn: Callable = reference_attention,
):
    """Call INSIDE shard_map. q: [B, S/sp, H, D]; k/v: [B, S/sp, Hkv, D].

    attn_fn(q, k, v, causal=..., scale=...) runs the full-sequence local
    attention on the head shard — pass ``ops.attention.flash_attention`` on
    real TPU; the default reference path keeps CPU-mesh tests exact.
    """
    sp = jax.lax.psum(1, axis_name)
    hq, hkv = q.shape[2], k.shape[2]
    if hq % sp or hkv % sp:
        raise ValueError(
            f"Ulysses SP needs heads divisible by sp={sp} (got Hq={hq}, Hkv={hkv}); "
            "use ring attention (parallel/ring_attention.py) for head-poor configs"
        )
    # [B, S/sp, H, D] -> [B, S, H/sp, D]: scatter heads, gather sequence
    q = _all_to_all(q, axis_name, split_axis=2, concat_axis=1)
    k = _all_to_all(k, axis_name, split_axis=2, concat_axis=1)
    v = _all_to_all(v, axis_name, split_axis=2, concat_axis=1)
    out = attn_fn(q, k, v, causal=causal, scale=scale)
    # [B, S, H/sp, D] -> [B, S/sp, H, D]: back to sequence sharding
    return _all_to_all(out, axis_name, split_axis=1, concat_axis=2)


def ulysses_attention_sharded(
    q,
    k,
    v,
    mesh,
    causal: bool = True,
    scale: Optional[float] = None,
    axis_name: str = "sp",
    q_spec=None,
    kv_spec=None,
    attn_fn: Callable = reference_attention,
):
    """shard_map wrapper over GLOBAL [B, S, H, D] arrays, sequence split on
    the sp axis. Like ring_attention_sharded, optional q_spec/kv_spec carry
    the full layout (batch over dp/fsdp, seq over sp) so dp/tp sharding is
    preserved at the boundary instead of forcing replication."""
    from jax.sharding import PartitionSpec as P

    wrap = functools.partial(jax.shard_map, check_vma=False)

    if q_spec is None:
        q_spec = P(None, axis_name, None, None)
    if kv_spec is None:
        kv_spec = q_spec
    fn = functools.partial(
        ulysses_attention, axis_name=axis_name, causal=causal, scale=scale,
        attn_fn=attn_fn,
    )
    return wrap(fn, mesh=mesh, in_specs=(q_spec, kv_spec, kv_spec),
                out_specs=q_spec)(q, k, v)
