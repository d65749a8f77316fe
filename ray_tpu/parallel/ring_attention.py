"""Ring attention: context parallelism over the ``cp`` mesh axis.

The reference has NO native sequence/context parallelism (SURVEY §5 —
verified absent; its posture is "bring your own engine"). Here it is a
first-class framework op, TPU-idiomatic:

- sequence is sharded over the ``cp`` axis; K/V shards rotate around the
  ring with ``jax.lax.ppermute`` (neighbor ICI hops, the canonical TPU ring
  pattern — see pallas_guide.md Ring Collectives), overlapping compute with
  the rotation;
- softmax uses the online (running max / normalizer) recurrence across ring
  steps, so each device only ever holds one K/V shard — memory per device is
  O(S/cp), enabling sequences cp× longer than single-device attention;
- causal masking is resolved at BLOCK granularity: a device skips K/V
  shards entirely in its causal future (no wasted FLOPs), applies the
  elementwise triangle only on the diagonal shard.

Layout contract: enter via ``shard_map`` with q/k/v sharded [B, S/cp, H, D]
on the cp axis (use ``ring_attention_sharded`` for the wrapped version).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _local_attention_stats(q, k, v, scale, mask=None):
    """One block: returns (m, l, acc) online-softmax stats.
    q: [B, Sq, H, D]; k/v: [B, Sk, Hkv, D]."""
    hq = q.shape[2]
    hkv = k.shape[2]
    if hq != hkv:
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)  # [B,H,Sq,1]
    # guard fully-masked rows
    m = jnp.maximum(m, NEG_INF / 2)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    acc = jnp.einsum("bhqk,bkhd->bhqd", p, v.astype(jnp.float32))
    return m, l, acc


def ring_attention(q, k, v, axis_name: str = "cp", causal: bool = True,
                   scale: Optional[float] = None):
    """Call INSIDE shard_map. q/k/v: [B, S_local, H(_kv), D] (seq-sharded)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    axis_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    b, s_local, hq, d = q.shape

    m0 = jnp.full((b, hq, s_local, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hq, s_local, 1), jnp.float32)
    acc0 = jnp.zeros((b, hq, s_local, d), jnp.float32)

    # ring: at step t, this device holds the K/V shard originally from
    # device (my_idx - t) mod cp; send to right neighbor each step.
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def step(t, carry):
        m, l, acc, k_cur, v_cur = carry
        src = (my_idx - t) % axis_size

        def compute(mlacc):
            m, l, acc = mlacc
            if causal:
                # block causality: src > my_idx => entire shard is future
                q_pos = my_idx * s_local + jax.lax.broadcasted_iota(
                    jnp.int32, (s_local, k_cur.shape[1]), 0
                )
                k_pos = src * s_local + jax.lax.broadcasted_iota(
                    jnp.int32, (s_local, k_cur.shape[1]), 1
                )
                mask = (q_pos >= k_pos)[None, None]
            else:
                mask = None
            m_new, l_new, acc_new = _local_attention_stats(q, k_cur, v_cur, scale, mask)
            m_tot = jnp.maximum(m, m_new)
            alpha_old = jnp.exp(m - m_tot)
            alpha_new = jnp.exp(m_new - m_tot)
            return (m_tot, l * alpha_old + l_new * alpha_new,
                    acc * alpha_old + acc_new * alpha_new)

        if causal:
            skip = src > my_idx
            m, l, acc = jax.lax.cond(skip, lambda x: x, compute, (m, l, acc))
        else:
            m, l, acc = compute((m, l, acc))
        # rotate for the next step (skipped on the last iteration by cond on
        # t would break ppermute uniformity; an extra rotation is harmless)
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return m, l, acc, k_nxt, v_nxt

    m, l, acc, _, _ = jax.lax.fori_loop(0, axis_size, step, (m0, l0, acc0, k, v))
    out = acc / jnp.maximum(l, 1e-30)
    return jnp.einsum("bhqd->bqhd", out).astype(q.dtype)


def ring_attention_sharded(q, k, v, mesh, causal: bool = True,
                           scale: Optional[float] = None, axis_name: str = "cp",
                           q_spec=None, kv_spec=None):
    """shard_map wrapper: q/k/v are GLOBAL [B, S, H, D] arrays (sharded or
    not); sequence is split over the cp axis inside.

    ``q_spec``/``kv_spec`` are optional PartitionSpecs carrying the FULL
    layout (batch over dp/fsdp, heads over tp, seq over cp). Attention is
    independent across batch and heads, so only the cp axis participates in
    the ring; passing the real specs keeps dp/tp sharding intact instead of
    forcing replication at the shard_map boundary."""
    from jax.sharding import PartitionSpec as P

    wrap = functools.partial(jax.shard_map, check_vma=False)

    if q_spec is None:
        q_spec = P(None, axis_name, None, None)
    if kv_spec is None:
        kv_spec = q_spec
    fn = functools.partial(ring_attention, axis_name=axis_name, causal=causal, scale=scale)
    return wrap(fn, mesh=mesh, in_specs=(q_spec, kv_spec, kv_spec), out_specs=q_spec)(q, k, v)
