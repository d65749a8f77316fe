"""Attention ops: Pallas TPU flash attention (forward and backward) and the
plain reference it is checked against.

Design (see /opt/skills/guides/pallas_guide.md):
- grid (batch, q_heads, q_blocks); K/V live whole-sequence in VMEM per
  (batch, head) and the kernel streams over K blocks with the online-softmax
  recurrence (running max m, normalizer l, fp32 accumulator) — the classic
  flash pattern, so S×S scores never touch HBM.
- causal masking skips fully-masked K blocks via the loop bound (block-level
  skip), and applies an elementwise mask only on the diagonal block. With a
  sliding ``window`` the loop also STARTS at the first K block a row of the
  q block can see, so a window layer costs S x window and not S^2 / 2, in
  serving's prefill and in training's forward and backward alike; those
  calls are named ``flash_window_fwd`` and ``flash_window_bwd`` in a profile.
- GQA: q heads map onto kv heads through the BlockSpec index_map
  (h // q_per_kv), so kv tensors are never materialized per-q-head.
- widths: q and k share one head width, any the compiler tiles (128 as the
  other families', 192 = 128 + the 64 rotated of latent attention); v the
  same or, forward only, one of its own (128 beside 192: the call is then
  named ``flash_mla_fwd`` in a profile), and the output is as wide as v.
  With ``lengths`` (forward only) a batch row's q blocks past its real rows
  are skipped: one program for every prompt length costs what the prompt
  needs.
- backward: ONE Pallas pass with the standard flash-bwd recurrence — the
  forward also emits the logsumexp per row; bwd recomputes p = exp(qk−lse)
  blockwise, so S×S never materializes. The grid walks the (q block, k block)
  pairs the mask admits (a table made from the static shapes, the window
  among them, read by scalar prefetch), k block by k block: a pair forms the
  scores, p, dP and dS once and adds to all of dV, dK and dQ. dK and dV of a
  k block are summed over the q blocks AND the group's q heads in float32
  scratch and written once in the storage dtype; the group's dQ is a float32
  block that stays in VMEM for the whole walk (a group whose dQ is over
  ``BWD_DQ_VMEM_BYTES`` is walked in equal parts, whose float32 dK and dV
  are summed outside). K, V, q, dO, lse and delta stream a block a pair.
  Defined where q, k and v share one width and no ``lengths`` are given.

Replaces-the-capability-of: the reference's NCCL-attached attention stacks
are external (DeepSpeed etc. via train integrations); here attention is a
first-class framework op.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.utils.logging import get_logger

logger = get_logger("ops.attention")

# Block sizes for a v5e: small blocks (128/128) leave the MXU idle between
# grid steps. What these reach is measured where the kernel is used: the
# benchmark's flash_fwd_roofline / flash_bwd_fused_roofline in train_4k (PERF.md 3).
DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 512
NEG_INF = -1e30


# --------------------------------------------------------------------------- #
# Reference implementation (also the backward path)
# --------------------------------------------------------------------------- #
def reference_attention(q, k, v, causal: bool = True, scale: Optional[float] = None,
                        window: Optional[int] = None):
    """q: [B, Sq, Hq, D]; k/v: [B, Skv, Hkv, D]. Returns [B, Sq, Hq, D].
    ``window`` (causal only): a query sees the ``window`` newest keys, itself
    among them (``i - j < window``)."""
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    if scale is None:
        scale = d ** -0.5
    if hq != hkv:
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    if causal:
        qpos = jnp.arange(sq)[:, None] + (skv - sq)
        kpos = jnp.arange(skv)[None, :]
        mask = qpos >= kpos
        if window is not None:
            mask = mask & (qpos - kpos < window)
        logits = jnp.where(mask[None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


# --------------------------------------------------------------------------- #
# Pallas forward kernel
# --------------------------------------------------------------------------- #
def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref=None, *, block_q, block_k, seq_kv, causal, scale, offset,
                      window=None, qi=None):
    # refs carry leading (1, 1) batch/head block dims:
    # q_ref: [1, 1, block_q, D]; k_ref/v_ref: [1, 1, seq_kv, D]
    # offset = seq_kv - seq_q: query row i sits at absolute position offset+i
    # (the KV-cache decode case where cached keys precede the queries).
    if qi is None:  # else the caller read it (outside a branch: the interpreter's rule)
        qi = pl.program_id(2)
    # operands stay in their storage dtype (bf16 on the hot path — the MXU
    # runs bf16 x bf16 at 2x the f32 rate); accumulation is f32 via
    # preferred_element_type, scale applied post-dot in f32.
    q = q_ref[0, 0]
    d = v_ref.shape[-1]  # the output is as wide as v: q and k may be wider

    q_start = qi * block_q + offset
    if causal:
        # number of k blocks any row of this q block can see
        num_k_blocks = jax.lax.div(
            jnp.minimum(q_start + block_q, seq_kv) + block_k - 1, block_k
        )
    else:
        num_k_blocks = seq_kv // block_k
    first_k_block = 0
    if window is not None:
        # the oldest key the block's FIRST row sees is q_start - window + 1
        first_k_block = jnp.maximum(q_start - window + 1, 0) // block_k

    m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, d), jnp.float32)

    def body(j, carry):
        m, l, acc = carry
        k_blk = k_ref[0, 0, pl.ds(j * block_k, block_k), :]
        v_blk = v_ref[0, 0, pl.ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [bq, bk] f32
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            cols = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            seen = rows >= cols
            if window is not None:
                seen = jnp.logical_and(seen, rows - cols < window)
            s = jnp.where(seen, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(first_k_block, num_k_blocks, body,
                                  (m0, l0, acc0))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    if lse_ref is not None:
        lse_ref[0, 0] = m + jnp.log(jnp.maximum(l, 1e-30))


def _flash_fwd_ragged_kernel(lengths_ref, q_ref, k_ref, v_ref, o_ref, *,
                             block_q, **kw):
    """The forward kernel told each batch row's length (scalar prefetch): a
    q block that starts at or past it is rows of padding, costs nothing and
    is zeros. Causal, so the live blocks never see a key past their own rows:
    they are computed as ever."""
    qi = pl.program_id(2)
    live = qi * block_q < lengths_ref[pl.program_id(0)]

    @pl.when(live)
    def _():
        _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, block_q=block_q, qi=qi,
                          **kw)

    @pl.when(jnp.logical_not(live))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


# K and V of one (batch, KV head) sit whole in VMEM, double buffered: over
# this many bytes the call asks Mosaic for more than its default 16 MB of
# scoped VMEM (a v5e has 128 MiB). 4 MB at the 4096 rows of the training
# cell: calls that fit are compiled as they were
KV_VMEM_DEFAULT_BYTES = 8 * 2 ** 20


def _flash_fwd(q, k, v, causal: bool, scale: float, block_q: int, block_k: int,
               interpret: bool, with_lse: bool = True,
               window: Optional[int] = None, lengths=None):
    """q: [B, Sq, Hq, D] -> (out [B, Sq, Hq, Dv], lse [B, Hq, Sq, 1] fp32 or
    None). lse carries a trailing singleton so its blocks satisfy the TPU
    (8, 128) tiling rule; inference-only callers pass with_lse=False to skip
    the extra HBM write entirely. Requires Sq % block_q == 0 and
    Skv % block_k == 0 (caller pads). v may have a width of its own (Dv;
    the call is then named ``flash_mla_fwd``). ``lengths`` (int32 [B], causal,
    without lse): q blocks wholly past a row's length are not computed."""
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    dv = v.shape[-1]
    q_per_kv = hq // hkv
    # layout for the kernel: [B, H, S, D]
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    grid = (b, hq, sq // block_q)
    kernel = functools.partial(
        _flash_fwd_kernel,
        block_q=block_q,
        block_k=block_k,
        seq_kv=skv,
        causal=causal,
        scale=scale,
        offset=skv - sq,
    )
    call = {}
    if window is not None:
        kernel = functools.partial(kernel, window=window)
        call["name"] = "flash_window_fwd"
    if dv != d:
        call["name"] = "flash_mla_fwd"
    kv_vmem = 2 * skv * (d + dv) * k.dtype.itemsize
    if kv_vmem > KV_VMEM_DEFAULT_BYTES:
        call["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=kv_vmem + 16 * 2 ** 20)
    in_specs = [
        pl.BlockSpec((1, 1, block_q, d), lambda bb, h, i: (bb, h, i, 0)),
        pl.BlockSpec((1, 1, skv, d), lambda bb, h, i, _g=q_per_kv: (bb, h // _g, 0, 0)),
        pl.BlockSpec((1, 1, skv, dv), lambda bb, h, i, _g=q_per_kv: (bb, h // _g, 0, 0)),
    ]
    o_spec = pl.BlockSpec((1, 1, block_q, dv), lambda bb, h, i: (bb, h, i, 0))
    o_shape = jax.ShapeDtypeStruct((b, hq, sq, dv), q.dtype)
    if lengths is not None:
        # the scalar rides in front of the grid's indices in every index map
        out = pl.pallas_call(
            functools.partial(_flash_fwd_ragged_kernel, **kernel.keywords),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=grid,
                in_specs=[pl.BlockSpec(spec.block_shape,
                                       lambda bb, h, i, _n, _m=spec.index_map:
                                       _m(bb, h, i)) for spec in in_specs],
                out_specs=pl.BlockSpec(o_spec.block_shape,
                                       lambda bb, h, i, _n: (bb, h, i, 0))),
            out_shape=o_shape,
            interpret=interpret,
            **call,
        )(lengths.astype(jnp.int32), qt, kt, vt)
        return out.transpose(0, 2, 1, 3), None
    if with_lse:
        out, lse = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=in_specs,
            out_specs=[
                o_spec,
                pl.BlockSpec((1, 1, block_q, 1), lambda bb, h, i: (bb, h, i, 0)),
            ],
            out_shape=[
                o_shape,
                jax.ShapeDtypeStruct((b, hq, sq, 1), jnp.float32),
            ],
            interpret=interpret,
            **call,
        )(qt, kt, vt)
    else:
        out = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=in_specs,
            out_specs=o_spec,
            out_shape=o_shape,
            interpret=interpret,
            **call,
        )(qt, kt, vt)
        lse = None
    return out.transpose(0, 2, 1, 3), lse


# what a step of the backward's table is, beside its two block indices
_PAIR_FIRST, _PAIR_LAST, _PAIR_EDGE = 1, 2, 4


def _flash_bwd_pairs(nq, nk, block_q, block_k, offset, causal, window):
    """The (q block, k block) pairs the mask admits, in the order the
    backward walks them: k block by k block, inside it the q blocks. Two
    int32 tables, a pair an entry: ``i | j << 16`` and flags that say
    whether the pair is the first or the last of its k block (dK and dV are
    zeroed at the one and written at the other) and whether it lies on an
    edge of the mask (only there is the mask applied). A k block no query
    sees (keys older than every window, where ``skv > sq``) keeps one pair
    that is all mask, so its dK and dV are written as zeros."""
    q_lo = offset + np.arange(nq)[None, :] * block_q
    k_lo = np.arange(nk)[:, None] * block_k
    q_hi, k_hi = q_lo + block_q - 1, k_lo + block_k - 1
    admitted = np.ones((nk, nq), bool)
    whole = np.ones((nk, nq), bool)
    if causal:
        admitted &= q_hi >= k_lo
        whole &= q_lo >= k_hi
    if window is not None:
        admitted &= q_lo - k_hi < window
        whole &= q_hi - k_lo < window
    where, flags = [], []
    for j in range(nk):
        i = np.flatnonzero(admitted[j])
        edge = ~whole[j, i] * _PAIR_EDGE
        if not i.size:
            i, edge = np.zeros(1, int), np.full(1, _PAIR_EDGE)
        edge[0] |= _PAIR_FIRST
        edge[-1] |= _PAIR_LAST
        where.append(i | j << 16)
        flags.append(edge)
    return (np.concatenate(where).astype(np.int32),
            np.concatenate(flags).astype(np.int32))


# The dQ of a KV head's q heads stays in VMEM for the whole walk, float32
# scratch and the output block twice. Up to this many bytes of it the whole
# group is walked in one grid step (68 MiB: Mellum's 8 heads x 8,192 rows, one
# head x 65,536); past it the group goes in equal parts (Mistral's 4 heads x
# 32,768 rows: two of 2), so the call's ask stops growing with the group and
# stays under a v5e's 128 MiB with the 16 MB a head's pair takes beside it
BWD_DQ_VMEM_BYTES = 80 * 2 ** 20


def _flash_bwd_kernel(where_ref, flags_ref, q_ref, do_ref, lse_ref, delta_ref,
                      k_ref, v_ref, dk_ref, dv_ref, dq_ref, dk_acc, dv_acc,
                      dq_acc, *, block_q, block_k, causal, scale, offset, window):
    """ONE (q block, k block) pair a grid step, for (batch, part of a KV
    head's group of q heads, pair of ``_flash_bwd_pairs``), every q head of
    the part in turn (the part is the whole group where its dQ fits
    ``BWD_DQ_VMEM_BYTES``): the
    scores, p, dP and dS of a head are formed once, keys down the sublanes
    and queries along the lanes (so that lse and delta ride as lane-dense
    rows and dV and dK take p^T and dS^T as they stand), and feed all three
    of dV += p^T dO, dK += dS^T q and dQ += dS k, each summed in float32
    scratch and written once in the storage dtype: dK and dV of the k block,
    over the heads and the q blocks, at the block's last pair; dQ of the
    part's heads, which the pair axis does not move, at the walk's last.
    ``scale`` multiplies dK and dQ there, once a row and not once a score."""
    t = pl.program_id(2)
    where, flags = where_ref[t], flags_ref[t]
    i, j = where & 0xFFFF, where >> 16

    @pl.when(t == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(flags & _PAIR_FIRST != 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def pair(edge):
        k, v = k_ref[0, 0], v_ref[0, 0]
        rows_of_i = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)
        nt = (((1,), (1,)), ((), ()))
        if edge:
            keys = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 0)
            rows = offset + i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 1)
            seen = rows >= keys
            if window is not None:
                seen = jnp.logical_and(seen, rows - keys < window)

        def head(h, _):
            q, do = q_ref[0, h], do_ref[0, h]  # storage dtype: bf16 on the MXU
            s = jax.lax.dot_general(
                k, q, nt, preferred_element_type=jnp.float32) * scale  # [bk, bq]
            if edge:
                s = jnp.where(seen, s, NEG_INF)
            p = jnp.exp(s - lse_ref[0, h, 0])  # lse, delta: [1, bq]
            dp = jax.lax.dot_general(v, do, nt, preferred_element_type=jnp.float32)
            ds = (p * (dp - delta_ref[0, h, 0])).astype(q.dtype)
            dv_acc[...] += jnp.dot(p.astype(do.dtype), do,
                                   preferred_element_type=jnp.float32)
            dk_acc[...] += jnp.dot(ds, q, preferred_element_type=jnp.float32)
            dq_acc[h, rows_of_i, :] += jax.lax.dot_general(
                ds, k, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

        jax.lax.fori_loop(0, q_ref.shape[1], head, None)

    if causal:
        pl.when(flags & _PAIR_EDGE != 0)(lambda: pair(True))
        pl.when(flags & _PAIR_EDGE == 0)(lambda: pair(False))
    else:
        pair(False)

    @pl.when(flags & _PAIR_LAST != 0)
    def _():
        dk_ref[0, 0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)

    @pl.when(t == pl.num_programs(2) - 1)
    def _():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _flash_bwd(q, k, v, out, lse, g, causal, scale, block_q, block_k, interpret,
               window=None):
    """dQ, dK and dV in ONE pass over the block pairs the mask admits. K, V
    and a group's q and dO stream a block a pair; what stays in VMEM whole is
    the dQ of the q heads a grid step walks (float32 while it is summed, the
    storage dtype on its way out), which is what the call asks Mosaic for: a
    KV head's whole group where that fits ``BWD_DQ_VMEM_BYTES``, else the
    largest equal part of it that does, and then each part writes its dK and
    dV in float32 and they are summed here before the one cast. The q block
    is twice the forward's where the rows divide: a pair does five products
    where a forward block does two, and a grid step costs what it costs
    (PERF.md 6, PR 58). The call under a window is named ``flash_window_bwd``."""
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    group = hq // hkv
    if sq % (2 * block_q) == 0:
        block_q *= 2
    nq, nk = sq // block_q, skv // block_k
    where, flags = _flash_bwd_pairs(nq, nk, block_q, block_k, skv - sq, causal,
                                    window)
    # a head's dQ, float32 scratch and the output block double buffered, and
    # its blocks of q and dO, double buffered
    head_vmem = d * (sq * (4 + 2 * q.dtype.itemsize)
                     + 4 * block_q * q.dtype.itemsize)
    heads = max(n for n in range(1, group + 1)
                if group % n == 0 and (n == 1 or n * head_vmem <= BWD_DQ_VMEM_BYTES))
    parts = group // heads
    qt = q.transpose(0, 2, 1, 3)
    dot = g.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    # delta_i = sum_d dO_i · O_i  (the softmax-jacobian row correction); it
    # and lse as rows of a q block, along the lanes: [b, hq, nq, 1, block_q]
    delta = jnp.einsum(
        "bqhd,bqhd->bhq", g.astype(jnp.float32), out.astype(jnp.float32)
    ).reshape(b, hq, nq, 1, block_q)
    lse = lse.reshape(b, hq, nq, 1, block_q)

    q_spec = pl.BlockSpec(
        (1, heads, block_q, d),
        lambda bb, part, t, where, flags: (bb, part, where[t] & 0xFFFF, 0))
    row_spec = pl.BlockSpec(
        (1, heads, 1, 1, block_q),
        lambda bb, part, t, where, flags: (bb, part, where[t] & 0xFFFF, 0, 0))
    kv_spec = pl.BlockSpec(
        (1, 1, block_k, d),
        lambda bb, part, t, where, flags: (bb, part // parts, where[t] >> 16, 0))
    dkv_spec = pl.BlockSpec(
        (1, 1, block_k, d),
        lambda bb, part, t, where, flags: (bb, part, where[t] >> 16, 0))
    dq_spec = pl.BlockSpec(
        (1, heads, sq, d), lambda bb, part, t, where, flags: (bb, part, 0, 0))
    call = {}
    if window is not None:
        call["name"] = "flash_window_bwd"
    dk, dv, dq = pl.pallas_call(
        functools.partial(
            _flash_bwd_kernel, block_q=block_q, block_k=block_k, causal=causal,
            scale=scale, offset=skv - sq, window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, hkv * parts, where.size),
            in_specs=[q_spec, q_spec, row_spec, row_spec, kv_spec, kv_spec],
            out_specs=[dkv_spec, dkv_spec, dq_spec],
            scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32)] * 2
            + [pltpu.VMEM((heads, sq, d), jnp.float32)]),
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv * parts, skv, d),
                                 k.dtype if parts == 1 else jnp.float32),
            jax.ShapeDtypeStruct((b, hkv * parts, skv, d),
                                 v.dtype if parts == 1 else jnp.float32),
            jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        ],
        # beside the dQ: what a head's pair takes (K, V, dK, dV, the scores
        # and their kin: 16 MB holds blocks of 512 x 512 and twice that)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=heads * head_vmem + 16 * 2 ** 20),
        interpret=interpret,
        **call,
    )(jnp.asarray(where), jnp.asarray(flags), qt, dot, lse, delta, kt, vt)
    if parts > 1:
        dk, dv = (x.reshape(b, hkv, parts, skv, d).sum(axis=2).astype(k.dtype)
                  for x in (dk, dv))
    return (dq.transpose(0, 2, 1, 3), dk.transpose(0, 2, 1, 3),
            dv.transpose(0, 2, 1, 3))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_attention(q, k, v, causal, scale, block_q, block_k, interpret,
                     window=None):
    out, _ = _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
                        with_lse=False, window=window)
    return out


def _flash_attention_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
                         window=None):
    out, lse = _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
                          window=window)
    # Tag the kernel outputs so a `save_only_these_names` remat policy can
    # pin EXACTLY these as residuals: the surrounding layer then recomputes
    # the cheap projections for q/k/v while the flash kernel itself is never
    # re-run in the backward pass (models/llama.py remat="save_attn").
    from jax.ad_checkpoint import checkpoint_name

    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return out, (q, k, v, out, lse)


def _flash_attention_bwd(causal, scale, block_q, block_k, interpret, window,
                         residuals, g):
    q, k, v, out, lse = residuals
    return _flash_bwd(q, k, v, out, lse, g, causal, scale, block_q, block_k,
                      interpret, window)


_flash_attention.defvjp(_flash_attention_fwd, _flash_attention_bwd)


def flash_attention(
    q,
    k,
    v,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
    window: Optional[int] = None,
    lengths=None,
):
    """Flash attention with automatic padding to block multiples.

    q: [B, Sq, Hq, D]; k: [B, Skv, Hkv, D] with Hq % Hkv == 0; v: [B, Skv,
    Hkv, Dv], Dv = D or a width of its own (forward only: the unabsorbed
    latent attention's 192 / 128). ``window``: causal sliding window (a query
    sees its ``window`` newest keys, itself among them); it differentiates,
    through kernels that visit only the blocks the window admits.
    ``lengths``: int32 [B], rows that are real (causal, forward only): q
    blocks past them are zeros and cost nothing.
    """
    if window is not None and not causal:
        raise ValueError("a sliding window is causal")
    if scale is None:
        scale = q.shape[-1] ** -0.5
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    if hq % hkv:
        raise ValueError(f"Hq={hq} must be a multiple of Hkv={hkv}")
    block_q = min(block_q, _round_up(sq, 8))
    block_k = min(block_k, _round_up(skv, 8))
    if causal and skv < sq:
        raise ValueError(f"causal attention requires Skv >= Sq, got {skv} < {sq}")
    pad = 0
    if sq % block_q or skv % block_k:
        # Padding changes absolute positions (queries pad at the end, so the
        # kernel's offset = skv-sq arithmetic shifts); with causal masking
        # padded KV rows at the end are never attended by real queries only
        # when both sides grow by the SAME amount p, with (sq+p) % block_q
        # == 0 and (skv+p) % block_k == 0. Find the smallest such p (it
        # always exists when sq == skv: p = -sq mod lcm); fall back to the
        # reference only when no common padding exists.
        import math

        lcm = block_q * block_k // math.gcd(block_q, block_k)
        pad = next(
            (p for p in range(0, lcm + 1)
             if (sq + p) % block_q == 0 and (skv + p) % block_k == 0),
            -1,
        )
        if not causal or pad < 0:
            # runs at trace time, once per compiled shape
            logger.warning(
                "flash_attention: no block padding for q %s / kv %s "
                "(causal=%s); this call computes the S x S reference instead",
                q.shape, k.shape, causal)
            return reference_attention(q, k, v, causal, scale, window)
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    if v.shape[-1] == d and lengths is None:
        out = _flash_attention(q, k, v, causal, scale, block_q, block_k,
                               interpret, window)
    else:
        out, _ = _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
                            with_lse=False, window=window, lengths=lengths)
    if pad:
        out = out[:, :sq]
    return out


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def attention(q, k, v, causal: bool = True, scale: Optional[float] = None, impl: str = "auto",
              window: Optional[int] = None, lengths=None):
    """Dispatch. impl: "flash" | "flash_interpret" | "reference" | "auto".
    ``window``: a causal sliding window (forward and backward). ``lengths``: the
    rows of each batch row that are real; the kernel skips the blocks past
    them, the reference computes them (nobody reads them).

    "flash" is the Pallas kernel and nothing else: where Mosaic cannot
    compile it the compiler's error surfaces. "auto" resolves once per trace
    from the default backend, kernel on a TPU and reference elsewhere; it is
    for code that must also run on a CPU. A measured path names its impl.
    """
    if impl == "auto":
        impl = "flash" if jax.default_backend() == "tpu" else "reference"
    if impl == "reference":
        return reference_attention(q, k, v, causal, scale, window)
    if impl == "flash":
        return flash_attention(q, k, v, causal, scale, window=window,
                               lengths=lengths)
    if impl == "flash_interpret":
        return flash_attention(q, k, v, causal, scale, interpret=True,
                               window=window, lengths=lengths)
    raise ValueError(f"unknown attention impl {impl!r}")
