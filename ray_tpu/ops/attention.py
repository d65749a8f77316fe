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
  calls are named ``flash_window_fwd``, ``flash_window_bwd_dq`` and
  ``flash_window_bwd_dkv`` in a profile.
- GQA: q heads map onto kv heads through the BlockSpec index_map
  (h // q_per_kv), so kv tensors are never materialized per-q-head.
- widths: q and k share one head width, any the compiler tiles (128 as the
  other families', 192 = 128 + the 64 rotated of latent attention); v the
  same or, forward only, one of its own (128 beside 192: the call is then
  named ``flash_mla_fwd`` in a profile), and the output is as wide as v.
  With ``lengths`` (forward only) a batch row's q blocks past its real rows
  are skipped: one program for every prompt length costs what the prompt
  needs.
- backward: Pallas kernels with the standard flash-bwd recurrence — the
  forward also emits the logsumexp per row; bwd recomputes p = exp(qk−lse)
  blockwise, so S×S never materializes. Two kernels: dq (grid over q blocks)
  and dk/dv (grid over k blocks, accumulated at q-head granularity then
  reduced onto kv heads for GQA). Under a window the dq kernel's K loop
  starts, and the dk/dv kernel's Q loop stops, at the blocks the window
  admits. Defined where q, k and v share one width and no ``lengths`` are
  given.

Replaces-the-capability-of: the reference's NCCL-attached attention stacks
are external (DeepSpeed etc. via train integrations); here attention is a
first-class framework op.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.utils.logging import get_logger

logger = get_logger("ops.attention")

# Block sizes for a v5e: small blocks (128/128) leave the MXU idle between
# grid steps. What these reach is measured where the kernel is used: the
# benchmark's flash_fwd_roofline / flash_bwd_roofline in train_4k (PERF.md 3).
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
# the same for what the dK/dV kernel keeps whole: q, dO and the two float32
# columns of one (batch, q head). 12 MB at the 4096 rows of ``train_4k``,
# which compiles as it did; 24 MB at 8192
ROWS_VMEM_DEFAULT_BYTES = 12 * 2 ** 20


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


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                         *, block_q, block_k, seq_kv, causal, scale, offset,
                         window=None):
    """dQ for one (batch, q_head, q_block): stream K/V blocks, recompute
    p = exp(s - lse), ds = p * (dO·Vᵀ - delta), dq += scale · ds · K. Under a
    ``window`` the stream starts at the first K block the q block's first
    row sees, as the forward's does."""
    qi = pl.program_id(2)
    q = q_ref[0, 0]  # storage dtype: bf16 dots on the MXU, f32 accumulate
    do = do_ref[0, 0]
    lse = lse_ref[0, 0]  # [block_q, 1] f32
    delta = delta_ref[0, 0]  # [block_q, 1] f32
    d = q.shape[-1]

    q_start = qi * block_q + offset
    if causal:
        num_k_blocks = jax.lax.div(
            jnp.minimum(q_start + block_q, seq_kv) + block_k - 1, block_k
        )
    else:
        num_k_blocks = seq_kv // block_k
    first_k_block = 0
    if window is not None:
        first_k_block = jnp.maximum(q_start - window + 1, 0) // block_k

    def body(j, dq):
        k_blk = k_ref[0, 0, pl.ds(j * block_k, block_k), :]
        v_blk = v_ref[0, 0, pl.ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            cols = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            seen = rows >= cols
            if window is not None:
                seen = jnp.logical_and(seen, rows - cols < window)
            s = jnp.where(seen, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = (p * (dp - delta) * scale).astype(k_blk.dtype)
        return dq + jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    dq = jax.lax.fori_loop(first_k_block, num_k_blocks, body,
                           jnp.zeros((block_q, d), jnp.float32))
    dq_ref[0, 0] = dq.astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, *, block_q, block_k, seq_q, causal,
                          scale, offset, window=None):
    """dK/dV for one (batch, q_head, k_block): stream q blocks from the first
    causally-visible one, and under a ``window`` only as far as the last q
    block whose first row still sees the k block's last key. Accumulated per
    Q head; the caller reduces onto kv heads (GQA)."""
    ki = pl.program_id(2)
    k_blk = k_ref[0, 0]  # storage dtype (bf16 MXU path)
    v_blk = v_ref[0, 0]
    d = k_blk.shape[-1]
    k_start = ki * block_k

    num_q_blocks = seq_q // block_q
    if causal:
        # first q block whose LAST row (abs pos offset + i*bq + bq - 1) can
        # see this k block: i >= (k_start - offset) / bq
        first = jax.lax.max(0, jax.lax.div(k_start - offset, block_q))
    else:
        first = 0
    if window is not None:
        # the newest row that sees the block's LAST key (k_start + bk - 1)
        # is that key's position + window - 1
        num_q_blocks = jax.lax.min(num_q_blocks, jax.lax.div(
            k_start + block_k + window - 2 - offset, block_q) + 1)

    def body(i, carry):
        dk, dv = carry
        q_blk = q_ref[0, 0, pl.ds(i * block_q, block_q), :]
        do_blk = do_ref[0, 0, pl.ds(i * block_q, block_q), :]
        lse_blk = lse_ref[0, 0, pl.ds(i * block_q, block_q), :]  # [bq, 1]
        delta_blk = delta_ref[0, 0, pl.ds(i * block_q, block_q), :]
        s = jax.lax.dot_general(
            q_blk, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if causal:
            rows = offset + i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            seen = rows >= cols
            if window is not None:
                seen = jnp.logical_and(seen, rows - cols < window)
            s = jnp.where(seen, s, NEG_INF)
        p = jnp.exp(s - lse_blk)
        p_lo = p.astype(do_blk.dtype)
        dv = dv + jax.lax.dot_general(
            p_lo, do_blk, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do_blk, v_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = (p * (dp - delta_blk) * scale).astype(q_blk.dtype)
        dk = dk + jax.lax.dot_general(
            ds, q_blk, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return dk, dv

    dk0 = jnp.zeros((block_k, d), jnp.float32)
    dv0 = jnp.zeros((block_k, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(first, num_q_blocks, body, (dk0, dv0))
    dk_ref[0, 0] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)


def _flash_bwd(q, k, v, out, lse, g, causal, scale, block_q, block_k, interpret,
               window=None):
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    q_per_kv = hq // hkv
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    dot = g.transpose(0, 2, 1, 3)
    # delta_i = sum_d dO_i · O_i  (the softmax-jacobian row correction)
    delta = jnp.einsum(
        "bqhd,bqhd->bhq", g.astype(jnp.float32), out.astype(jnp.float32)
    )[..., None]
    offset = skv - sq

    q_spec = pl.BlockSpec((1, 1, block_q, d), lambda bb, h, i: (bb, h, i, 0))
    q_full = pl.BlockSpec((1, 1, sq, d), lambda bb, h, i: (bb, h, 0, 0))
    kv_full = pl.BlockSpec((1, 1, skv, d), lambda bb, h, i, _g=q_per_kv: (bb, h // _g, 0, 0))
    kv_blk = pl.BlockSpec((1, 1, block_k, d), lambda bb, h, j, _g=q_per_kv: (bb, h // _g, j, 0))
    row_blk = pl.BlockSpec((1, 1, block_q, 1), lambda bb, h, i: (bb, h, i, 0))
    row_full = pl.BlockSpec((1, 1, sq, 1), lambda bb, h, i: (bb, h, 0, 0))
    dq_call, dkv_call, windowed = {}, {}, {}
    if window is not None:
        windowed = {"window": window}
        dq_call["name"] = "flash_window_bwd_dq"
        dkv_call["name"] = "flash_window_bwd_dkv"
    # what sits whole in VMEM, double buffered: K and V for dQ; q, dO and the
    # two float32 columns (a lane-padded tile a row: 512 bytes) for dK/dV
    kv_vmem = 2 * skv * 2 * d * k.dtype.itemsize
    if kv_vmem > KV_VMEM_DEFAULT_BYTES:
        dq_call["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=kv_vmem + 16 * 2 ** 20)
    rows_vmem = 2 * sq * (2 * d * q.dtype.itemsize + 2 * 128 * 4)
    if rows_vmem > ROWS_VMEM_DEFAULT_BYTES:
        dkv_call["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=rows_vmem + 16 * 2 ** 20)

    dq = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel, block_q=block_q, block_k=block_k,
            seq_kv=skv, causal=causal, scale=scale, offset=offset, **windowed,
        ),
        grid=(b, hq, sq // block_q),
        in_specs=[q_spec, kv_full, kv_full, q_spec, row_blk, row_blk],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        interpret=interpret,
        **dq_call,
    )(qt, kt, vt, dot, lse, delta)

    dk_h, dv_h = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_kernel, block_q=block_q, block_k=block_k,
            seq_q=sq, causal=causal, scale=scale, offset=offset, **windowed,
        ),
        grid=(b, hq, skv // block_k),
        in_specs=[q_full, kv_blk, kv_blk, q_full, row_full, row_full],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, d), lambda bb, h, j: (bb, h, j, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda bb, h, j: (bb, h, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, skv, d), jnp.float32),
            jax.ShapeDtypeStruct((b, hq, skv, d), jnp.float32),
        ],
        interpret=interpret,
        **dkv_call,
    )(qt, kt, vt, dot, lse, delta)

    # GQA reduction: q-head-granular dk/dv sum onto their kv head
    dk = dk_h.reshape(b, hkv, q_per_kv, skv, d).sum(axis=2)
    dv = dv_h.reshape(b, hkv, q_per_kv, skv, d).sum(axis=2)
    return (
        dq.transpose(0, 2, 1, 3),
        dk.transpose(0, 2, 1, 3).astype(k.dtype),
        dv.transpose(0, 2, 1, 3).astype(v.dtype),
    )


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
