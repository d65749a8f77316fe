"""Decode attention over the page pool: one query token a slot against the
pages that slot owns, costing what is live.

The pool is ``[n_kv, pages, page_size, D]`` a side and a slot's pages are
named by its row of the block table; ``lengths[b]`` is how many cached rows
slot ``b`` attends over, 0 for a slot that holds nothing.

Design (see /opt/skills/guides/pallas_guide.md):
- ONE invocation (a grid of one): the kernel walks the slots itself. A dead slot
  (length 0) is skipped by a scalar read of ``lengths``: no DMA, no block of
  compute; its output rows are the zeros the kernel starts from.
- a block is ``pages_per_block`` pages of EVERY KV head of one slot, fetched
  by one strided DMA a page and side (``n_kv`` rows of a page each) into a
  double buffer; the next block, of this slot or of the next live one, is
  in flight while this one is multiplied. Only the pages that hold live
  rows are fetched: ``cdiv(length, page_size)``, not the block's worth.
- q and K/V go to the MXU as stored (bfloat16), accumulation is float32;
  running max, sum and the output accumulator stay float32 across blocks
  (the online-softmax recurrence of ``ops/attention.py``).
- the heads are told by the shapes (``q`` is ``[B, n_kv, G, D]``), what is
  live by ``lengths``: one kernel for every family that keeps pages.
- with ``starts`` a slot attends over rows ``[starts[b], lengths[b])``, a
  sliding window: columns before the start are masked, pages wholly before
  it are not fetched and blocks wholly before it not multiplied. The table
  row may then be a RING in logical order (``models/laguna.py`` hands the
  window layers the slot's ring from its oldest live page, with lengths and
  starts counted from that page). Without ``starts`` the kernel traces to
  the text it had before there was a window.

- widths: K and V pools of ONE shape, ``[n_kv, pages, page_size, D]`` with D
  a multiple of the 128 lanes (``paged_attention``); or ONE pool of latent
  rows, ``[1, pages, page_size, W]``, whose first ``v_width`` columns are
  also the values (``paged_attention_latent``: multi-head latent attention,
  absorbed: one KV head, a query group of every head, W = 640 for 512 + 64
  stored values, ``v_width`` 512). The same kernel body: there the block's
  rows are fetched ONCE and the values are a lane-aligned slice of them, no
  second pool, no second DMA. W and ``v_width`` are whole lane tiles.

The jitted wrapper is named ``paged_attention`` and so is the call: a
profile's operation reads ``paged_attention.N``, the name the benchmark's
readers look for; the call with ``starts`` is ``paged_attention_window.N``,
the call over a latent pool ``paged_attention_latent.N``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import NEG_INF

# 8 pages of 64 rows: 512 tokens, 128 KB a head and side; K and V double
# buffered are 4 MB of VMEM at 8 KV heads. Measured on a v5e (PERF.md 6)
PAGES_PER_BLOCK = 8


def _kernel(*refs, pages_per_block: int, windowed: bool,
            v_width: Optional[int] = None):
    # lengths_ref (and, windowed, starts_ref): [B] and table_ref:
    # [B * pages_per_slot] in SMEM; q_ref / o_ref: [B, n_kv, G, D] in VMEM;
    # k_hbm / v_hbm: the pool, in HBM; kbuf / vbuf: [2, n_kv,
    # pages_per_block, page_size, D]; sems: DMA semaphores [side, buffer].
    # With ``v_width`` (a latent pool) there is no v_hbm and no vbuf: the
    # values are the first ``v_width`` columns of the fetched key rows, and
    # o_ref is [B, n_kv, G, v_width]
    lengths_ref, *refs = refs
    starts_ref = refs.pop(0) if windowed else None
    if v_width is None:
        table_ref, q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sems = refs
        sides = ((k_hbm, kbuf), (v_hbm, vbuf))
    else:
        table_ref, q_ref, k_hbm, o_ref, kbuf, sems = refs
        sides, vbuf = ((k_hbm, kbuf),), kbuf
    nb, nkv, g, d = q_ref.shape
    ps = k_hbm.shape[2]
    ppb = pages_per_block
    bk = ppb * ps
    pages_per_slot = table_ref.shape[0] // nb

    def next_live(b):
        """The first slot at or after ``b`` with something to attend over;
        ``nb`` where there is none."""
        return jax.lax.while_loop(
            lambda b: jnp.logical_and(
                b < nb, lengths_ref[jnp.minimum(b, nb - 1)] == 0),
            lambda b: b + 1, b)

    def first_block(b):
        """The block that holds slot ``b``'s first attended row."""
        return starts_ref[b] // bk if windowed else 0

    def block_dma(b, i, buf, wait: bool):
        """Start, or wait for, the live pages of block ``i`` of slot ``b``."""
        live_pages = pl.cdiv(lengths_ref[b], ps) - i * ppb
        for j in range(ppb):
            live = j < live_pages
            if windowed:  # a page wholly before the window is not fetched
                live = jnp.logical_and(live,
                                       i * ppb + j >= starts_ref[b] // ps)

            @pl.when(live)
            def _():
                # a wait needs the copy's shape and semaphore, not its source
                page = 0 if wait else table_ref[
                    b * pages_per_slot + i * ppb + j]
                for side, (hbm, vmem) in enumerate(sides):
                    copy = pltpu.make_async_copy(
                        hbm.at[:, page], vmem.at[buf, :, j],
                        sems.at[side, buf])
                    copy.wait() if wait else copy.start()

    # a dead slot's rows stay as they are here. A masked column's weight is
    # an exact 0, but 0 x NaN is NaN: no page is fetched behind a slot's
    # last live one, so what V's buffers hold there must be finite
    o_ref[...] = jnp.zeros_like(o_ref)
    vbuf[...] = jnp.zeros_like(vbuf)

    def slot(carry):
        b, buf = carry
        length = lengths_ref[b]
        blocks = pl.cdiv(length, bk)
        after = next_live(b + 1)

        def block(i, carry):
            m, l, acc, buf = carry
            last = i + 1 == blocks

            @pl.when(jnp.logical_not(last))
            def _():
                block_dma(b, i + 1, 1 - buf, wait=False)

            @pl.when(jnp.logical_and(last, after < nb))
            def _():
                block_dma(after, first_block(after), 1 - buf, wait=False)

            block_dma(b, i, buf, wait=True)
            q = q_ref[b]                                   # [n_kv, G, D]
            k = kbuf[buf].reshape(nkv, bk, d)
            v = k[..., :v_width] if v_width else \
                vbuf[buf].reshape(nkv, bk, d)
            s = jnp.einsum("hgd,htd->hgt", q, k,
                           preferred_element_type=jnp.float32)
            cols = i * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
            live = cols < length
            if windowed:
                live = jnp.logical_and(live, cols >= starts_ref[b])
            s = jnp.where(live, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * alpha + jnp.einsum(
                "hgt,htd->hgd", p.astype(v.dtype), v,
                preferred_element_type=jnp.float32)
            return m_new, l, acc, 1 - buf

        m0 = jnp.full((nkv, g, 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros((nkv, g, 1), jnp.float32)
        acc0 = jnp.zeros((nkv, g, v_width or d), jnp.float32)
        _, l, acc, buf = jax.lax.fori_loop(first_block(b), blocks, block,
                                           (m0, l0, acc0, buf))
        o_ref[b] = (acc / l).astype(o_ref.dtype)  # length >= 1: l > 0
        return after, buf

    first = next_live(0)

    @pl.when(first < nb)
    def _():
        block_dma(first, first_block(first), 0, wait=False)

    jax.lax.while_loop(lambda c: c[0] < nb, slot, (first, 0))


@functools.partial(jax.jit, static_argnames=("pages_per_block", "interpret"))
def paged_attention(q, k_pool, v_pool, lengths, table, *, starts=None,
                    pages_per_block: int = PAGES_PER_BLOCK,
                    interpret: bool = False):
    """q: [B, nh, D], already scaled; pools: [n_kv, pages, page_size, D];
    lengths: int32 [B], the cached rows a slot attends over, 0 for a slot
    that holds nothing; table: int32 [B, pages_per_slot]. Returns
    [B, nh, D] in q's dtype: softmax(q k^T) v over the slot's first
    ``lengths[b]`` rows, exact zeros where ``lengths[b]`` is 0. Only table
    entries that cover live rows are read.

    ``starts``: int32 [B], the first row a slot attends over (a live slot:
    ``starts[b] < lengths[b]``): the softmax is over rows ``[starts[b],
    lengths[b])`` and table entries of pages wholly before ``starts[b]`` are
    not read either."""
    nb, nh, d = q.shape
    nkv, _, ps, _ = k_pool.shape
    if nh % nkv:
        raise ValueError(f"{nh} query heads over {nkv} KV heads")
    if k_pool.shape != v_pool.shape or k_pool.shape[3] != d:
        raise ValueError(f"q {q.shape} against pools {k_pool.shape}, "
                         f"{v_pool.shape}")
    ppb = min(pages_per_block, table.shape[1])
    buffers = pltpu.VMEM((2, nkv, ppb, ps, d), k_pool.dtype)
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    windowed = starts is not None
    scalars = (lengths.astype(jnp.int32),)
    if windowed:
        scalars += (starts.astype(jnp.int32),)
    out = pl.pallas_call(
        functools.partial(_kernel, pages_per_block=ppb, windowed=windowed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars) + 1,
            grid=(1,),
            in_specs=[whole, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=whole,
            scratch_shapes=[buffers, buffers,
                            pltpu.SemaphoreType.DMA((2, 2))]),
        out_shape=jax.ShapeDtypeStruct((nb, nkv, nh // nkv, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="paged_attention_window" if windowed else "paged_attention",
        interpret=interpret,
    )(*scalars, table.astype(jnp.int32).reshape(-1),
      q.reshape(nb, nkv, nh // nkv, d), k_pool, v_pool)
    return out.reshape(nb, nh, d)


@functools.partial(jax.jit, static_argnames=("v_width", "pages_per_block",
                                             "interpret"))
def paged_attention_latent(q, pool, lengths, table, *, v_width: int,
                           pages_per_block: int = PAGES_PER_BLOCK,
                           interpret: bool = False):
    """Decode attention over a LATENT pool (multi-head latent attention,
    absorbed): ONE cached row a token, shared by every query head, whose
    first ``v_width`` columns are also the values. q: [B, G, W], already
    scaled (per head: the query carried into the latent space beside its
    rotated part); pool: [1, pages, page_size, W]; lengths, table: as
    ``paged_attention``. Returns [B, G, v_width] in q's dtype: softmax(q
    k^T) k[:, :v_width] over the slot's first ``lengths[b]`` rows, zeros
    where ``lengths[b]`` is 0. Each fetched row is read from HBM ONCE, for
    its scores and its values: there is no second pool. The same kernel
    body as ``paged_attention`` at one KV head and a group of G; the call
    is named ``paged_attention_latent`` in a profile."""
    nb, g, w = q.shape
    one, _, ps, _ = pool.shape
    if one != 1 or pool.shape[3] != w or not 0 < v_width <= w:
        raise ValueError(f"q {q.shape} against a latent pool {pool.shape} "
                         f"with values of {v_width}")
    ppb = min(pages_per_block, table.shape[1])
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        functools.partial(_kernel, pages_per_block=ppb, windowed=False,
                          v_width=v_width),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[whole, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=whole,
            scratch_shapes=[pltpu.VMEM((2, 1, ppb, ps, w), pool.dtype),
                            pltpu.SemaphoreType.DMA((1, 2))]),
        out_shape=jax.ShapeDtypeStruct((nb, 1, g, v_width), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="paged_attention_latent",
        interpret=interpret,
    )(lengths.astype(jnp.int32), table.astype(jnp.int32).reshape(-1),
      q.reshape(nb, 1, g, w), pool)
    return out.reshape(nb, g, v_width)
