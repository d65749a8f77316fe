"""A decode tick's new rows into the page pool, in place: one row a LIVE slot
and KV head, written into ``(head, page, row)`` of a pool the call aliases.

The pool is ``[n_kv, pages, page_size, D]`` in bfloat16
(``ops/paged_attention.py`` reads the same array); ``rows`` is
``[B, n_kv, D]``, one token a slot; ``pages[b]`` and ``rownum[b]`` say where
slot ``b``'s token goes and ``live[b]`` whether it goes anywhere. K and V go
to ONE call. Called by the Llama-shaped family's decode tick
(``models/paged_decode.py`` ``_write_token_rows``).

Design (see /opt/skills/guides/pallas_guide.md):
- ONE invocation (a grid of one) that walks the slots itself, pools in HBM
  (``pl.ANY``) and aliased in to out: nothing the size of a pool, or of a
  page, is moved.
- a single bfloat16 row is not a copy Mosaic takes (a tile is 16 rows of 128
  lanes, two rows to a word), so a slot's write is a read-modify-write of the
  TILE that holds its row: every head's tile of the slot in one strided copy
  ``[n_kv, 16, D]`` in, the one row replaced by a select (bits untouched: no
  arithmetic on what is kept), the same copy out.
- a call moves a tile for a row, 16 times the bytes written (64 slots x K, V
  x 8 heads x 4 KB each way: 8 MB, 10 us at a v5e's 819 GB/s), so the copies
  have to overlap: every live slot's read is started before the first is
  waited for, each has a semaphore of its own, and a slot's write-back starts
  as soon as its tile is patched, while later reads still land. The
  write-backs share one semaphore and are waited for at the end.
- every slot's tiles are in VMEM at once (``fits`` holds the batch to
  ``VMEM_BYTES``: 64 slots x K, V x 8 heads are 4 MB).
- a slot that is not ``live`` is skipped on one scalar read, as
  ``ops/paged_attention.py`` skips a slot of length 0: the tick's ``active``
  says so, and nothing about pages is assumed. The caller's scatter would
  have put such a slot's row on its layer's trash page, of which nothing is
  read; the kernel leaves that page as it was.

The jitted wrapper and the call are both named ``token_rows_write``: a
profile's operation reads ``token_rows_write.N``.
"""

from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
TILE = 16  # rows of a bfloat16 memory tile: 8 sublanes of two rows a word
# every slot's tiles at once: 64 slots x (K, V) x 8 heads x 4 KB
VMEM_BYTES = 4 << 20


def fits(pools: Sequence, slots: int) -> bool:
    """Whether the kernel takes these pools (arrays or ShapeDtypeStructs of
    ONE shape ``[n_kv, pages, ps, D]``) for a batch of ``slots``: bfloat16,
    whole tiles a page, whole lanes a row, and the batch's tiles in
    ``VMEM_BYTES``."""
    n_kv, _, ps, d = pools[0].shape
    return (all(p.dtype == jnp.bfloat16 and p.shape == pools[0].shape
                for p in pools)
            and ps % TILE == 0 and d % LANES == 0
            and len(pools) * slots * n_kv * TILE * d * 2 <= VMEM_BYTES)


def _kernel(pages_ref, rownum_ref, live_ref, *refs, n_pools: int):
    # pages_ref / rownum_ref / live_ref: [B] in SMEM; then n_pools x rows
    # [B, n_kv, D] in VMEM, n_pools x the pool in HBM (the aliased inputs:
    # the outputs are the same memory and the only names used), n_pools x the
    # pool out, tiles [n_pools, B, n_kv, TILE, D], a read semaphore a slot
    # and one for every write-back
    rows = refs[:n_pools]
    pools = refs[2 * n_pools:3 * n_pools]
    tiles, read_sems, write_sem = refs[3 * n_pools:]
    nb, nkv, _ = rows[0].shape

    def copies(b, back: bool):
        row = rownum_ref[b]
        first = pl.multiple_of(row - jnp.bitwise_and(row, TILE - 1), TILE)
        for p, pool in enumerate(pools):
            held = pool.at[:, pages_ref[b], pl.ds(first, TILE)]
            yield (pltpu.make_async_copy(tiles.at[p, b], held, write_sem)
                   if back else
                   pltpu.make_async_copy(held, tiles.at[p, b], read_sems.at[b]))

    def fetch(b):
        for copy in copies(b, back=False):
            copy.start()

    def patch(b):
        for copy in copies(b, back=False):
            copy.wait()
        mine = jax.lax.broadcasted_iota(jnp.int32, tiles.shape[3:], 0) \
            == jnp.bitwise_and(rownum_ref[b], TILE - 1)
        for p in range(n_pools):
            for h in range(nkv):
                new = jnp.broadcast_to(rows[p][b, h:h + 1, :], mine.shape)
                tiles[p, b, h] = jnp.where(mine, new, tiles[p, b, h])
        for copy in copies(b, back=True):
            copy.start()

    def settle(b):
        for copy in copies(b, back=True):
            copy.wait()

    for step in (fetch, patch, settle):
        def if_live(b, carry, step=step):
            pl.when(live_ref[b] != 0)(lambda: step(b))
            return carry

        jax.lax.fori_loop(0, nb, if_live, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def token_rows_write(pools: Sequence[jax.Array], rows: Sequence[jax.Array],
                     pages, rownum, live, *, interpret: bool = False
                     ) -> Tuple[jax.Array, ...]:
    """pools: arrays of ONE shape ``[n_kv, pages, page_size, D]`` (``fits``);
    rows: as many ``[B, n_kv, D]``; pages, rownum: int32 [B]; live: bool or
    int [B]. Returns the pools with ``pools[p][h, pages[b], rownum[b]] =
    rows[p][b, h]`` for every live slot and every head, every other row as
    it was: the call aliases them, so under a donated argument or a loop's
    carry they are updated in place. Live slots name pages of their own."""
    pools, rows = tuple(pools), tuple(rows)
    shape, dtype = pools[0].shape, pools[0].dtype
    nb, nkv, d = rows[0].shape
    if (len(pools) != len(rows) or not fits(pools, nb)
            or any(r.shape != (nb, shape[0], shape[3]) for r in rows)):
        raise ValueError(
            f"rows {[r.shape for r in rows]} into pools "
            f"{[(p.shape, p.dtype) for p in pools]}")
    n = len(pools)
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    return tuple(pl.pallas_call(
        functools.partial(_kernel, n_pools=n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * n + [in_hbm] * n,
            out_specs=[in_hbm] * n,
            scratch_shapes=[pltpu.VMEM((n, nb, nkv, TILE, d), dtype),
                            pltpu.SemaphoreType.DMA((nb,)),
                            pltpu.SemaphoreType.DMA(())]),
        out_shape=[jax.ShapeDtypeStruct(shape, dtype)] * n,
        input_output_aliases={3 + n + p: p for p in range(n)},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="token_rows_write",
        interpret=interpret,
    )(pages.astype(jnp.int32), rownum.astype(jnp.int32),
      live.astype(jnp.int32), *(r.astype(dtype) for r in rows), *pools))
