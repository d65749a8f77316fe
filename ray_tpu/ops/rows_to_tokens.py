"""One Pallas kernel for the expert layer's combine (``ops/moe.py``): the sum
of a compacted block's rows onto their tokens, forward (the weighted outputs)
and reverse (the row cotangents).

``rows_to_tokens(rows f32 [cap, h], token int32 [cap], t, factor=None,
onto=None) -> f32 [t, h]``: ``out[i]`` is the float32 sum of the rows whose
``token`` is ``i`` (each times its ``factor``, float32 [cap], where one is
given), added to ``onto`` where one is given (``(sums f32 [t, h], keep)``: a
loop's carry, whose memory the result takes; where the scalar ``keep`` is
false what it holds is not read and the sum starts from zeros). A row
whose ``token`` is ``t`` goes nowhere and WHAT IT HOLDS IS NEVER READ INTO A
RESULT (the grouped product leaves whatever the memory held past its groups,
maybe not numbers): it is selected away, not multiplied by zero. A token
with no row gets zeros. It is what ``jnp.zeros((t, h)).at[token].add(rows,
mode="drop")`` computes, in the order the rows lie (XLA's scatter has its
own order; both are float32 sums of float32 rows).

How: the block's rows are walked, not the tokens'. The row tiles that hold a
row with a token stream through VMEM by the pipeline, whole tiles at the
memory's bandwidth, and the tiles behind the last such row are never fetched
(the block is twice the expected rows: half of it is tail); the RESULT stays
in VMEM for the whole call, a block of its columns at a time (``[t, h]``
float32 is 38-59 MB at the cells' shapes), starts from zeros or from the
block of ``onto`` (one DMA), and is written to HBM once; ``token`` and
``factor`` are prefetched to scalar memory and each row is
added to its token's row of the result by a load, an add and a store at a
dynamic sublane. Nothing is planned outside but the number of tiles to
fetch. (A row DMA a token's row, the walk the other way round, cannot be
built on a float32 ``[cap, h]`` in HBM: it lies in tiles of 8 rows, and
Mosaic refuses a slice of one. PERF.md 6, PR 48.)

Tiles follow the call's static shapes (``_tiling``): nothing chooses them
from outside. Off a TPU the same kernel runs interpreted
(``grouped_matmul.INTERPRET``). The program's name is ``rows-to-tokens``:
the benchmark's readers of ``^ragged-dot`` (the grouped products' share and
roofline) do not count it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import grouped_matmul
from ray_tpu.ops.attention import _round_up
from ray_tpu.ops.grouped_matmul import (
    VMEM_MARGIN_BYTES, _divisor, _padded)

# the rows of a tile (what one grid step fetches) and how many of them one
# trip of the kernel's loop adds, unrolled (Mosaic unrolls a loop wholly or
# not at all). Static, from the call's shapes in ``_tiling``. Measured on a
# v5e (PERF.md 6, PR 48) at the three cells' shapes: tiles of 256, 512 and
# 1,024 and trips of 8 and 16 rows lie within 5% of each other everywhere
ROW_TILE = 512
ROWS_A_TRIP = 8
# the block of the result that stays in VMEM while the rows go by (double
# buffered by the pipeline): a result over this is walked along its columns,
# whole lanes' worth (128), and the rows are fetched once a block of columns.
# At 20 MB Mellum's result (4,096 x 2,304) is two blocks: 0.24 ms a call
# (10 MB, four blocks: 0.42; 40 MB, whole: 0.22 in 101 MB of VMEM); Kimi's
# (2,048 x 7,168, 512 rows held: the time is the result's 59 MB written
# once) four: 0.12 (40 MB, two blocks, writes less of it under the rows:
# 0.18); Laguna's (4,096 x 2,048) two: 0.14 (whole: 0.10)
RESULT_BLOCK_BYTES = 20 * 2 ** 20


def _tiling(cap: int, t: int, h: int, row_tile: int, trip: int,
            block_bytes: int):
    """(rows of a tile, columns of a block of the result) for a call of
    ``cap`` rows onto ``t`` tokens of ``h``: a call of fewer rows than
    ``row_tile`` is one tile; the result's block is the most whole lanes'
    worth that divide ``h`` and fit ``block_bytes``, all of ``h`` where
    ``h`` is no multiple of 128 (one block, as wide as it is)."""
    tile = min(row_tile, _round_up(cap, trip))
    return tile, _divisor(h, max(block_bytes // (t * 4), 128), 128)


def _kernel(live_ref, token_ref, factor_ref, keep_ref, rows_ref, *refs,
            tile: int, trip: int, t: int, weighted: bool):
    # refs: the result's block; with ``onto`` its sums, in HBM, before it
    # and a DMA semaphore behind it
    onto_ref, o_ref, sem = refs if len(refs) == 3 else (None, *refs, None)
    c, v = pl.program_id(0), pl.program_id(1)

    @pl.when((v == 0) & (keep_ref[0] == 0))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    if onto_ref is not None:
        @pl.when((v == 0) & (keep_ref[0] != 0))
        def _():
            columns = o_ref.shape[1]
            here = pl.ds(pl.multiple_of(c * columns, columns), columns)
            copy = pltpu.make_async_copy(onto_ref.at[:, here], o_ref, sem)
            copy.start()
            copy.wait()

    def one(r):
        at = v * tile + r
        token = token_ref[at]
        row = rows_ref[pl.ds(r, 1), :]
        if weighted:
            row = row * factor_ref[at]
        # a row to nowhere is selected away: what it holds may be no number
        row = jnp.where(token < t, row, 0.0)
        o_ref[pl.ds(jnp.minimum(token, t - 1), 1), :] += row

    def some(i, carry):
        for j in range(trip):
            one(i * trip + j)
        return carry

    @pl.when(v < live_ref[0])
    def _():
        jax.lax.fori_loop(0, tile // trip, some, None)


@functools.partial(jax.jit, static_argnames=("t", "tiles", "interpret"))
def _rows_to_tokens(rows, token, factor, onto, *, t: int, tiles,
                    interpret: bool):
    row_tile, trip, block_bytes = tiles
    cap, h = rows.shape
    assert token.shape == (cap,), (rows.shape, token.shape)
    weighted = factor is not None
    tile, columns = _tiling(cap, t, h, row_tile, trip, block_bytes)
    rows, = _padded(cap, tile, rows)
    pad = rows.shape[0] - cap
    token = jnp.pad(token.astype(jnp.int32), (0, pad), constant_values=t)
    # unweighted, the factor is one word that is never read
    factor = jnp.pad(factor.astype(jnp.float32), (0, pad)) if weighted \
        else jnp.zeros((1,), jnp.float32)
    sums, keep = onto or ((), False)
    keep = jnp.asarray(keep, jnp.int32).reshape(1)
    # the tiles up to the last row that has a token: the rest is not fetched
    last = jnp.max(jnp.where(token < t, jnp.arange(token.shape[0]), -1))
    live = (last // tile + 1).astype(jnp.int32)[None]

    def rows_at(c, v, live, *_):
        # a step past the last live tile fetches nothing new
        return jnp.minimum(v, jnp.maximum(live[0] - 1, 0)), c

    return pl.pallas_call(
        functools.partial(_kernel, tile=tile, trip=trip, t=t,
                          weighted=weighted),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(h // columns, rows.shape[0] // tile),
            in_specs=[pl.BlockSpec((tile, columns), rows_at)]
            + [pl.BlockSpec(memory_space=pl.ANY)] * bool(onto),
            out_specs=pl.BlockSpec((t, columns), lambda c, v, *_: (0, c)),
            scratch_shapes=[pltpu.SemaphoreType.DMA(())] * bool(onto)),
        out_shape=jax.ShapeDtypeStruct((t, h), jnp.float32),
        # the result takes the carry's memory: a block of columns is read
        # from it before that block is written, and no other block is
        input_output_aliases={5: 0} if onto else {},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 2,
            vmem_limit_bytes=2 * (tile + t) * columns * 4
            + VMEM_MARGIN_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=cap * h, transcendentals=0,
            bytes_accessed=(cap + t) * h * 4 + cap * 8),
        name="rows-to-tokens",
        interpret=interpret,
    )(live, token, factor, keep, rows, *([sums] if onto else []))


def rows_to_tokens(rows, token, t: int, factor=None, onto=None):
    """rows f32 [cap, h], token int32 [cap] (``t``: to nowhere), factor None
    or f32 [cap], onto None or (f32 [t, h], a bool scalar) -> f32 [t, h]:
    each token's rows (times their factors) summed in float32, onto what
    ``onto`` holds where its scalar says to keep it, else onto zeros (a token
    with no row gets zeros). A row to nowhere is never read into a result."""
    if rows.dtype != jnp.float32:
        raise ValueError(f"rows_to_tokens sums float32 rows, not {rows.dtype}")
    return _rows_to_tokens(
        rows, token, factor, onto, t=t,
        tiles=(ROW_TILE, ROWS_A_TRIP, RESULT_BLOCK_BYTES),
        interpret=grouped_matmul._interpret())
