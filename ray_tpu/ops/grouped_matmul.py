"""One Pallas grouped matmul for the expert layers (``ops/moe.py``).

Rows sorted by expert, ``sizes`` rows each; the rows past ``sum(sizes)``
belong to no expert. Three entry points over one plan of VISITS:

- ``grouped_dot(rows [R, a], w [E, a, b], sizes) -> f32 [R, b]``: each row
  times ITS expert's matrix;
- ``grouped_dot(rows [R, b], w [E, a, b], sizes, transposed=True) -> f32
  [R, a]``: the same contracted over the matrices' LAST dimension (a
  cotangent back through the product to its rows), the stack read as it
  lies: no transposed copy of it;
- ``grouped_outer(rows [R, a], cot [R, b], sizes) -> f32 [E, a, b]``: each
  expert's matrix from ITS rows (the contracted dimension is the ragged
  one); an expert with no row gets zeros.

A VISIT is one (row tile, expert) pair that shares rows: the tiles that hold
no row of any expert are never fetched, multiplied or written, so a block of
16,384 rows that holds 8,500 costs what 8,500 cost. The plan (``_visits``) is
a handful of int32 vectors computed from ``sizes`` in the surrounding
program and prefetched to scalar memory, as the decode kernels prefetch page
tables; the grid has the most visits the static shapes allow (``tiles +
experts - 1``) and the ones past the plan's end repeat its last block
indices, so they fetch nothing and their bodies are skipped. Inside a visit
the rows are multiplied a PIECE at a time and only the pieces that hold a
row of the visit's expert: a tile is what is fetched, a piece is what the
MXU is given, and an expert boundary inside a tile costs one more piece, not
one more tile.

What a caller may rely on: ``grouped_dot`` WRITES only the rows of the
groups (its result past ``sum(sizes)`` is whatever the memory held: never
read it, mask by ``where`` and not by a product); ``grouped_outer`` READS
only them (what its operands hold past ``sum(sizes)``, or in another
expert's rows, reaches no result: both operands are masked by row before the
product).

The arithmetic is the operands' own (bfloat16 on the chip) with float32
accumulation and float32 results. Tiles follow the call's static shapes
(``_tiling``): nothing chooses them from outside. Off a TPU the same kernel
runs interpreted (``INTERPRET``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import _round_up

# None: by the default backend, the Mosaic kernel on a TPU and the
# interpreter elsewhere. A chip-less compile for a described TPU sets False
INTERPRET: Optional[bool] = None

# the rows of a tile (what one visit fetches of the rows and writes of the
# result) and of a piece (what one product inside it takes). Static, from the
# call's shapes in ``_tiling``; rows come in sixteens (a bfloat16 tile).
# Measured on a v5e (PERF.md 6, PR 47) at the four families' shapes: tiles
# of 256, 512 and 1,024 lie within 6% of each other everywhere, Kimi's 43
# rows an expert excepted (1,024: +15%); a row product's pieces of 64 beat
# 128 by 0-9% (fewer rows of the neighbouring experts multiplied), and the
# outer product's of 128 beat 64 by 25% (its float32 result block is read
# and written in VMEM once a piece)
ROW_TILE = 512
DOT_PIECE = 64
OUTER_PIECE = 128
ALIGN = 16
# a block of an expert's matrix in VMEM, double buffered: a matrix over this
# is walked along its MIDDLE dimension (its rows in memory), whole rows at a
# time, and then fetched again by every visit. Mellum's 2304 x 896 (4.1 MB)
# and Nemotron-H's 2688 x 1856 (10 MB) are one block, so consecutive visits
# of one expert fetch it once (Nemotron-H's walked in three: 1.93 ms a call
# for 1.55); Kimi's 7168 x 2048 (29 MB) is walked in four
MATRIX_BLOCK_BYTES = 12 * 2 ** 20
# ``grouped_outer`` keeps a float32 block of the result in VMEM while an
# expert's rows go by: over this it walks the result's middle dimension
# (Mellum's 2304 x 896 is 8.3 MB: whole. Walked in two it read the rows
# twice: 0.89 ms a call for 0.44)
RESULT_BLOCK_BYTES = 10 * 2 ** 20
# beside the blocks: Mosaic's own temporaries (a product before it is
# stored), on a v5e's 128 MiB
VMEM_MARGIN_BYTES = 16 * 2 ** 20


def _interpret() -> bool:
    if INTERPRET is not None:
        return INTERPRET
    return jax.default_backend() != "tpu"


def _divisor(n: int, most: int, align: int) -> int:
    """The largest divisor of ``n`` that is a multiple of ``align`` and at
    most ``most``; ``n`` where there is none (the whole dimension)."""
    for d in range(min(most, n) // align * align, 0, -align):
        if n % d == 0:
            return d
    return n


def _walk_step(mid: int, row_bytes: int, block_bytes: int) -> int:
    """Rows of a block of a [mid, ...] matrix whose rows are ``row_bytes``:
    all of them where the matrix fits ``block_bytes``, else the most whole
    lanes' worth (128) that fit and divide ``mid``."""
    if mid * row_bytes <= block_bytes:
        return mid
    return _divisor(mid, max(block_bytes // row_bytes, 1), 128)


def _tiling(rows: int, row_tile: int, piece: int):
    """(rows of a tile, rows of a piece) for a call of ``rows`` sorted rows:
    a call of fewer than ``row_tile`` (a decode tick's) is one tile."""
    tile = min(row_tile, _round_up(rows, ALIGN))
    return tile, _divisor(tile, piece, ALIGN)


def _visits(sizes, rows: int, tile: int, empty: bool):
    """The plan: for each grid step ``v`` the expert and the row tile it
    works on, each expert's first and last row, and the number of steps that
    are visits. Experts in order, each one's tiles in order, so a tile (or,
    with ``empty``, an expert) is revisited only by consecutive steps.
    ``empty``: an expert without rows is visited once all the same
    (``grouped_outer`` has zeros to write there). Steps past the plan's end
    repeat its last."""
    n = sizes.shape[0]
    tiles = rows // tile
    sizes = sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = jnp.minimum(starts // tile, tiles - 1)
    per = jnp.where(sizes > 0, (ends - 1) // tile - first + 1, int(empty))
    upto = jnp.cumsum(per)
    total = upto[-1]
    v = jnp.minimum(jnp.arange(tiles + n - 1, dtype=jnp.int32),
                    jnp.maximum(total - 1, 0))
    group = jnp.minimum(
        jnp.sum(v[:, None] >= upto[None, :], axis=1, dtype=jnp.int32), n - 1)
    tile_of = first[group] + v - (upto - per)[group]
    return group, tile_of, starts, ends, total[None]


def _span(v, group_ref, tile_ref, starts_ref, ends_ref, tile: int):
    """The rows [lo, hi) of step ``v``'s tile that are its expert's."""
    g = group_ref[v]
    base = tile_ref[v] * tile
    return (jnp.maximum(starts_ref[g] - base, 0),
            jnp.minimum(ends_ref[g] - base, tile))


def _for_pieces(lo, hi, piece: int, body):
    """``body(here, keep)`` for each piece of the tile that holds a row of
    [lo, hi): ``here`` the piece's rows (a slice of the block's refs),
    ``keep`` [piece, 1] which of them are in [lo, hi)."""
    def one(j, carry):
        at = pl.multiple_of(j * piece, piece)
        row = at + jax.lax.broadcasted_iota(jnp.int32, (piece, 1), 0)
        body(pl.ds(at, piece), (row >= lo) & (row < hi))
        return carry

    jax.lax.fori_loop(lo // piece, pl.cdiv(hi, piece), one, None)


def _dot_kernel(group_ref, tile_ref, starts_ref, ends_ref, total_ref,
                x_ref, w_ref, o_ref, *acc, tile: int, piece: int,
                transposed: bool):
    v, k = pl.program_id(1), pl.program_id(2)
    lo, hi = _span(v, group_ref, tile_ref, starts_ref, ends_ref, tile)
    dims = (((1,), (1 if transposed else 0,)), ((), ()))

    def one(here, keep):
        part = jax.lax.dot_general(x_ref[here, :], w_ref[...], dims,
                                   preferred_element_type=jnp.float32)
        if not acc:
            # rows of the tile that are another expert's keep what that
            # expert's visit wrote, or will be written by it
            o_ref[here, :] = jnp.where(keep, part, o_ref[here, :])
            return
        acc_ref, = acc

        @pl.when(k == 0)
        def _():
            acc_ref[here, :] = part

        @pl.when(k > 0)
        def _():
            acc_ref[here, :] += part

        @pl.when(k == pl.num_programs(2) - 1)
        def _():
            o_ref[here, :] = jnp.where(keep, acc_ref[here, :], o_ref[here, :])

    @pl.when((v < total_ref[0]) & (hi > lo))
    def _():
        _for_pieces(lo, hi, piece, one)


def _outer_kernel(group_ref, tile_ref, starts_ref, ends_ref, total_ref,
                  x_ref, c_ref, o_ref, *, tile: int, piece: int):
    v = pl.program_id(1)
    lo, hi = _span(v, group_ref, tile_ref, starts_ref, ends_ref, tile)
    live = v < total_ref[0]
    new = (v == 0) | (group_ref[jnp.maximum(v - 1, 0)] != group_ref[v])

    @pl.when(live & new)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    def one(here, keep):
        def kept(ref):
            # through float32: a v5e selects no bfloat16
            rows = ref[here, :]
            return jnp.where(keep, rows.astype(jnp.float32), 0.0).astype(
                rows.dtype)

        o_ref[...] += jax.lax.dot_general(
            kept(x_ref), kept(c_ref), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(live & (hi > lo))
    def _():
        _for_pieces(lo, hi, piece, one)


def _padded(count: int, tile: int, *arrays):
    pad = _round_up(count, tile) - count
    if not pad:
        return arrays
    return tuple(jnp.pad(a, ((0, pad), (0, 0))) for a in arrays)


def _of_plan(index):
    """A block's index map: ``index(*grid ids, group, tile_of, total)`` of
    the prefetched plan, which rides behind the grid's ids."""
    def index_map(*args):
        *ids, group, tile_of, _starts, _ends, total = args
        return index(*ids, group, tile_of, total)
    return index_map


# the entry points are traced and lowered ONCE a shape, however many calls a
# program makes (Mellum's step: 38 calls of six shapes, and lowering them one
# by one added 2.7 s to every start of the cell, from a warm compile cache
# too); what they read of this module's constants is an argument, so that a
# test that moves a constant is not handed a stale trace
@functools.partial(jax.jit, static_argnames=("transposed", "tiles", "interpret"))
def _dot(rows, w, sizes, *, transposed: bool, tiles, interpret: bool):
    row_tile, piece, block_bytes = tiles
    count, width = rows.shape
    e, mid, last = w.shape
    assert width == (last if transposed else mid), (rows.shape, w.shape)
    size = w.dtype.itemsize
    tile, piece = _tiling(count, row_tile, piece)
    rows, = _padded(count, tile, rows)
    step = _walk_step(mid, last * size, block_bytes)
    # over the matrices' last dimension the walk is over the result's
    # columns; over their middle one it is over the contraction, summed in a
    # float32 scratch block
    n_tiles, k_tiles = (mid // step, 1) if transposed else (1, mid // step)
    plan = _visits(sizes, rows.shape[0], tile, empty=False)

    def k_at(v, k, total):
        # a step past the plan's end fetches nothing new
        return k if k_tiles == 1 else jnp.where(v < total[0], k, k_tiles - 1)

    x_block, o_block = ((tile, width), (tile, step)) if transposed \
        else ((tile, step), (tile, last))

    def x_at(n, v, k, group, tile_of, total):
        return tile_of[v], 0 if transposed else k_at(v, k, total)

    def w_at(n, v, k, group, tile_of, total):
        return group[v], n if transposed else k_at(v, k, total), 0

    def o_at(n, v, k, group, tile_of, total):
        return tile_of[v], n
    scratch = [pltpu.VMEM(o_block, jnp.float32)] if k_tiles > 1 else []
    o_bytes = o_block[0] * o_block[1] * 4
    vmem = 2 * (x_block[0] * x_block[1] * rows.dtype.itemsize
                + step * last * size + o_bytes) + (len(scratch) + 1) * o_bytes
    out = pl.pallas_call(
        functools.partial(_dot_kernel, tile=tile, piece=piece,
                          transposed=transposed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(plan),
            grid=(n_tiles, plan[0].shape[0], k_tiles),
            in_specs=[pl.BlockSpec(x_block, _of_plan(x_at)),
                      pl.BlockSpec((None, step, last), _of_plan(w_at))],
            out_specs=pl.BlockSpec(o_block, _of_plan(o_at)),
            scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((rows.shape[0], o_block[1] * n_tiles),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=vmem + VMEM_MARGIN_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=2 * count * mid * last, transcendentals=0,
            bytes_accessed=count * (width * rows.dtype.itemsize
                                    + o_block[1] * n_tiles * 4)
            + e * mid * last * size),
        name="ragged-dot-rows-t" if transposed else "ragged-dot-rows",
        interpret=interpret,
    )(*plan, rows, w)
    return out[:count]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def grouped_dot(rows, w, sizes, transposed: bool = False):
    """rows [R, a] sorted by expert x w [E, a, b] -> f32 [R, b]; with
    ``transposed`` rows [R, b] x w [E, a, b] over b -> f32 [R, a]. Only the
    rows of the groups are written."""
    return _dot(rows, w, sizes, transposed=transposed,
                tiles=(ROW_TILE, DOT_PIECE, MATRIX_BLOCK_BYTES),
                interpret=_interpret())


def _grouped_dot_fwd(rows, w, sizes, transposed):
    return grouped_dot(rows, w, sizes, transposed), (rows, w, sizes)


def _grouped_dot_bwd(transposed, kept, g):
    """The other two entry points: the cotangent in the operands' type, as
    the expert layer's own reverse pass takes it; what the result's unowned
    rows are handed reaches nothing."""
    rows, w, sizes = kept
    g = g.astype(rows.dtype)
    d_rows = grouped_dot(g, w, sizes, not transposed)
    d_w = grouped_outer(g, rows, sizes) if transposed \
        else grouped_outer(rows, g, sizes)
    valid = (jnp.arange(rows.shape[0]) < jnp.sum(sizes))[:, None]
    return (jnp.where(valid, d_rows, 0).astype(rows.dtype),
            d_w.astype(w.dtype), None)


grouped_dot.defvjp(_grouped_dot_fwd, _grouped_dot_bwd)


@functools.partial(jax.jit, static_argnames=("tiles", "interpret"))
def _outer(rows, cot, sizes, *, tiles, interpret: bool):
    row_tile, piece, block_bytes = tiles
    count, a = rows.shape
    b = cot.shape[1]
    e = sizes.shape[0]
    assert cot.shape[0] == count, (rows.shape, cot.shape)
    tile, piece = _tiling(count, row_tile, piece)
    rows, cot = _padded(count, tile, rows, cot)
    step = _walk_step(a, b * 4, block_bytes)
    plan = _visits(sizes, rows.shape[0], tile, empty=True)
    vmem = 2 * tile * (step * rows.dtype.itemsize + b * cot.dtype.itemsize) \
        + 3 * step * b * 4
    return pl.pallas_call(
        functools.partial(_outer_kernel, tile=tile, piece=piece),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(plan),
            grid=(a // step, plan[0].shape[0]),
            in_specs=[
                pl.BlockSpec((tile, step), _of_plan(
                    lambda i, v, group, tile_of, total: (tile_of[v], i))),
                pl.BlockSpec((tile, b), _of_plan(
                    lambda i, v, group, tile_of, total: (tile_of[v], 0)))],
            out_specs=pl.BlockSpec((None, step, b), _of_plan(
                lambda i, v, group, tile_of, total: (group[v], i, 0)))),
        out_shape=jax.ShapeDtypeStruct((e, a, b), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 2,
            vmem_limit_bytes=vmem + VMEM_MARGIN_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=2 * count * a * b, transcendentals=0,
            bytes_accessed=count * (a * rows.dtype.itemsize
                                    + b * cot.dtype.itemsize) + e * a * b * 4),
        name="ragged-dot-outer",
        interpret=interpret,
    )(*plan, rows, cot)


def grouped_outer(rows, cot, sizes):
    """rows [R, a], cot [R, b], both sorted by expert -> f32 [E, a, b]: each
    expert's matrix is the product over ITS rows, zeros where it has none.
    Nothing past ``sum(sizes)`` is read into a result."""
    return _outer(rows, cot, sizes,
                  tiles=(ROW_TILE, OUTER_PIECE, RESULT_BLOCK_BYTES),
                  interpret=_interpret())
