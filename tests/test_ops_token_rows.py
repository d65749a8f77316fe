"""``ops/token_rows.py``: a decode tick's rows into the page pool through the
in-place Pallas writer, interpreted on the CPU, against the scatter it
replaces where a decode program runs the kernels
(``models/paged_decode.py`` ``_scatter_token_rows``): the same bits in the
same places over the WHOLE pool, for the slots that are live."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import paged_decode as pd
from ray_tpu.ops import token_rows

PS = 64
P = 7          # pages a layer, page 0 of a layer's block its trash page


def _bits(x):
    return np.asarray(x.view(jnp.uint16))


def _scatter(pools, rows, pages, rownum, live=None):
    del live   # the scatter writes every slot's row
    return tuple(pd._scatter_token_rows(pool, new, pages, rownum)
                 for pool, new in zip(pools, rows))


_kernel = functools.partial(token_rows.token_rows_write, interpret=True)

# name: (n_kv, B, pages of the pool, pages [B] or None for distinct pages,
# rownum [B] or None for random rows); K and V of rows of 128, every slot live
CASES = {
    # the dense family's decode shape, cut small
    "dense_8_heads": (8, 5, P, None, None),
    # a row at the first and the last row of a tile and of a page
    "tile_and_page_edges": (2, 6, P, None, [0, 15, 16, 47, 48, 63]),
    "one_slot": (8, 1, P, [3], [63]),
    "eleven_slots": (2, 11, 2 * P, None, None),
    # the layer's block is not the first: pages offset by 2 * P
    "third_layers_block": (2, 4, 3 * P,
                           [2 * P + 1, 2 * P + 5, 2 * P + 2, 2 * P + 6], None),
}


def _operands(case, seed=0):
    nkv, b, pages_total, pages, rownum = CASES[case]
    rng = np.random.default_rng(seed)
    pools = tuple(jnp.asarray(rng.standard_normal((nkv, pages_total, PS, 128)),
                              jnp.bfloat16) for _ in range(2))
    rows = tuple(jnp.asarray(rng.standard_normal((b, nkv, 128)), jnp.bfloat16)
                 for _ in range(2))
    if pages is None:
        pages = rng.permutation(pages_total - 1)[:b] + 1
    if rownum is None:
        rownum = rng.integers(0, PS, b)
    return (pools, rows, jnp.asarray(pages, jnp.int32),
            jnp.asarray(rownum, jnp.int32), jnp.ones((b,), bool))


def _whole_pool_equal(case):
    operands = _operands(case)
    for want, got in zip(_scatter(*operands), _kernel(*operands)):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(_bits(got), _bits(want))


def _inactive_slots_beside_live_ones(case):
    """Slots 1, 3, 4 and 6 are inactive: their table rows are zeros, so the
    caller names the trash page of the layer's block (here the second) for
    all four, two of them the same row. The live slots' rows land as the
    scatter's do, and no page but the trash page differs from the scatter's,
    which the kernel leaves as it was. An inactive slot that names a LIVE
    slot's page and row (slot 7 names slot 0's) writes nothing either: what
    decides is ``live``, not the page."""
    del case
    nkv, d, b = 2, 128, 8
    rng = np.random.default_rng(3)
    pools = tuple(jnp.asarray(rng.standard_normal((nkv, 2 * P, PS, d)),
                              jnp.bfloat16) for _ in range(2))
    rows = tuple(jnp.asarray(rng.standard_normal((b, nkv, d)), jnp.bfloat16)
                 for _ in range(2))
    trash = P
    pages = jnp.asarray([P + 1, trash, P + 2, trash, trash, P + 3, trash,
                         P + 1], jnp.int32)
    rownum = jnp.asarray([5, 17, 63, 18, 17, 0, 40, 5], jnp.int32)
    live = jnp.asarray([1, 0, 1, 0, 0, 1, 0, 0], bool)
    alive = np.flatnonzero(np.asarray(live))
    wants = _scatter(pools, tuple(r[alive] for r in rows), pages[alive],
                     rownum[alive])
    gots = _kernel(pools, rows, pages, rownum, live)
    for pool, want, got in zip(pools, wants, gots):
        np.testing.assert_array_equal(_bits(got), _bits(want))
        np.testing.assert_array_equal(_bits(got)[:, :P + 1],
                                      _bits(pool)[:, :P + 1])
        assert (_bits(got) != _bits(pool)).any()


def _under_jit_in_a_scan_carry(case):
    """What the dense family does: the pools ride a ``lax.scan`` as carry,
    donated through ``jit``, one write a step."""
    del case
    pools, rows, pages, rownum, live = _operands("dense_8_heads", seed=1)
    steps = 4
    rng = np.random.default_rng(2)
    many = tuple(jnp.asarray(rng.standard_normal((steps,) + r.shape), r.dtype)
                 for r in rows)
    rownums = (rownum[None, :] + jnp.arange(steps)[:, None] * 13) % PS

    def run(write):
        def program(pools, many, pages, rownums):
            def step(pools, xs):
                *rows, rownum = xs
                return write(pools, tuple(rows), pages, rownum, live), None
            return jax.lax.scan(step, pools, (*many, rownums))[0]
        return jax.jit(program, donate_argnums=(0,))

    copy = lambda: tuple(jnp.array(pool) for pool in pools)  # noqa: E731
    wants = run(_scatter)(copy(), many, pages, rownums)
    gots = run(_kernel)(copy(), many, pages, rownums)
    for pool, want, got in zip(pools, wants, gots):
        np.testing.assert_array_equal(_bits(got), _bits(want))
        assert (_bits(got) != _bits(pool)).any()


@pytest.mark.parametrize("case", [
    *CASES, "inactive_slots_beside_live_ones", "under_jit_in_a_scan_carry"])
def test_row_writer_leaves_the_scatters_bits(case):
    if case == "inactive_slots_beside_live_ones":
        _inactive_slots_beside_live_ones(case)
    elif case == "under_jit_in_a_scan_carry":
        _under_jit_in_a_scan_carry(case)
    else:
        _whole_pool_equal(case)


@pytest.mark.parametrize("page_size,d,dtype,slots,fits", [
    (64, 128, jnp.bfloat16, 64, True),     # the chat cell's: 4 MB of tiles
    (16, 128, jnp.bfloat16, 8, True),
    (64, 128, jnp.bfloat16, 65, False),    # a slot more than VMEM_BYTES hold
    (8, 128, jnp.bfloat16, 8, False),      # half a tile a page
    (64, 64, jnp.bfloat16, 8, False),      # half the lanes a row
    (64, 128, jnp.float32, 8, False)])     # a tile of 8 rows, not built
def test_which_pools_the_row_writer_takes(page_size, d, dtype, slots, fits):
    pools = (jax.ShapeDtypeStruct((8, 5, page_size, d), dtype),) * 2
    assert token_rows.fits(pools, slots) is fits


@pytest.mark.parametrize("use_kernel,page_size,kernel", [
    (True, 64, True), (False, 64, False), (True, 8, False)])
def test_decode_write_follows_use_kernel_and_the_pools_tiles(
        use_kernel, page_size, kernel):
    """``_write_token_rows`` runs the kernel where the decode program runs
    the Pallas kernels and a page is whole tiles, and the scatter, word for
    word, everywhere else: told from the traced program, nothing runs."""
    pools = tuple(jax.ShapeDtypeStruct((2, 5, page_size, 128), jnp.bfloat16)
                  for _ in range(2))
    rows = tuple(jax.ShapeDtypeStruct((3, 2, 128), jnp.bfloat16)
                 for _ in range(2))
    ints = jax.ShapeDtypeStruct((3,), jnp.int32)
    live = jax.ShapeDtypeStruct((3,), bool)
    text = str(jax.make_jaxpr(functools.partial(
        pd._write_token_rows, use_kernel=use_kernel))(
            pools, rows, ints, ints, live))
    assert ("token_rows_write" in text) is kernel
    assert ("scatter[" in text) is not kernel


def test_row_writer_refuses_rows_that_are_not_the_pools():
    pools = (jnp.zeros((2, 3, 64, 128), jnp.bfloat16),) * 2
    ints = jnp.zeros((4,), jnp.int32)
    with pytest.raises(ValueError, match="rows"):
        token_rows.token_rows_write(
            pools, (jnp.zeros((4, 2, 128), jnp.bfloat16),), ints, ints, ints,
            interpret=True)
    with pytest.raises(ValueError, match="rows"):
        token_rows.token_rows_write(
            pools[:1], (jnp.zeros((4, 3, 128), jnp.bfloat16),), ints, ints,
            ints, interpret=True)
