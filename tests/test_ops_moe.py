"""``ops/moe.py``'s grouped ("ragged") product on a share: the held
assignments are compacted to a block of ``_capacity`` rows before anything
as wide as the model is touched, and a routing that holds more than a block
runs the block again (PR 37). Float32 on the CPU, against ``_dense`` (every
held expert over every token) and against the uncompacted product, which is
what ``_ragged`` emits where the block would hold every assignment.

Since PR 47 every grouped product is ``ops/grouped_matmul.py``'s Pallas
kernel, here in interpret mode (which hands out NaN for what a kernel never
wrote): the tests above it run over it unchanged, and its three entry points
are held to ``lax.ragged_dot`` / ``ragged_dot_general`` on their own.

Since PR 48 a block's rows reach their tokens through
``ops/rows_to_tokens.py``'s kernel, forward and reverse, interpreted here
too: the tests above run over it unchanged, and the kernel alone is held to
the row scatter-add it replaced."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import grouped_matmul as gm
from ray_tpu.ops import moe
from ray_tpu.ops import rows_to_tokens as rt

T, K, H, F, R = 96, 4, 16, 8, 16


def _layer(form, scoring, n, key=3):
    keys = jax.random.split(jax.random.key(key), 6)
    router = {"w": jax.random.normal(keys[0], (H, R), jnp.float32)}
    if scoring == "sigmoid_bias":
        router["bias"] = 0.1 * jax.random.normal(keys[1], (R,), jnp.float32)
    experts = {"w_up": 0.2 * jax.random.normal(keys[2], (n, H, F)),
               "w_down": 0.2 * jax.random.normal(keys[3], (n, F, H))}
    if form == "swiglu":
        experts["w_gate"] = 0.2 * jax.random.normal(keys[4], (n, H, F))
    return router, experts, jax.random.normal(keys[5], (T, H), jnp.float32)


def _run(x, router, experts, n, impl, form, scoring, counted=True, lo=0):
    return moe.routed_experts(
        x, router, experts, held=(lo, lo + n), top_k=K, scale=2.5, impl=impl,
        scoring=scoring, form=form,
        counted=jnp.ones((x.shape[0],), bool) if counted else None)


@pytest.fixture
def small_tiles(monkeypatch):
    """Blocks of whole 8-row tiles, so that 384 assignments are several; the
    grouped product's row tiles 32 rows and its pieces 16, so that a block is
    several tiles and an expert's rows several pieces; the combine's row
    tiles 32 rows too, and its result in blocks of 128 columns of up to 96
    tokens."""
    monkeypatch.setattr(moe, "RAGGED_TILE", 8)
    monkeypatch.setattr(rt, "ROW_TILE", 32)
    monkeypatch.setattr(rt, "RESULT_BLOCK_BYTES", 96 * 128 * 4)
    monkeypatch.setattr(gm, "ROW_TILE", 32)
    monkeypatch.setattr(gm, "DOT_PIECE", 16)
    monkeypatch.setattr(gm, "OUTER_PIECE", 16)


def test_capacity_is_the_expected_share_with_slack_in_whole_tiles():
    # Laguna's prefill chunk, its decode tick, Nemotron-H's prefill
    assert moe._capacity(4096 * 8, 32, 256) == 8192
    assert moe._capacity(24 * 8, 32, 256) == 512 >= 24 * 8
    assert moe._capacity(2048 * 6, 64, 128) == 2048 * 6  # every assignment
    assert moe._capacity(100 * 6, 64, 128) == 1024 >= 100 * 6


@pytest.mark.parametrize("n", [2, 8, 16], ids=["eighth", "half", "whole"])
@pytest.mark.parametrize("scoring", ["softmax", "sigmoid_bias"])
@pytest.mark.parametrize("form", ["relu2", "swiglu"])
def test_compacted_equals_dense_and_uncompacted(small_tiles, monkeypatch, form,
                                                scoring, n):
    router, experts, x = _layer(form, scoring, n)
    compacted = moe._capacity(T * K, n, R) < T * K
    assert compacted == (n == 2)  # a half share's block holds every assignment
    got, counts = _run(x, router, experts, n, "ragged", form, scoring)
    dense, dense_counts = _run(x, router, experts, n, "dense", form, scoring)
    np.testing.assert_allclose(got, dense, atol=1e-5)
    assert counts.shape == (6,) and dense_counts.shape == (4,)
    assert counts[:4].tolist() == dense_counts.tolist()
    assert counts[4] == int(compacted) and counts[5] == 0
    monkeypatch.setattr(moe, "COMPACT_SLACK", 1e9)  # the parent's product
    plain, plain_counts = _run(x, router, experts, n, "ragged", form, scoring)
    np.testing.assert_allclose(got, plain, atol=1e-5)
    assert plain_counts.tolist() == counts[:4].tolist() + [0, 0]


@pytest.mark.parametrize("form", ["relu2", "swiglu"])
def test_a_routing_that_leans_on_the_share_runs_more_blocks(small_tiles, form):
    """Router weights that send every token's every choice to the four held
    experts: 384 held assignments for a block of 192. Nothing is dropped."""
    router, experts, x = _layer(form, "softmax", 4)
    # an offset of +40 on the held experts' logits, -40 on the others
    x = x.at[:, 0].set(1.0)
    router = {"w": router["w"].at[0, :4].set(40.0).at[0, 4:].set(-40.0)}
    cap = moe._capacity(T * K, 4, R)
    assert cap == 192 < T * K
    got, counts = _run(x, router, experts, 4, "ragged", form, "softmax")
    dense, _ = _run(x, router, experts, 4, "dense", form, "softmax")
    assert counts[:2].tolist() == [T * K, T * K]  # every choice is held
    assert counts[4] == 1 and counts[5] == -(-T * K // cap) - 1 == 1
    np.testing.assert_allclose(got, dense, atol=1e-5)
    assert float(jnp.abs(got).max()) > 0.1


def test_a_share_no_token_chose_gives_zeros(small_tiles):
    router, experts, x = _layer("swiglu", "softmax", 2)
    x = x.at[:, 0].set(1.0)
    router = {"w": router["w"].at[0, :2].set(-40.0).at[0, 2:].set(40.0)}
    got, counts = _run(x, router, experts, 2, "ragged", "swiglu", "softmax")
    assert counts.tolist() == [T * K, 0, 0, 0, 1, 0]
    assert not np.any(np.asarray(got)) and got.shape == x.shape


def test_the_result_alone_without_counted(small_tiles):
    router, experts, x = _layer("swiglu", "softmax", 2)
    got = _run(x, router, experts, 2, "ragged", "swiglu", "softmax",
               counted=False)
    want, _ = _run(x, router, experts, 2, "ragged", "swiglu", "softmax")
    np.testing.assert_array_equal(got, want)


def test_compacted_at_the_real_tile_under_jit():
    """4,096 tokens' 32,768 choices over 2 of 16: a block of 8,192 rows at
    the real tile, inside ``lax.map`` as Laguna's prefill calls it."""
    router, experts, _ = _layer("swiglu", "softmax", 2, key=9)
    x = jax.random.normal(jax.random.key(10), (2, 4096, H), jnp.float32)
    assert moe._capacity(4096 * 8, 2, R) == 8192

    def layer(impl):
        def one(rows):
            out, counts = moe.routed_experts(
                rows, router, experts, held=(0, 2), top_k=8, scale=2.5,
                impl=impl, scoring="softmax", form="swiglu",
                counted=jnp.ones((rows.shape[0],), bool))
            return out, counts[4:] if impl == "ragged" else counts[:2]
        return jax.jit(lambda x: jax.lax.map(one, x))(x)

    got, blocks = layer("ragged")
    want, _ = layer("dense")
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert blocks.tolist() == [[1, 0], [1, 0]]


# --------------------------------------------------------------------------- #
# PR 46: reverse mode through the compacted product
# --------------------------------------------------------------------------- #
def _grads(x, router, experts, n, impl, form, scoring, lo=0):
    """d(sum of out * a fixed random tensor) / d(x, router, experts)."""
    probe = jax.random.normal(jax.random.key(77), x.shape, jnp.float32)

    def f(x, router, experts):
        out = _run(x, router, experts, n, impl, form, scoring, counted=False,
                   lo=lo)
        return jnp.sum(out * probe)

    return jax.grad(f, argnums=(0, 1, 2))(x, router, experts)


def _assert_trees_close(got, want, atol):
    flat_got, tree = jax.tree.flatten_with_path(got)
    flat_want, tree_want = jax.tree.flatten_with_path(want)
    assert tree == tree_want
    for (path, a), (_, b) in zip(flat_got, flat_want):
        # a gradient that says something; the correction bias moves the
        # choice alone, and the choice has no gradient
        assert float(jnp.abs(b).max()) > 1e-3 or "bias" in str(path), path
        np.testing.assert_allclose(a, b, atol=atol, err_msg=str(path))


@pytest.mark.parametrize("n,lo", [(4, 0), (2, 6), (4, 12)],
                         ids=["quarter", "eighth", "last-quarter"])
@pytest.mark.parametrize("scoring", ["softmax", "sigmoid_bias"])
@pytest.mark.parametrize("form", ["relu2", "swiglu"])
def test_compacted_gradient_equals_the_dense_forms(small_tiles, form, scoring,
                                                   n, lo):
    """``jax.grad`` through the compacted path (a ``custom_vjp`` over the
    block loop) against the gradient JAX derives of ``_dense``: to x, to the
    router (through the chosen weights and their normalisation) and to each
    held expert's matrices. Float32 on both sides; 2e-5 absolute is the order
    of the sums (the gradients' entries reach 0.1 to 10)."""
    router, experts, x = _layer(form, scoring, n)
    assert moe._capacity(T * K, n, R) < T * K
    got = _grads(x, router, experts, n, "ragged", form, scoring, lo)
    want = _grads(x, router, experts, n, "dense", form, scoring, lo)
    _assert_trees_close(got, want, 2e-5)


@pytest.mark.parametrize("form", ["relu2", "swiglu"])
def test_gradient_is_dropless_under_a_routing_that_overflows_a_block(
        small_tiles, form):
    """Every token's every choice on the four held experts: two blocks
    forward, two in reverse; a reverse pass that stopped after one block
    would leave half of the tokens' and experts' gradient out."""
    router, experts, x = _layer(form, "softmax", 4)
    x = x.at[:, 0].set(1.0)
    router = {"w": router["w"].at[0, :4].set(40.0).at[0, 4:].set(-40.0)}
    assert moe._capacity(T * K, 4, R) * 2 == T * K
    got = _grads(x, router, experts, 4, "ragged", form, "softmax")
    want = _grads(x, router, experts, 4, "dense", form, "softmax")
    _assert_trees_close(got, want, 2e-5)


def test_compacted_gradient_at_the_real_tile_under_jit():
    """2,048 tokens' 16,384 choices over 4 of 16 at the real tile: one block
    of 8,192 rows, the reverse pass compiled (``lax.while_loop`` in both
    directions)."""
    router, experts, _ = _layer("swiglu", "softmax", 4, key=9)
    x = jax.random.normal(jax.random.key(10), (2048, H), jnp.float32)
    assert moe._capacity(2048 * 8, 4, R) == 8192 < 2048 * 8

    def grads(impl):
        def f(x, router, experts):
            out = moe.routed_experts(
                x, router, experts, held=(0, 4), top_k=8, scale=1.0, impl=impl,
                scoring="softmax", form="swiglu")
            return jnp.sum(out * out)
        return jax.jit(jax.grad(f, argnums=(0, 1, 2)))(x, router, experts)

    _assert_trees_close(grads("ragged"), grads("dense"), 1e-4)


def test_gradient_of_a_share_no_token_chose_is_zero_and_finite(small_tiles):
    """A router that has walked away from this share (what training on data
    with nothing to learn does to one chip's share within 25 steps, PERF.md 6,
    PR 46): the reverse pass runs its first block over no group at all, and
    what flows back is zeros, not what the products left in unowned rows."""
    router, experts, x = _layer("swiglu", "softmax", 2)
    x = x.at[:, 0].set(1.0)
    router = {"w": router["w"].at[0, :2].set(-40.0).at[0, 2:].set(40.0)}
    got = _grads(x, router, experts, 2, "ragged", "swiglu", "softmax")
    for leaf in jax.tree.leaves(got):
        assert not np.any(np.asarray(leaf)), leaf


@pytest.mark.parametrize("n", [8, 16], ids=["half", "whole"])
@pytest.mark.parametrize("form", ["relu2", "swiglu"])
def test_uncompacted_gradient_equals_the_dense_forms(small_tiles, form, n):
    """A share of a half or more runs uncompacted, and the grouped product's
    own ``custom_vjp`` differentiates it. The rows past the held ones hold no
    number in interpret mode: nothing of them reaches a gradient."""
    router, experts, x = _layer(form, "softmax", n)
    assert moe._capacity(T * K, n, R) >= T * K
    got = _grads(x, router, experts, n, "ragged", form, "softmax")
    want = _grads(x, router, experts, n, "dense", form, "softmax")
    _assert_trees_close(got, want, 2e-5)


# --------------------------------------------------------------------------- #
# PR 47: the grouped product is one Pallas kernel with three entry points
# --------------------------------------------------------------------------- #
ROWS, A, B = 96, 24, 40
SIZES = {
    # 32-row tiles: the first group spans three, the last three share one
    "spans-tiles-and-shares-one": [75, 3, 2, 4],
    "sums-to-less-than-the-rows": [10, 5, 23, 5],
    "an-empty-group-in-the-middle": [12, 0, 0, 30, 7],
    "no-held-row-at-all": [0, 0, 0],
    "every-row-held": [32, 16, 48],
    "one-row-each": [1, 1, 1, 1, 1, 1],
}


def _outer_reference(rows, cot, sizes):
    return jax.lax.ragged_dot_general(
        rows, cot, sizes, jax.lax.RaggedDotDimensionNumbers(
            dot_dimension_numbers=(((0,), (0,)), ((), ())),
            lhs_ragged_dimensions=(0,), rhs_group_dimensions=()),
        preferred_element_type=jnp.float32)


def _operands(sizes, dtype=jnp.float32):
    keys = jax.random.split(jax.random.key(len(sizes)), 3)
    return (jax.random.normal(keys[0], (ROWS, A), dtype),
            jax.random.normal(keys[1], (ROWS, B), dtype),
            jax.random.normal(keys[2], (len(sizes), A, B), dtype),
            jnp.asarray(sizes, jnp.int32))


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 1e-5),
                                        (jnp.bfloat16, 1e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(SIZES))
def test_grouped_dot_is_ragged_dot_on_the_rows_of_the_groups(small_tiles, case,
                                                             dtype, atol):
    """Rows x matrices, and the same over the matrices' LAST dimension with
    no transposed copy, against ``lax.ragged_dot``: float32 results whatever
    the operands. What callers rely on past ``sum(sizes)``: NOTHING is
    written there (the interpreter's NaN stands), so they mask by ``where``;
    and a row that holds no number spoils no other row."""
    rows, cot, w, sizes = _operands(SIZES[case], dtype)
    held = int(sizes.sum())
    for got, want in [
            (gm.grouped_dot(rows, w, sizes),
             jax.lax.ragged_dot(rows, w, sizes,
                                preferred_element_type=jnp.float32)),
            (gm.grouped_dot(cot, w, sizes, True),
             jax.lax.ragged_dot(cot, jnp.swapaxes(w, 1, 2), sizes,
                                preferred_element_type=jnp.float32))]:
        assert got.dtype == jnp.float32 and got.shape == want.shape
        np.testing.assert_allclose(got[:held], want[:held], atol=atol)
        assert np.all(np.isnan(np.asarray(got[held:])))
    spoiled = rows.at[held:].set(jnp.nan)
    got = gm.grouped_dot(spoiled, w, sizes)
    assert np.all(np.isfinite(np.asarray(got[:held])))


@pytest.mark.parametrize("case", sorted(SIZES))
def test_grouped_outer_reads_only_its_experts_rows(small_tiles, case):
    """Each expert's matrix from ITS rows against ``ragged_dot_general`` over
    the ragged dimension (whose operands must be zeroed past the sum by
    hand): the kernel reads nothing there, on either operand, so what is not
    a number there reaches no result; an expert with no row gets zeros."""
    rows, cot, _, sizes = _operands(SIZES[case])
    valid = (jnp.arange(ROWS) < sizes.sum())[:, None]
    want = _outer_reference(jnp.where(valid, rows, 0), jnp.where(valid, cot, 0),
                            sizes)
    for r, c in [(jnp.where(valid, rows, jnp.nan), cot),
                 (rows, jnp.where(valid, cot, jnp.nan))]:
        got = gm.grouped_outer(r, c, sizes)
        assert got.dtype == jnp.float32
        np.testing.assert_allclose(got, want, atol=1e-5)
    empty = np.asarray(sizes) == 0
    assert not np.any(np.asarray(got)[empty])


@pytest.mark.parametrize("transposed", [False, True], ids=["plain", "last-dim"])
def test_grouped_dot_differentiates_through_its_other_entry_points(
        small_tiles, transposed):
    rows, cot, w, sizes = _operands(SIZES["sums-to-less-than-the-rows"])
    valid = (jnp.arange(ROWS) < sizes.sum())[:, None]
    x = cot if transposed else rows

    def loss(product):
        return lambda x, w: jnp.sum(jnp.where(valid, product(x, w), 0.0) ** 2)

    got = jax.grad(loss(lambda x, w: gm.grouped_dot(x, w, sizes, transposed)),
                   argnums=(0, 1))(x, w)
    want = jax.grad(loss(lambda x, w: jax.lax.ragged_dot(
        x, jnp.swapaxes(w, 1, 2) if transposed else w, sizes)),
        argnums=(0, 1))(x, w)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-4)


def test_rows_that_are_no_multiple_of_a_tile_and_one_short_tile(monkeypatch):
    """50 rows under 32-row tiles are padded to 64; 40 rows under the real
    512-row tile are one tile of 48 (a decode tick's call)."""
    rows, _, w, _ = _operands([0] * 4)
    sizes = jnp.asarray([7, 0, 20, 11], jnp.int32)
    want = jax.lax.ragged_dot(rows[:50], w, sizes)
    got = gm.grouped_dot(rows[:40], w, jnp.asarray([7, 0, 20, 11], jnp.int32))
    np.testing.assert_allclose(got[:38], want[:38], atol=1e-5)
    assert got.shape == (40, B)
    monkeypatch.setattr(gm, "ROW_TILE", 32)
    got = gm.grouped_dot(rows[:50], w, sizes)
    np.testing.assert_allclose(got[:38], want[:38], atol=1e-5)
    assert got.shape == (50, B)
    outer = gm.grouped_outer(rows[:50], want.at[38:].set(jnp.nan), sizes)
    np.testing.assert_allclose(
        outer, _outer_reference(rows[:50], want.at[38:].set(0.0), sizes),
        atol=1e-4)


def test_the_plan_visits_the_tiles_that_hold_rows_and_no_other():
    """16,384 rows holding 8,500 under 512-row tiles: 17 tiles hold a row,
    and the visits are those plus one for each expert boundary inside a
    tile; the steps past the plan's end repeat its last."""
    sizes = jnp.asarray([500] * 15 + [1000], jnp.int32)
    group, tile, starts, ends, total = gm._visits(sizes, 16384, 512, False)
    assert group.shape == tile.shape == (32 + 16 - 1,)
    n = int(total[0])
    assert int(tile[n - 1]) == 16 and int(tile.max()) == 16  # 8,500 rows
    pairs = {(int(g), int(t)) for g, t in zip(group[:n], tile[:n])}
    want = {(g, t) for g in range(16)
            for t in range(int(starts[g]) // 512, (int(ends[g]) - 1) // 512 + 1)}
    assert pairs == want and len(pairs) == n == 17 + 15
    assert group[n:].tolist() == [15] * (47 - n)
    assert np.all(np.diff(np.asarray(tile)) >= 0)
    # an expert with no row: not visited by the products over rows, visited
    # once by the outer product (which has zeros to write there)
    sizes = jnp.asarray([600, 0, 0, 425], jnp.int32)
    assert int(gm._visits(sizes, 2048, 512, False)[4][0]) == 2 + 2
    group, _, _, _, total = gm._visits(sizes, 2048, 512, True)
    assert int(total[0]) == 6 and group[:6].tolist() == [0, 0, 1, 2, 3, 3]


# --------------------------------------------------------------------------- #
# PR 48: a block's rows onto their tokens (``ops/rows_to_tokens.py``)
# --------------------------------------------------------------------------- #
def _tokens_of(case, cap, t, rng):
    """int32 [cap]: the token each row of the block goes to, ``t`` nowhere."""
    held = cap * 5 // 8
    token = rng.integers(0, t, cap)
    if case == "top-k-rows-and-none":
        # token 3 holds K rows (one an expert, far apart in the block),
        # token 5 none
        token = np.where(np.isin(token, (3, 5)), 7, token)
        token[np.arange(K) * (held // K)] = 3
    if case == "no-held-row":
        held = 0
    if case == "nowhere-in-the-middle":
        token[::3] = t
    token[held:] = t
    return token.astype(np.int32)


# cap, t, h -> (rows of a tile, columns of a block of the result): 32 rows
# under the file's small tiles, 512 at the real tile
BLOCKS = {
    "nan-to-nowhere": (64, 24, 128, (32, 128)),
    "top-k-rows-and-none": (128, 24, 128, (32, 128)),
    "no-held-row": (64, 24, 128, (32, 128)),
    "nowhere-in-the-middle": (96, 40, 128, (32, 128)),
    "no-multiple-of-a-tile": (75, 21, 128, (32, 128)),      # 3 tiles, 21 padded
    "three-blocks-of-columns": (64, 96, 384, (32, 128)),
    "a-width-of-no-whole-lanes": (64, 24, 200, (32, 200)),  # one block, whole
    "the-real-tile-under-jit": (1200, 300, 256, (512, 256)),  # 3 tiles
}


@pytest.mark.parametrize("weighted,onto", [
    (False, None), (True, None), (True, "kept"), (False, "not-kept")],
    ids=["plain", "weighted", "onto-a-carry", "onto-a-carry-not-kept"])
@pytest.mark.parametrize("case", sorted(BLOCKS))
def test_rows_to_tokens_is_the_row_scatter_add(request, case, weighted, onto):
    """Against ``zeros.at[token].add(rows, mode="drop")``: every row to
    nowhere holds NaN, as the grouped product may leave it, and no NaN
    reaches a result; a token with ``K`` rows, a token with none, a block
    with no held row at all (zeros), ``cap`` and ``t`` that are no multiple
    of a tile, a result walked in blocks of columns, a width that is no
    multiple of 128 (one block as wide as it is). Onto a carry: what it
    holds is added where it is kept, and is never read (NaN here) where it
    is not."""
    cap, t, h, tiling = BLOCKS[case]
    small = tiling[0] == 32
    if small:
        request.getfixturevalue("small_tiles")
    assert rt._tiling(cap, t, h, rt.ROW_TILE, rt.ROWS_A_TRIP,
                      rt.RESULT_BLOCK_BYTES) == tiling
    rng = np.random.default_rng(sorted(BLOCKS).index(case))
    token = _tokens_of(case, cap, t, rng)
    rows = rng.standard_normal((cap, h)).astype(np.float32)
    rows[token == t] = np.nan
    factor = rng.standard_normal(cap).astype(np.float32) if weighted else None
    weighed = rows * factor[:, None] if weighted else rows
    want = jnp.zeros((t, h), jnp.float32).at[token].add(weighed, mode="drop")
    carry = None
    if onto == "kept":
        sums = rng.standard_normal((t, h)).astype(np.float32)
        carry, want = (jnp.asarray(sums), jnp.asarray(True)), want + sums
    elif onto:
        carry = (jnp.full((t, h), jnp.nan, jnp.float32), jnp.asarray(False))
    run = rt.rows_to_tokens if small else jax.jit(
        rt.rows_to_tokens, static_argnums=2)
    got = run(jnp.asarray(rows), jnp.asarray(token), t,
              None if factor is None else jnp.asarray(factor), carry)
    assert got.shape == (t, h) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=1e-5)
    counts = np.bincount(token, minlength=t + 1)[:t]
    if onto != "kept":
        assert not np.any(np.asarray(got)[counts == 0])  # exact zeros
    if case == "top-k-rows-and-none":
        assert counts[3] == K and counts[5] == 0
    if case == "no-held-row":
        assert not counts.any()


def test_rows_to_tokens_sums_float32_rows_only():
    with pytest.raises(ValueError, match="float32 rows"):
        rt.rows_to_tokens(jnp.zeros((8, 128), jnp.bfloat16),
                          jnp.zeros((8,), jnp.int32), 4)
