"""``ops/moe.py``'s grouped ("ragged") product on a share: the held
assignments are compacted to a block of ``_capacity`` rows before anything
as wide as the model is touched, and a routing that holds more than a block
runs the block again (PR 37). Float32 on the CPU, against ``_dense`` (every
held expert over every token) and against the uncompacted product, which is
what ``_ragged`` emits where the block would hold every assignment."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import moe

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
    """Blocks of whole 8-row tiles, so that 384 assignments are several."""
    monkeypatch.setattr(moe, "RAGGED_TILE", 8)


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
