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
