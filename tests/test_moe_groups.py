"""``ops/moe.py`` ``route`` with a group limit (``groups = (n_group,
topk_group)``) against the rule written out literally, and WITHOUT one against
the lines it ran before it knew groups, bit for bit."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import moe

T, H, R, K = 64, 32, 64, 8


def _router(seed):
    ks = jax.random.split(jax.random.key(seed), 3)
    x = jax.random.normal(ks[0], (T, H), jnp.float32)
    router = {"w": jax.random.normal(ks[1], (H, R), jnp.float32) * H ** -0.5,
              "bias": 0.05 * jax.random.normal(ks[2], (R,), jnp.float32)}
    return x, router


def _literal(x, router, top_k, scale, n_group, topk_group):
    """The group-limited rule a token at a time, in numpy."""
    scores = 1.0 / (1.0 + np.exp(-(np.asarray(x, np.float64)
                                    @ np.asarray(router["w"], np.float64))))
    choice = scores + np.asarray(router["bias"], np.float64)
    size = choice.shape[1] // n_group
    chosen, weights = [], []
    for s, c in zip(scores, choice):
        groups = c.reshape(n_group, size)
        group_score = np.sort(groups, axis=-1)[:, -2:].sum(axis=-1)
        kept = np.argsort(-group_score)[:topk_group]
        allowed = np.isin(np.arange(len(c)) // size, kept)
        best = np.argsort(-np.where(allowed, c, -np.inf))[:top_k]
        chosen.append(best)
        weights.append(s[best] / s[best].sum() * scale)
    return np.asarray(chosen), np.asarray(weights)


@pytest.mark.parametrize("groups", [(8, 4), (4, 2), (8, 1), (2, 2)])
def test_route_with_groups_is_the_literal_rule(groups):
    x, router = _router(1)
    chosen, weights = moe.route(x, router, K, 2.5, groups=groups)
    want, want_w = _literal(x, router, K, 2.5, *groups)
    # the same set (the order within equal scores is not the rule's)
    assert np.array_equal(np.sort(chosen, axis=-1), np.sort(want, axis=-1))
    order, want_order = np.argsort(chosen, axis=-1), np.argsort(want, axis=-1)
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(weights), order, -1),
        np.take_along_axis(want_w, want_order, -1), rtol=2e-6)
    assert np.all(np.isin(np.asarray(chosen) // (R // groups[0]),
                          np.arange(groups[0])))


def test_the_group_limit_changes_the_choice():
    """Seeded scores whose ungrouped top 8 spreads over more than 4 groups:
    the grouped top 8 differs, lies in 4 groups, and still sums to the
    scale."""
    x, router = _router(2)
    free, _ = moe.route(x, router, K, 2.5)
    held, weights = moe.route(x, router, K, 2.5, groups=(8, 4))
    differ = np.any(np.sort(free, -1) != np.sort(held, -1), axis=-1)
    assert differ.any()
    groups_used = [len(set((row // (R // 8)).tolist())) for row in np.asarray(held)]
    assert max(groups_used) <= 4
    assert max(len(set((row // (R // 8)).tolist()))
               for row in np.asarray(free)) > 4
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 2.5, rtol=1e-6)


def _route_before_groups(x, router, top_k, scale, scoring):
    """``route`` as it stood before it took ``groups`` (PR 51's tree)."""
    logits = jnp.matmul(x.astype(jnp.float32), router["w"].astype(jnp.float32),
                        precision="highest")
    if scoring == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
        weights, chosen = jax.lax.top_k(scores, top_k)
    else:
        scores = jax.nn.sigmoid(logits)
        _, chosen = jax.lax.top_k(scores + router["bias"].astype(jnp.float32),
                                  top_k)
        weights = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True) * scale
    return chosen.astype(jnp.int32), weights


@pytest.mark.parametrize("scoring", ["sigmoid_bias", "softmax"])
@pytest.mark.parametrize("groups", [None, (1, 1)])
def test_route_without_groups_is_bit_equal_to_what_it_was(scoring, groups):
    x, router = _router(3)
    got = moe.route(x, router, K, 2.5, scoring, groups)
    want = _route_before_groups(x, router, K, 2.5, scoring)
    for a, b in zip(got, want):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # and the traced program is the same text
    def text(fn):
        return jax.jit(fn).lower(x, router).as_text()
    assert text(lambda x, r: moe.route(x, r, K, 2.5, scoring, groups)) \
        == text(lambda x, r: _route_before_groups(x, r, K, 2.5, scoring))
