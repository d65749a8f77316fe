"""``ops/gdn.py``: the gated delta rule's chunk kernels, forward and reverse,
against the recurrence a token a step (``gdn_recurrence``) and ``jax.grad`` of
it. Key 24 / value 48 are 96 / 192 scaled down by four: 0.75 and 1.5 of a tile
of 32, the ratios the published widths have to a tile of 128 (the 30-head case
runs at 12 / 24). The kernels run interpreted (``pallas_interpret``); the
module's own fallback (``reference``) is the recurrence itself and is held to
the same numbers.

Tolerances, float32 (measured when the test was written): the forward reads
2e-6 from the recurrence at outputs of 0.5-2 (the order of the sums, a chunk
at a time against a token at a time), the gradients 6e-5 at gradients of
3-70, both with ``beta`` in (1.9, 2) and ``g`` = -20 in some heads and 0 in
others. ``ATOL`` 2e-5 (forward) and ``RTOL`` 2e-5 of a gradient's largest
entry leave an order of room. Under bfloat16 inputs a head's output reads 0.3-0.4%
of its norm from the recurrence (one rounding of a result), and 1.2% (0.05 at
outputs of 3.3) in the heads where NOTHING decays and ``beta`` is near 2, over
three chunks: three orders outside the float32 bound, so a product taken in
the lower precision fails it."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import gdn

ATOL, RTOL = 2e-5, 2e-5
BF16_REL = 0.02   # a head's |got - want| / |want| under bfloat16 inputs
IMPLS = ("pallas_interpret", "reference")


def _inputs(b, s, h, dk, dv, seed, dtype=jnp.float32, hard=True):
    """q scaled and k normalised as the model hands them over; with ``hard``,
    ``beta`` in (1.9, 2) everywhere, ``g`` = -20 in every third head (the
    state is gone in a token), 0 in the next (nothing ever decays)."""
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((b, s, h, dk))
    k = rng.standard_normal((b, s, h, dk))
    q = q / np.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(dk)
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.standard_normal((b, s, h, dv))
    g = -np.exp(rng.standard_normal((b, s, h)) * 1.5 - 2.0)
    beta = 2.0 / (1.0 + np.exp(-rng.standard_normal((b, s, h))))
    if hard:
        beta = rng.uniform(1.9, 2.0, (b, s, h))
        g[:, :, 0::3] = -20.0
        g[:, :, 1::3] = 0.0
    return tuple(jnp.asarray(x, dtype) for x in (q, k, v)) + (
        jnp.asarray(g, jnp.float32), jnp.asarray(beta, jnp.float32))


CASES = {
    # whole chunks, a grid step of three heads
    "three_heads": dict(s=128, h=3),
    # rows that end inside a chunk: the padding decays nothing, writes nothing
    "ends_inside_a_chunk": dict(s=100, h=3),
    # a grid step of six heads in lockstep
    "six_heads": dict(s=128, h=6),
    # the published count: three grid steps of ten heads, one chunk
    "thirty_heads": dict(s=64, h=30, dk=12, dv=24),
    # less than a chunk, two rows of a batch
    "short_rows": dict(s=40, h=2, b=2),
}


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_chunked_forward_is_the_recurrence(impl, case):
    spec = CASES[case]
    args = _inputs(spec.get("b", 1), spec["s"], spec["h"], spec.get("dk", 24),
                   spec.get("dv", 48), seed=len(case))
    want = np.asarray(gdn.gdn_recurrence(*args))
    got = gdn.gdn_chunk(*args, impl=impl)
    assert got.shape == want.shape and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), want, atol=ATOL, rtol=0)
    assert np.abs(want).max() > 0.3


@pytest.mark.parametrize("case", ["three_heads", "ends_inside_a_chunk",
                                  "six_heads", "thirty_heads"])
def test_the_reverse_pass_is_the_gradient_of_the_recurrence(case):
    """All five operands: dq, dk, dv, dg, dbeta of a weighted sum of the
    outputs, the custom reverse pass (two kernels, the chunks' states
    recomputed) against ``jax.grad`` through the token-a-step scan."""
    spec = CASES[case]
    args = _inputs(1, spec["s"], spec["h"], spec.get("dk", 24),
                   spec.get("dv", 48), seed=len(case))
    weight = jnp.asarray(np.random.default_rng(9).standard_normal(
        (1, spec["s"], spec["h"], spec.get("dv", 48))), jnp.float32)

    def loss(fn, *a):
        return jnp.sum(fn(*a) * weight)

    want = jax.grad(functools.partial(loss, gdn.gdn_recurrence),
                    argnums=(0, 1, 2, 3, 4))(*args)
    got = jax.grad(functools.partial(
        loss, functools.partial(gdn.gdn_chunk, impl="pallas_interpret")),
        argnums=(0, 1, 2, 3, 4))(*args)
    for name, a, b in zip(("dq", "dk", "dv", "dg", "dbeta"), got, want):
        b = np.asarray(b)
        assert a.shape == b.shape and np.abs(b).max() > 0.1, name
        np.testing.assert_allclose(np.asarray(a), b, rtol=0,
                                   atol=RTOL * np.abs(b).max(), err_msg=name)


def test_bfloat16_inputs_read_what_bfloat16_allows_and_fail_the_float32_bound():
    """The kernel under bfloat16 inputs (bfloat16 into the MXU, the inverse's
    chain in three passes, float32 sums) against the recurrence on the same
    rounded inputs: within what the rounding of its products allows, with
    ``beta`` near 2 and repeated decays of 0 (the chain's headroom, measured
    here again and not inherited from ``ops/kda.py``'s ``beta`` < 1), and two
    orders outside the float32 tolerance."""
    args = _inputs(1, 192, 6, 24, 48, seed=5, dtype=jnp.bfloat16)
    want = np.asarray(gdn.gdn_recurrence(*args))
    got = np.asarray(gdn.gdn_chunk(*args, impl="pallas_interpret"),
                     np.float32)
    assert np.abs(got - want).max() > 50 * ATOL
    for h in range(6):
        rel = np.linalg.norm(got[:, :, h] - want[:, :, h]) \
            / np.linalg.norm(want[:, :, h])
        assert rel < BF16_REL, (h, rel)
    weight = jnp.ones(want.shape, jnp.float32)
    grads = jax.grad(lambda *a: jnp.sum(
        gdn.gdn_chunk(*a, impl="pallas_interpret").astype(jnp.float32)
        * weight), argnums=(0, 1, 2, 3, 4))(*args)
    wants = jax.grad(lambda *a: jnp.sum(gdn.gdn_recurrence(*a) * weight),
                     argnums=(0, 1, 2, 3, 4))(*args)
    for name, a, b in zip(("dq", "dk", "dv", "dg", "dbeta"), grads, wants):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        rel = np.linalg.norm(a - b) / np.linalg.norm(b)
        assert np.isfinite(a).all() and rel < 0.03, (name, rel)


def test_a_head_reads_the_same_bits_whatever_its_partners_hold():
    """Heads share a grid step and nothing else: head 0's outputs and
    gradients are the same BITS beside five other heads' real rows and beside
    zeros in their place."""
    args = _inputs(1, 128, 6, 24, 48, seed=3)
    alone = tuple(jnp.where(
        (jnp.arange(6) == 0).reshape((1, 1, 6) + (1,) * (a.ndim - 3)), a,
        jnp.zeros_like(a)) for a in args)
    weight = jnp.asarray(np.random.default_rng(4).standard_normal(
        (1, 128, 6, 48)), jnp.float32)

    def both(*a):
        def loss(*a):
            o = gdn.gdn_chunk(*a, impl="pallas_interpret")
            return jnp.sum(o[:, :, 0] * weight[:, :, 0]), o
        grads, o = jax.grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True)(*a)
        return [np.asarray(o[:, :, 0])] + [np.asarray(x[:, :, 0]) for x in grads]

    for with_partners, by_itself in zip(both(*args), both(*alone)):
        np.testing.assert_array_equal(with_partners, by_itself)


def test_repeated_keys_at_beta_two_do_not_grow():
    """Where every row of a chunk has the SAME key, ``beta`` = 2 and nothing
    decays, ``A`` is 2 on its whole strict lower triangle and its powers grow
    like 2^k binomials: a power series over 64 rows would lose every digit.
    The doubling of the diagonal blocks is substitution in another order and
    reads the recurrence to rounding."""
    rng = np.random.default_rng(0)
    key = rng.standard_normal(24)
    key = key / np.linalg.norm(key)
    k = jnp.asarray(np.broadcast_to(key, (1, 128, 2, 24)), jnp.float32)
    q = k / np.sqrt(24.0)
    v = jnp.asarray(rng.standard_normal((1, 128, 2, 48)), jnp.float32)
    g = jnp.zeros((1, 128, 2), jnp.float32)
    beta = jnp.full((1, 128, 2), 2.0, jnp.float32)
    want = np.asarray(gdn.gdn_recurrence(q, k, v, g, beta))
    got = np.asarray(gdn.gdn_chunk(q, k, v, g, beta, impl="pallas_interpret"))
    # the rule itself swings there (S k <- 2 v - S k a token): 1e-4 of the
    # largest output is float32's rounding over 128 such tokens
    assert np.abs(want).max() > 0.3
    np.testing.assert_allclose(got, want, atol=3e-4 * np.abs(want).max(),
                               rtol=0)


def test_an_unknown_impl_is_refused():
    args = _inputs(1, 64, 2, 24, 48, seed=1)
    with pytest.raises(ValueError, match="unknown"):
        gdn.gdn_chunk(*args, impl="jnp")
