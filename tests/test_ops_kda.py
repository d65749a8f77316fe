"""``ops/kda.py``: the chunked delta-rule kernel and its one-token update
against the recurrence written out literally here, in numpy and float64: the
state a head is ``S <- Diag(exp(a)) S; S <- S + beta k (v - S^T k)^T``, the
output ``S^T q``. The kernels run interpreted (``pallas_interpret``); the
module's own fallback (``reference``) is held to the same numbers."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import kda

IMPLS = ("pallas_interpret", "reference")


def _inputs(b, s, h, d, seed, low=-5.0, high=0.0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    q, k, v = (rng.standard_normal((b, s, h, d)) for _ in range(3))
    q = q / np.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(d)
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    a = rng.uniform(low, high, (b, s, h, d))
    beta = 1.0 / (1.0 + np.exp(-rng.standard_normal((b, s, h))))
    state0 = 0.1 * rng.standard_normal((b, h, d, d))
    return tuple(x.astype(dtype) for x in (q, k, v)) + (
        a.astype(np.float32), beta.astype(np.float32),
        state0.astype(np.float32))


def _literal(q, k, v, a, beta, state0, lengths):
    """-> (o [B, S, H, D], the state after each row's last real token,
    stored [d_v, d_k] as the module stores it), float64."""
    b, s, h, d = q.shape
    o = np.zeros((b, s, h, d))
    last = np.zeros((b, h, d, d))
    for i in range(b):
        for j in range(h):
            state = state0[i, j].astype(np.float64).T          # [d_k, d_v]
            for t in range(int(lengths[i])):
                kt = k[i, t, j].astype(np.float64)
                state = np.exp(a[i, t, j].astype(np.float64))[:, None] * state
                write = beta[i, t, j] * (v[i, t, j] - kt @ state)
                state = state + np.outer(kt, write)
                o[i, t, j] = q[i, t, j].astype(np.float64) @ state
            last[i, j] = state.T
    return o, last


def _close(got, want, atol):
    np.testing.assert_allclose(np.asarray(got, np.float64), want, atol=atol,
                               rtol=0)


CASES = {
    # rows shorter than the piece, one all padding; a non-zero start
    "ragged": dict(s=150, lengths=(150, 70, 0), low=-5.0, high=0.0),
    # the lower bound everywhere: a sub-chunk's pair decay spans e^-80
    "a_at_the_lower_bound": dict(s=128, lengths=(128, 100, 64), low=-5.0,
                                 high=-5.0),
    # next to no decay: the chunk's triangular system at its fullest
    "a_near_zero": dict(s=128, lengths=(128, 127, 1), low=-1e-3, high=0.0),
    # a grid step of eight heads: four pairs side by side, taken in lockstep
    "eight_heads_in_pairs": dict(s=128, lengths=(128, 90, 17), low=-5.0,
                                 high=0.0, heads=8),
    # a head count no grid step divides: a head a step, its partner absent
    "three_heads_unpaired": dict(s=128, lengths=(128, 64, 3), low=-5.0,
                                 high=0.0, heads=3),
    # a piece whose last real row lies inside a chunk, and inside a sub-chunk
    "ends_inside_a_chunk": dict(s=192, lengths=(130, 97, 71), low=-2.0,
                                high=0.0, heads=8),
}


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("case", sorted(CASES))
def test_kda_prefill_is_the_recurrence(impl, case):
    spec = CASES[case]
    lengths = np.asarray(spec["lengths"], np.int32)
    q, k, v, a, beta, state0 = _inputs(3, spec["s"], spec.get("heads", 2), 32,
                                       seed=len(case), low=spec["low"],
                                       high=spec["high"])
    want_o, want_state = _literal(q, k, v, a, beta, state0, lengths)
    o, state = kda.kda_prefill(*(jnp.asarray(x) for x in (
        q, k, v, a, beta, state0, lengths)), impl=impl)
    assert o.dtype == jnp.float32 and state.shape == state0.shape
    for i, n in enumerate(lengths):
        _close(o[i, :n], want_o[i, :n], 2e-5)
    _close(state, want_state, 2e-5)
    # a row of padding alone hands its state back as it came
    if 0 in lengths:
        assert np.array_equal(np.asarray(state[list(lengths).index(0)]),
                              state0[list(lengths).index(0)])


@pytest.mark.parametrize("impl", IMPLS)
def test_kda_prefill_two_pieces_resume_as_one_call(impl):
    """A prompt of 200 rows as one call, and as a piece of 128 rows whose
    state starts a piece of 128 that holds the other 72: the engine's walk."""
    q, k, v, a, beta, state0 = (jnp.asarray(x) for x in
                                _inputs(2, 256, 2, 128, seed=7))
    lengths = jnp.asarray([200, 131], jnp.int32)
    whole_o, whole_state = kda.kda_prefill(q, k, v, a, beta, state0, lengths,
                                           impl=impl)
    first = [t[:, :128] for t in (q, k, v, a, beta)]
    second = [t[:, 128:] for t in (q, k, v, a, beta)]
    o1, mid = kda.kda_prefill(*first, state0, jnp.minimum(lengths, 128),
                              impl=impl)
    o2, end = kda.kda_prefill(*second, mid, jnp.maximum(lengths - 128, 0),
                              impl=impl)
    both = jnp.concatenate([o1, o2], axis=1)
    for i, n in enumerate((200, 131)):
        _close(both[i, :n], np.asarray(whole_o[i, :n], np.float64), 1e-5)
    _close(end, np.asarray(whole_state, np.float64), 1e-5)


@pytest.mark.parametrize("keys", ["apart", "alike_and_no_decay"])
def test_kda_prefill_in_bfloat16_stays_within_its_rounding(keys):
    """bfloat16 q, k, v multiply as bfloat16 (float32 sums, the inverse in
    three-pass products, a float32 state): the outputs stay within a
    bfloat16's rounding of the float64 recurrence over the same rounded
    inputs, also where every key leans on the first and nothing decays, so
    that the triangular system's terms cancel (one bfloat16 pass over the
    inverse's chain read 9% off there)."""
    alike = keys == "alike_and_no_decay"
    q, k, v, a, beta, state0 = _inputs(1, 192, 2, 128, seed=3,
                                       low=-0.01 if alike else -5.0)
    if alike:
        k = 0.3 * k + k[:, :1]
        k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    lengths = np.asarray([192], np.int32)
    low = [jnp.asarray(x, jnp.bfloat16) for x in (q, k, v)]
    want_o, want_state = _literal(
        *(np.asarray(x.astype(jnp.float32)) for x in low), a, beta, state0,
        lengths)
    o, state = kda.kda_prefill(*low, jnp.asarray(a), jnp.asarray(beta),
                               jnp.asarray(state0), jnp.asarray(lengths),
                               impl="pallas_interpret")
    assert o.dtype == jnp.bfloat16
    assert np.abs(np.asarray(o, np.float64) - want_o).max() \
        < 0.025 * np.abs(want_o).max()
    assert np.abs(np.asarray(state, np.float64) - want_state).max() \
        < 0.025 * np.abs(want_state).max()


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_kda_prefill_of_a_head_does_not_see_its_partner(dtype):
    """Two heads share each product of the chunk kernel, side by side
    against a block-diagonal operand. The zero blocks are exact: the even
    heads' outputs and states are the same BITS whatever the odd heads hold,
    also where the partner decays at the bound of -5 a row, so that its
    factors are the largest a zero block can meet (e^40 in a diagonal
    block)."""
    q, k, v, a, beta, state0 = _inputs(2, 192, 8, 128, seed=17)
    lengths = jnp.asarray([192, 150], jnp.int32)
    other = list(_inputs(2, 192, 8, 128, seed=19, low=-5.0, high=-5.0))
    other[:3] = [8.0 * x for x in other[:3]]

    def run(tensors):
        q, k, v, a, beta, state0 = tensors
        low = [jnp.asarray(x, dtype) for x in (q, k, v)]
        return kda.kda_prefill(*low, jnp.asarray(a), jnp.asarray(beta),
                               jnp.asarray(state0), lengths,
                               impl="pallas_interpret")

    o, state = run((q, k, v, a, beta, state0))
    swapped = []
    for mine, theirs in zip((q, k, v, a, beta, state0), other):
        mixed = mine.copy()
        odd = (slice(None), slice(1, None, 2)) if mine is state0 \
            else (slice(None), slice(None), slice(1, None, 2))
        mixed[odd] = theirs[odd]
        swapped.append(mixed)
    o2, state2 = run(swapped)
    assert np.array_equal(np.asarray(o[:, :, ::2].astype(jnp.float32)),
                          np.asarray(o2[:, :, ::2].astype(jnp.float32)))
    assert np.array_equal(np.asarray(state[:, ::2]), np.asarray(state2[:, ::2]))
    assert not np.array_equal(np.asarray(state[:, 1::2]),
                              np.asarray(state2[:, 1::2]))
    assert np.isfinite(np.asarray(o2.astype(jnp.float32))).all()


@pytest.mark.parametrize("impl", IMPLS)
def test_kda_step_is_one_step_of_the_recurrence(impl):
    """Layer 1 of a state of two layers, three live slots and a trash row:
    the live rows of that layer move, every other row is handed back as it
    was, and a slot given a = 0 and beta = 0 keeps its state."""
    q, k, v, a, beta, state0 = _inputs(3, 1, 8, 128, seed=11)
    a[2], beta[2] = 0.0, 0.0
    rng = np.random.default_rng(5)
    whole = rng.standard_normal((2, 4, 8, 128, 128)).astype(np.float32)
    whole[1, :3] = state0
    want_o, want_state = _literal(q, k, v, a, beta, state0, (1, 1, 1))
    o, moved = kda.kda_step(*(jnp.asarray(x[:, 0]) for x in (q, k, v, a, beta)),
                            jnp.asarray(whole), layer=1, impl=impl)
    _close(o, want_o[:, 0], 1e-5)
    _close(moved[1, :3], want_state, 1e-5)
    assert np.array_equal(np.asarray(moved[0]), whole[0])
    assert np.array_equal(np.asarray(moved[1, 3]), whole[1, 3])
    assert np.array_equal(np.asarray(moved[1, 2]), whole[1, 2])


def test_kda_64_steps_are_the_prefill_of_the_same_64_tokens():
    q, k, v, a, beta, state0 = (jnp.asarray(x) for x in
                                _inputs(2, 64, 8, 128, seed=13))
    o, state = kda.kda_prefill(q, k, v, a, beta, state0,
                               jnp.asarray([64, 64], jnp.int32),
                               impl="pallas_interpret")
    moved = jnp.zeros((1, 3, 8, 128, 128), jnp.float32).at[0, :2].set(state0)
    step = jax.jit(lambda *x: kda.kda_step(*x, impl="pallas_interpret"))
    outs = []
    for t in range(64):
        ot, moved = step(q[:, t], k[:, t], v[:, t], a[:, t], beta[:, t], moved)
        outs.append(ot)
    _close(jnp.stack(outs, axis=1), np.asarray(o, np.float64), 2e-5)
    _close(moved[0, :2], np.asarray(state, np.float64), 2e-5)


def test_kda_refuses_an_unknown_impl():
    with pytest.raises(ValueError, match="unknown kda impl"):
        kda.kda_prefill(*(jnp.asarray(x) for x in _inputs(1, 64, 1, 8, 0)),
                        jnp.asarray([64]), impl="mosaic")
