"""Flash-attention kernel correctness vs the jnp reference.

Run in pallas interpret mode on the CPU backend (the fake-TPU CI analogue);
matmul precision is forced to HIGHEST because the backend's default matmul
precision is bf16-like, which would swamp the comparison."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.attention import flash_attention, reference_attention
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.rope import apply_rope, rope_frequencies

CASES = [
    # (batch, seq, q_heads, kv_heads, head_dim, causal)
    (2, 256, 4, 2, 64, True),
    (1, 128, 8, 8, 32, True),
    (2, 256, 4, 4, 64, False),
    (1, 64, 2, 1, 128, True),
    (1, 200, 2, 2, 64, True),  # non-multiple of block -> pad path
]

# seqs that are NOT multiples of the (asymmetric) default blocks: the pad
# logic must find a COMMON q/k padding so these stay on the flash kernel
# (regression: minimal per-side padding used to kick them to the reference).
RAGGED_CASES = [(768, 256, 512), (640, 256, 512), (1100, 256, 512)]


@pytest.mark.parametrize("s,bq,bk", RAGGED_CASES)
def test_flash_common_padding_ragged_seq(s, bq, bk):
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.standard_normal((1, s, 4, 32)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, s, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, s, 2, 32)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        ref = reference_attention(q, k, v, causal=True)
        out = flash_attention(q, k, v, causal=True, interpret=True, block_q=bq, block_k=bk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("b,sq,hq,hkv,d,causal", CASES)
def test_flash_matches_reference(b, sq, hq, hkv, d, causal):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((b, sq, hq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, sq, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, sq, hkv, d)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        ref = reference_attention(q, k, v, causal=causal)
        out = flash_attention(q, k, v, causal=causal, interpret=True, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_gradient_flows(causal):
    """Causal, and not: there every block pair is admitted and none masked."""
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((1, 128, 2, 32)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 128, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 128, 2, 32)), jnp.float32)

    with jax.default_matmul_precision("highest"):

        def loss_flash(q, k, v):
            return flash_attention(q, k, v, causal=causal, interpret=True,
                                   block_q=64, block_k=64).sum()

        def loss_ref(q, k, v):
            return reference_attention(q, k, v, causal=causal).sum()

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5, rtol=2e-5)


def test_causal_masking_is_exact():
    """Future tokens must have exactly zero influence."""
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.standard_normal((1, 128, 2, 32)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 128, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 128, 2, 32)), jnp.float32)
    out1 = flash_attention(q, k, v, causal=True, interpret=True, block_q=64, block_k=64)
    # perturb the second half of k/v; first half of outputs must be unchanged
    k2 = k.at[:, 64:].add(100.0)
    v2 = v.at[:, 64:].add(-50.0)
    out2 = flash_attention(q, k2, v2, causal=True, interpret=True, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(out1[:, :64]), np.asarray(out2[:, :64]), atol=1e-6)


def test_rms_norm():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((4, 16, 64)), jnp.float32)
    w = jnp.ones((64,), jnp.float32) * 2.0
    y = rms_norm(x, w)
    expected = x / np.sqrt((np.asarray(x) ** 2).mean(-1, keepdims=True) + 1e-6) * 2.0
    np.testing.assert_allclose(np.asarray(y), expected, atol=1e-5, rtol=1e-5)


def test_rope_properties():
    cos, sin = rope_frequencies(64, 512)
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((2, 16, 4, 64)), jnp.float32)
    y = apply_rope(x, cos, sin)
    # norm-preserving per (pos, head)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(x), axis=-1),
        np.linalg.norm(np.asarray(y), axis=-1),
        rtol=1e-5,
    )
    # position 0 is identity
    np.testing.assert_allclose(np.asarray(y[:, 0]), np.asarray(x[:, 0]), atol=1e-6)
    # relative property: dot(q_m, k_n) depends only on m-n
    q = jnp.asarray(rng.standard_normal((1, 8, 1, 64)), jnp.float32)
    kk = jnp.asarray(np.tile(rng.standard_normal((1, 1, 1, 64)), (1, 8, 1, 1)), jnp.float32)
    qq = jnp.asarray(np.tile(rng.standard_normal((1, 1, 1, 64)), (1, 8, 1, 1)), jnp.float32)
    rq = np.asarray(apply_rope(qq, cos, sin))
    rk = np.asarray(apply_rope(kk, cos, sin))
    dots = [(rq[0, m, 0] * rk[0, m + 1, 0]).sum() for m in range(7)]
    np.testing.assert_allclose(dots, dots[0] * np.ones(7), rtol=1e-4)


def test_rope_with_positions():
    cos, sin = rope_frequencies(32, 128)
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((1, 4, 2, 32)), jnp.float32)
    pos = jnp.asarray([[10, 11, 12, 13]], jnp.int32)
    y1 = apply_rope(x, cos, sin, positions=pos)
    # same as embedding a length-14 sequence and slicing
    xx = jnp.pad(x, ((0, 0), (10, 0), (0, 0), (0, 0)))
    y2 = apply_rope(xx, cos, sin)[:, 10:]
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-5)


def test_flash_kv_cache_decode_shape():
    """sq != skv causal (cached prefix) — review regression: the kernel must
    offset query positions by skv-sq, not silently mis-mask."""
    rng = np.random.default_rng(6)
    q = jnp.asarray(rng.standard_normal((1, 128, 2, 32)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 256, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 256, 2, 32)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        ref = reference_attention(q, k, v, causal=True)
        out = flash_attention(q, k, v, causal=True, interpret=True, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_flash_causal_requires_kv_longer():
    q = jnp.zeros((1, 128, 2, 32), jnp.float32)
    k = jnp.zeros((1, 64, 2, 32), jnp.float32)
    with pytest.raises(ValueError, match="Skv >= Sq"):
        flash_attention(q, k, k, causal=True, interpret=True)


# (batch, sq, skv, q heads, kv heads, block_q, block_k): Mistral's kind of
# group; a group of 1 (Olmo-Hybrid's 30 / 30: a k block's pairs are one
# head's); a group of 8 on ONE KV head (Mellum's: every head's pairs of a k
# block sum into one scratch); keys that precede the queries (``offset`` =
# skv - sq moves every pair's mask and the first q block a k block meets); a
# row count the pad path grows (384 -> 512 at 256 / 512, the default blocks:
# two q blocks against one k block, the padded rows' dO zero). The backward's
# q block is twice the one asked for where the rows divide, so the first
# three walk ONE q block of 128 against two k blocks; the last two walk
# several: 256 rows in two q blocks of 128 against four k blocks of 64, and
# 192 rows, which twice 64 does not divide, in three of 64
GQA_GRAD_CASES = [(2, 128, 128, 4, 2, 64, 64), (1, 128, 128, 3, 3, 64, 64),
                  (1, 128, 128, 8, 1, 64, 64), (1, 64, 192, 4, 2, 64, 64),
                  (1, 128, 256, 2, 1, 64, 128), (1, 384, 384, 4, 2, 256, 512),
                  (1, 256, 256, 4, 2, 64, 64), (1, 192, 192, 2, 2, 64, 64)]


@pytest.mark.parametrize("b,sq,skv,hq,hkv,bq,bk", GQA_GRAD_CASES)
def test_flash_gradient_gqa_causal(b, sq, skv, hq, hkv, bq, bk):
    """The one backward pass under GQA: dk/dv are summed over the q-head
    group inside the kernel; compare against the reference vjp."""
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.standard_normal((b, sq, hq, 32)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, skv, hkv, 32)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, skv, hkv, 32)), jnp.float32)

    with jax.default_matmul_precision("highest"):

        def loss_flash(q, k, v):
            out = flash_attention(q, k, v, causal=True, interpret=True,
                                  block_q=bq, block_k=bk)
            return (out * out).sum()

        def loss_ref(q, k, v):
            out = reference_attention(q, k, v, causal=True)
            return (out * out).sum()

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("hq,hkv,window", [(8, 2, None), (8, 1, 40), (6, 3, None)])
def test_flash_dkv_of_a_kv_head_is_the_sum_over_its_groups_heads(hq, hkv, window):
    """dK and dV of a KV head against the float32 SUM, over the q heads of
    its group, of the reference's gradients with every q head given a K and V
    of its own: the kernel sums a group in VMEM and writes once. Every head's
    output is weighted by its own number, so a head summed onto the wrong KV
    head moves dK by more than the tolerance."""
    rng = np.random.default_rng(13)
    group = hq // hkv
    q = jnp.asarray(rng.standard_normal((1, 128, hq, 32)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 128, hkv, 32)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 128, hkv, 32)), jnp.float32)
    weight = jnp.arange(1, hq + 1, dtype=jnp.float32)[None, None, :, None]

    with jax.default_matmul_precision("highest"):
        dk, dv = jax.grad(
            lambda k, v: (flash_attention(
                q, k, v, interpret=True, block_q=64, block_k=64,
                window=window) * weight).sum(), argnums=(0, 1))(k, v)
        a_head = lambda x: jnp.repeat(x, group, axis=2)  # noqa: E731
        dk_h, dv_h = jax.grad(
            lambda k, v: (reference_attention(q, k, v, window=window)
                          * weight).sum(), argnums=(0, 1))(a_head(k), a_head(v))
    for got, per_head in ((dk, dk_h), (dv, dv_h)):
        want = per_head.reshape(1, 128, hkv, group, 32).sum(axis=3)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=5e-5, rtol=5e-5)


# (what the group's dQ may take of VMEM, in heads' worth; the parts a group of
# 4 is then walked in): a head's worth and under it, one head a grid step;
# two heads' worth, two parts of two; the group's whole, one part
PARTS_CASES = [(0, 4), (1, 4), (2, 2), (3, 2), (4, 1)]


@pytest.mark.parametrize("window", [None, 100])
@pytest.mark.parametrize("heads_worth,parts", PARTS_CASES)
def test_flash_backward_walks_a_group_in_parts_that_fit(monkeypatch, heads_worth,
                                                        parts, window):
    """A group whose dQ is over ``BWD_DQ_VMEM_BYTES`` is walked in equal
    parts, each a grid step's heads with a dQ of its own; a part writes its
    dK and dV in float32 and ``_flash_bwd`` sums the parts before the one
    cast. Whatever the parts, the gradients are the reference's: GQA 8 / 2,
    two q blocks against four k blocks, with a window and without."""
    import importlib

    # ``ray_tpu.ops`` names a function ``attention`` over its module
    attention = importlib.import_module("ray_tpu.ops.attention")
    rng = np.random.default_rng(17)
    s, d = 256, 32
    q = jnp.asarray(rng.standard_normal((1, s, 8, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, s, 2, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, s, 2, d)), jnp.float32)
    # what ``_flash_bwd`` counts for a head: dQ in float32 scratch and its
    # output block twice, and the blocks of q and dO (128 rows) twice
    head_vmem = d * (s * (4 + 2 * 4) + 4 * 128 * 4)
    monkeypatch.setattr(attention, "BWD_DQ_VMEM_BYTES", heads_worth * head_vmem)
    seen = []
    real = attention.pl.pallas_call
    monkeypatch.setattr(
        attention.pl, "pallas_call",
        lambda *a, **kw: seen.append(kw.get("grid_spec")) or real(*a, **kw))

    def grads(fn):
        return jax.grad(lambda q, k, v: (fn(q, k, v) ** 2).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    with jax.default_matmul_precision("highest"):
        got = grads(lambda q, k, v: flash_attention(
            q, k, v, interpret=True, block_q=64, block_k=64, window=window))
        want = grads(lambda q, k, v: reference_attention(q, k, v, window=window))
    # the backward's grid: (batch, KV heads x parts, admitted pairs)
    # (the forward's call states its grid without a specification)
    assert seen[0] is None and seen[1].grid[:2] == (1, 2 * parts), seen
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-5)


# (seq, window, block_q, block_k, q heads, kv heads, keys before the queries).
# At GQA 4 / 2: one block; a window that is no multiple of a block and spans
# several; blocks of unequal size (a k block's last q block and a q block's
# first k block are both inside the sequence); S < window (the window never
# bites: the full backward's answer); a sequence the pad path grows. Then
# Mellum's group of 8 under a window that spans blocks; a group of 1; keys
# that precede the queries, the oldest two k blocks of them older than every
# query's window (their dK and dV are zeros the kernel still has to write)
WINDOW_GRAD_CASES = [
    (64, 24, 64, 64, 4, 2, 0), (256, 100, 64, 64, 4, 2, 0),
    (512, 130, 64, 128, 4, 2, 0), (256, 96, 128, 64, 4, 2, 0),
    (128, 1024, 64, 64, 4, 2, 0), (200, 70, 64, 64, 4, 2, 0),
    (256, 100, 64, 64, 8, 1, 0), (256, 96, 128, 64, 3, 3, 0),
    (64, 40, 64, 64, 4, 2, 192), (128, 100, 64, 128, 8, 1, 128)]


@pytest.mark.parametrize("s,window,bq,bk,hq,hkv,before", WINDOW_GRAD_CASES)
def test_flash_window_gradient_matches_reference(s, window, bq, bk, hq, hkv,
                                                 before):
    """The backward under a sliding window (``flash_window_bwd``, which
    visits the block pairs the window admits and masks those on its edges)
    against ``reference_attention``'s gradient.
    Tolerance 5e-5: float32 throughout with ``highest`` products, so what is
    left is the order of the sums (the same bound as the full backward's
    test above); a block wrongly skipped or a mask off by one moves an entry
    by 1e-2 or more (the planted fault below)."""
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.standard_normal((2, s, hq, 32)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, before + s, hkv, 32)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, before + s, hkv, 32)), jnp.float32)

    def loss(fn, window):
        def f(q, k, v):
            out = fn(q, k, v, window)
            return (out * out).sum()
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    with jax.default_matmul_precision("highest"):
        flash = lambda q, k, v, w: flash_attention(  # noqa: E731
            q, k, v, causal=True, interpret=True, block_q=bq, block_k=bk,
            window=w)
        ref = lambda q, k, v, w: reference_attention(  # noqa: E731
            q, k, v, causal=True, window=w)
        got, want = loss(flash, window), loss(ref, window)
        off_by_one = loss(flash, window + 1)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-5)
    if window < s:
        worst = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(off_by_one, want))
        assert worst > 1e-2, worst
