"""The decode-attention kernel (``ops/paged_attention.py``) on the CPU, its
own code through Pallas's interpreter, against the gather reference
(``models/paged_decode.py`` ``_paged_attention_reference``) in float32: the
two differ in the order of their sums (blocks of pages against one softmax
over the whole table), a few float32 roundings.

What it must hold: a slot of length 0 costs nothing and yields exact zeros;
only pages that hold live rows are read; the heads come from the shapes
(32/8 as Mistral, 32/2 as the hybrid family, 4/4); and the decode programs
of both families, handed ``active``, still give an active slot the tokens
it got when every slot attended over ``position + 1`` rows."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import nemotron_h as nh
from ray_tpu.models import paged_decode as pd
from ray_tpu.models.llama import LlamaConfig, llama_init
from ray_tpu.ops.paged_attention import paged_attention

PS, D = 8, 128          # rows a page; the head dimension the kernel tiles
SLOT_PAGES = 8          # pages a slot's table row names
BLOCK = 2               # pages a compute block: a block boundary at 16 rows
TOL = 2e-5


def _case(lengths, nh_, nkv, seed=0, poison=False):
    """(q, k, v, table, lengths) with every live slot's pages scattered over
    the pool. ``poison``: every page that holds no live row (the trash page
    too) is NaN, so reading one shows in the output."""
    rng = np.random.default_rng(seed)
    nb = len(lengths)
    total = 1 + nb * SLOT_PAGES
    k = rng.standard_normal((nkv, total, PS, D)).astype(np.float32)
    v = rng.standard_normal((nkv, total, PS, D)).astype(np.float32)
    q = (rng.standard_normal((nb, nh_, D)) * D ** -0.5).astype(np.float32)
    table = np.zeros((nb, SLOT_PAGES), np.int32)
    pages = rng.permutation(np.arange(1, total))
    owned = np.zeros((total,), bool)
    for b, n in enumerate(lengths):
        mine = pages[b * SLOT_PAGES: b * SLOT_PAGES + -(-n // PS)]
        table[b, :len(mine)] = mine
        owned[mine] = True
    if poison:
        k[:, ~owned] = np.nan
        v[:, ~owned] = np.nan
    return tuple(jnp.asarray(x) for x in (
        q, k, v, table, np.asarray(lengths, np.int32)))


def _kernel(q, k, v, table, lengths):
    return paged_attention(q, k, v, lengths, table, pages_per_block=BLOCK,
                           interpret=True)


# 0: dead; 1: one row; 8 / 9: a page boundary; 16 / 17: a block boundary;
# 64: the whole table. Dead and live slots interleave.
LENGTHS = [0, 1, 0, 8, 9, 0, 0, 16, 17, 64, 0, 37]


@pytest.mark.parametrize("heads", [(32, 8), (32, 2), (4, 4)],
                         ids=["32over8", "32over2", "4over4"])
def test_kernel_equals_the_gather_reference(heads):
    q, k, v, table, lengths = _case(LENGTHS, *heads)
    got = np.asarray(_kernel(q, k, v, table, lengths))
    want = np.asarray(
        pd._paged_attention_reference(q, k, v, table, lengths, 1.0))
    assert np.isfinite(got).all()
    live = np.asarray(lengths) > 0
    assert np.abs(got[live] - want[live]).max() < TOL
    # a dead slot: exact zeros from both
    assert not got[~live].any() and not want[~live].any()


@pytest.mark.parametrize("lengths", [[0] * 5, [0, 0, 0, 0, 3], [5, 0, 0, 0, 0],
                                     [64] * 3],
                         ids=["all_dead", "last_live", "first_live", "full"])
def test_kernel_at_the_edges_of_the_batch(lengths):
    q, k, v, table, n = _case(lengths, 32, 8, seed=1)
    got = np.asarray(_kernel(q, k, v, table, n))
    want = np.asarray(pd._paged_attention_reference(q, k, v, table, n, 1.0))
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() < TOL


def test_kernel_reads_only_pages_that_hold_live_rows():
    """Every other page of the pool is NaN: the trash page, which a dead
    slot's table row names (a retired slot: inactive, its row zeros, its old
    position handed in as length 0), and the pages behind a live slot's last
    one. A masked column weighs an exact 0, and 0 x NaN is NaN: a page that
    was fetched would show."""
    q, k, v, table, lengths = _case(LENGTHS, 32, 8, seed=2, poison=True)
    got = np.asarray(_kernel(q, k, v, table, lengths))
    assert np.isfinite(got).all()
    clean = [jnp.nan_to_num(x) for x in (k, v)]
    want = np.asarray(pd._paged_attention_reference(
        q, *clean, table, lengths, 1.0))
    assert np.abs(got - want).max() < TOL


def test_kernel_in_bfloat16_is_the_reference_in_bfloat16():
    """The engine's dtypes: a bfloat16 pool and q, float32 sums."""
    q, k, v, table, lengths = _case(LENGTHS, 32, 8, seed=3)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    got = np.asarray(_kernel(q, k, v, table, lengths), np.float32)
    want = np.asarray(pd._paged_attention_reference(
        q, k, v, table, lengths, 1.0), np.float32)
    assert got.dtype == want.dtype and np.isfinite(got).all()
    assert np.abs(got - want).max() < 2e-2  # one bfloat16 rounding of O(1)


def test_a_decode_call_scales_q_and_takes_the_lengths_it_is_given():
    """``_paged_attention`` on the gather path: q scaled, a slot of length 0
    zeros. (The kernel path inside the compiled decode programs, and its
    name there: ``tests/test_chip_compile.py``.)"""
    q, k, v, table, lengths = _case([0, 12, 40], 4, 4, seed=4)
    scale = D ** -0.5
    got = pd._paged_attention(q[:, None], k, v, table, lengths, scale, False)
    want = _kernel(q * scale, k, v, table, lengths)
    assert not np.asarray(got[0]).any()
    assert np.abs(np.asarray(got[:, 0]) - np.asarray(want)).max() < TOL


# --------------------------------------------------------------------------- #
# The decode programs of both families, told what is dead
# --------------------------------------------------------------------------- #
def _llama_tokens(active_slots, stale):
    """Tokens a tiny Llama decodes for ``active_slots`` of 5; ``stale``:
    the other slots keep an old position (retired: inactive, table row
    zeros) and not 0."""
    cfg = LlamaConfig.tiny(dtype=jnp.float32, remat=None,
                           attention_impl="reference")
    params = llama_init(cfg, jax.random.key(0))
    ps, bucket, ticks, slots = 16, 32, 6, 5
    cache = pd.init_paged_cache(cfg, 1 + 4 * slots, ps, dtype=jnp.float32)
    table = np.zeros((slots, 4), np.int32)
    tokens = np.zeros((slots,), np.int32)
    positions = np.full((slots,), 29 if stale else 0, np.int32)
    active = np.zeros((slots,), bool)
    for slot in active_slots:
        prompt = np.random.default_rng(slot).integers(
            0, cfg.vocab_size, 9 + 3 * slot)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :len(prompt)] = prompt
        table[slot] = 1 + 4 * slot + np.arange(4)
        logits, cache, _ = pd.paged_prefill(
            params, cache, jnp.asarray(padded),
            jnp.asarray(table[slot:slot + 1, :bucket // ps]),
            jnp.asarray([len(prompt)], jnp.int32), cfg, ps)
        tokens[slot] = int(jnp.argmax(logits[0]))
        positions[slot], active[slot] = len(prompt), True
    dec = pd.make_paged_decode_fn(cfg, ticks, ps, 0.0, use_kernel=False)
    sampled, _, new_pos, _ = dec(params, cache, tokens, positions, active,
                                 table, jax.random.key(1))
    assert np.array_equal(np.asarray(new_pos),
                          positions + ticks * active)
    return np.asarray(sampled)


def test_llama_decode_gives_an_active_slot_the_same_tokens_beside_dead_ones():
    """Slots 1 and 3 decode the same tokens alone, beside never-used slots,
    and beside retired slots with stale positions: a dead slot attends over
    nothing, writes the trash page and changes no live slot's reply."""
    both = _llama_tokens([1, 3], stale=True)
    for slot in (1, 3):
        alone = _llama_tokens([slot], stale=False)
        assert np.array_equal(both[slot], alone[slot])


def test_hybrid_decode_holds_a_dead_slot_still_and_finite():
    """The hybrid family: an inactive slot's attention output is zeros, so
    its row stays finite through the expert and Mamba layers (its state is
    held by ``dt = 0``, which a NaN in ``x`` would defeat), and the active
    slots' logits do not depend on what the dead slot's position says."""
    config = nh.NemotronHConfig.tiny(dtype=jnp.float32,
                                     attention_impl="reference")
    params = jax.jit(lambda k: nh.init_params(config, k))(jax.random.key(7))
    page, slots = 16, 4
    rng = np.random.default_rng(6)
    prompts = {0: rng.integers(1, 256, 21), 2: rng.integers(1, 256, 5)}

    def run(stale_position):
        cache = nh.init_cache(config, slots, 33, page)
        tokens = np.zeros((4, 32), np.int32)
        pages = np.zeros((4, 2), np.int32)
        lengths = np.ones((4,), np.int32)
        rows = np.full((4,), slots, np.int32)
        table = np.zeros((slots, 8), np.int32)
        for r, (slot, p) in enumerate(prompts.items()):
            tokens[r, :len(p)] = p
            table[slot] = 1 + 8 * r + np.arange(8)
            pages[r], lengths[r], rows[r] = table[slot, :2], len(p), slot
        logits, cache = nh.make_paged_prefill_fn(config, page)(
            params, cache, tokens, pages, lengths, rows)
        first = np.zeros((slots,), np.int32)
        positions = np.full((slots,), stale_position, np.int32)
        active = np.zeros((slots,), bool)
        for r, (slot, p) in enumerate(prompts.items()):
            first[slot] = int(jnp.argmax(logits[r]))
            positions[slot], active[slot] = len(p), True
        before = np.asarray(cache.ssm)
        logits, cache, _ = nh.paged_decode_one(
            params, cache, first, positions, active, table, config, page,
            False)
        held = np.array_equal(np.asarray(cache.ssm)[:, [1, 3]],
                              before[:, [1, 3]])
        return np.asarray(logits), held

    fresh, held_fresh = run(0)
    stale, held_stale = run(57)
    assert np.isfinite(fresh).all() and np.isfinite(stale).all()
    assert held_fresh and held_stale
    assert np.array_equal(fresh[[0, 2]], stale[[0, 2]])
