"""Paged KV cache correctness: paged prefill/decode must match the dense
slotted path token-for-token (reference capability: vLLM PagedAttention,
here first-class in models/paged_decode.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import decode as dd
from ray_tpu.models import paged_decode as pd
from ray_tpu.models.llama import LlamaConfig, llama_init

PS = 16       # page size
BUCKET = 32   # prefill bucket (multiple of PS)
T = 6         # decode chunk


@pytest.fixture(scope="module")
def setup():
    cfg = LlamaConfig.tiny(dtype=jnp.float32, remat=None,
                           attention_impl="reference")
    params = llama_init(cfg, jax.random.key(0))
    return cfg, params


def _dense_generate(cfg, params, prompt, steps):
    cache = dd.init_kv_cache(cfg, 2, 64, dtype=jnp.float32)
    padded = np.zeros((1, BUCKET), np.int32)
    padded[0, :len(prompt)] = prompt
    logits, cache = dd.prefill(params, cache, jnp.asarray(padded),
                               jnp.int32(0), jnp.int32(len(prompt)), cfg)
    first = int(jnp.argmax(logits))
    dec = dd.make_decode_fn(cfg, steps, 0.0)
    toks = jnp.zeros((2,), jnp.int32).at[0].set(first)
    pos = jnp.zeros((2,), jnp.int32).at[0].set(len(prompt))
    act = jnp.zeros((2,), bool).at[0].set(True)
    sampled, *_ = dec(params, cache, toks, pos, act, jax.random.key(1))
    return [first] + [int(t) for t in sampled[0]]


def _paged_generate(cfg, params, prompt, steps, num_slots=2, total_pages=9):
    cache = pd.init_paged_cache(cfg, total_pages, PS, dtype=jnp.float32)
    alloc = pd.PageAllocator(total_pages)
    pages = alloc.alloc(4)
    assert pd.PageAllocator.TRASH_PAGE not in pages
    padded = np.zeros((1, BUCKET), np.int32)
    padded[0, :len(prompt)] = prompt
    logits, cache = pd.paged_prefill(
        params, cache, jnp.asarray(padded),
        jnp.asarray([pages[: BUCKET // PS]], jnp.int32),
        jnp.asarray([len(prompt)], jnp.int32), cfg, PS)
    first = int(jnp.argmax(logits[0]))
    table = np.zeros((num_slots, 4), np.int32)  # zeros = trash page
    table[0, : len(pages)] = pages
    dec = pd.make_paged_decode_fn(cfg, steps, PS, 0.0, use_kernel=False)
    toks = jnp.zeros((num_slots,), jnp.int32).at[0].set(first)
    pos = jnp.zeros((num_slots,), jnp.int32).at[0].set(len(prompt))
    act = jnp.zeros((num_slots,), bool).at[0].set(True)
    sampled, *_ = dec(params, cache, toks, pos, act, jnp.asarray(table),
                      jax.random.key(1))
    return [first] + [int(t) for t in sampled[0]]


def test_paged_matches_dense_greedy(setup):
    cfg, params = setup
    prompt = list(np.random.default_rng(0).integers(0, cfg.vocab_size, 13))
    dense = _dense_generate(cfg, params, prompt, T)
    paged = _paged_generate(cfg, params, prompt, T)
    assert paged == dense, (paged, dense)


def test_paged_crosses_page_boundary(setup):
    """Prompt of 13 + 6 tokens crosses the 16-row page boundary; a second
    chunk crosses into page 2."""
    cfg, params = setup
    prompt = list(np.random.default_rng(1).integers(0, cfg.vocab_size, 13))
    dense = _dense_generate(cfg, params, prompt, 24)
    paged = _paged_generate(cfg, params, prompt, 24)
    assert paged == dense


def test_inactive_slots_never_corrupt_live_pages(setup):
    """An inactive slot's frozen-position writes land in the trash page,
    not in a live slot's page 0 (the bug the trash page exists for)."""
    cfg, params = setup
    prompt = list(np.random.default_rng(2).integers(0, cfg.vocab_size, 9))
    # 7 slots, 6 of them inactive with zeroed table rows
    paged = _paged_generate(cfg, params, prompt, T, num_slots=7)
    dense = _dense_generate(cfg, params, prompt, T)
    assert paged == dense


def test_page_allocator_reserves_trash_and_recycles():
    a = pd.PageAllocator(8)
    assert a.free_pages == 7
    got = a.alloc(7)
    assert 0 not in got
    assert a.alloc(1) is None
    a.release(got[:3])
    assert a.free_pages == 3
    again = a.alloc(3)
    assert set(again) == set(got[:3])


# --------------------------------------------------------------------------- #
# One pool a side, [n_kv, L*P, ps, D]: layer l owns pages l*P .. l*P + P - 1
# --------------------------------------------------------------------------- #
P_TOTAL = 9  # pages a layer


def _touched(before, after):
    """{(page, row)} of the pool's rows that differ, over heads and D."""
    diff = np.asarray(before != after).any(axis=(0, 3))
    return {(int(p), int(r)) for p, r in zip(*np.nonzero(diff))}


def _decode_one_tick(cfg, params, table, positions):
    cache = pd.init_paged_cache(cfg, P_TOTAL, PS, dtype=jnp.float32)
    slots = table.shape[0]
    _, new = pd.paged_decode_one(
        params, cache, jnp.arange(1, slots + 1, dtype=jnp.int32),
        jnp.asarray(positions, jnp.int32), jnp.asarray(table, jnp.int32),
        cfg, PS, use_kernel=False)
    return cache, new


def _layer_block_case(cfg, params):
    """A live slot's token is written once a layer, at the same (page, row)
    of that layer's block, and nowhere else."""
    table = np.zeros((1, 4), np.int32)
    table[0] = [5, 2, 7, 3]
    old, new = _decode_one_tick(cfg, params, table, [PS + 3])  # page 2, row 3
    want = {(l * P_TOTAL + 2, 3) for l in range(cfg.num_layers)}
    assert cfg.num_layers >= 2
    assert _touched(old.k, new.k) == want
    assert _touched(old.v, new.v) == want


def _inactive_trash_case(cfg, params):
    """Slots with zeroed table rows write page l*P + 0 of each layer only;
    the live slot's pages hold its row and nothing of theirs."""
    table = np.zeros((5, 4), np.int32)
    table[2] = [4, 6, 1, 8]
    positions = [0, 7, 2 * PS + 1, 0, 21]  # slot 2 live: page 1, row 1
    old, new = _decode_one_tick(cfg, params, table, positions)
    for pool_old, pool_new in ((old.k, new.k), (old.v, new.v)):
        got = _touched(pool_old, pool_new)
        live = {(l * P_TOTAL + 1, 1) for l in range(cfg.num_layers)}
        trash = got - live
        assert live <= got
        assert trash and {p for p, _ in trash} <= {
            l * P_TOTAL for l in range(cfg.num_layers)}
        assert {r for _, r in trash} == {0, 7, 21 % PS}


def _interleaved_case(cfg, params):
    """Two slots whose page ids interleave (1,3,5,7 / 2,4,6,8): prefill then
    decode through the gather path gives each the dense cache's tokens."""
    rng = np.random.default_rng(3)
    prompts = [list(rng.integers(0, cfg.vocab_size, 13)),
               list(rng.integers(0, cfg.vocab_size, 21))]
    owned = [[1, 3, 5, 7], [2, 4, 6, 8]]
    cache = pd.init_paged_cache(cfg, P_TOTAL, PS, dtype=jnp.float32)
    padded = np.zeros((2, BUCKET), np.int32)
    for b, prompt in enumerate(prompts):
        padded[b, :len(prompt)] = prompt
    logits, cache = pd.paged_prefill(
        params, cache, jnp.asarray(padded),
        jnp.asarray([pages[: BUCKET // PS] for pages in owned], jnp.int32),
        jnp.asarray([len(p) for p in prompts], jnp.int32), cfg, PS)
    first = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    dec = pd.make_paged_decode_fn(cfg, T, PS, 0.0, use_kernel=False)
    sampled, *_ = dec(params, cache, first,
                      jnp.asarray([len(p) for p in prompts], jnp.int32),
                      jnp.ones((2,), bool), jnp.asarray(owned, jnp.int32),
                      jax.random.key(1))
    for b, prompt in enumerate(prompts):
        got = [int(first[b])] + [int(t) for t in sampled[b]]
        assert got == _dense_generate(cfg, params, prompt, T), b


@pytest.mark.parametrize("case", [_layer_block_case, _inactive_trash_case,
                                  _interleaved_case],
                         ids=["layer_block", "inactive_trash", "interleaved"])
def test_one_pool_keeps_layers_and_slots_apart(setup, case):
    case(*setup)
