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
