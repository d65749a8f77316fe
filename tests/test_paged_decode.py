"""Paged KV cache correctness: prefill and decode through the page pool give,
token for token, what greedy decoding by full recompute gives (reference
capability: vLLM PagedAttention, here first-class in models/paged_decode.py).
The oracle is ``llama_forward`` at float32, which shares no projection, MLP
or head code with the programs under test."""

import concurrent.futures as cf
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import paged_decode as pd
from ray_tpu.models.llama import LlamaConfig, llama_forward, llama_init

PS = 16       # page size
BUCKET = 32   # prefill bucket (multiple of PS)
T = 6         # decode chunk


@pytest.fixture(scope="module")
def setup():
    cfg = LlamaConfig.tiny(dtype=jnp.float32, remat=None,
                           attention_impl="reference")
    params = llama_init(cfg, jax.random.key(0))
    return cfg, params


def _assert_greedy_of_full_forward(cfg, params, prompt, got, max_gap=0.0):
    """``got`` is greedy decoding of ``prompt`` by full recompute: one
    forward over prompt + got, teacher-forced, must choose got[i] after
    prompt + got[:i] at every i (by induction that IS the recompute loop).
    With a float32 pool there is no tolerance: the oracle's two best logits
    are 0.018 apart at the closest step of those prompts, float32 rounding
    is 1e-6. The engine keeps K/V in bfloat16, which decides a near-tie its
    own way (two logits 0.0015 apart in these replies): there the oracle's
    largest logit may pass the chosen token's by ``max_gap``, as in the
    benchmark's ``correct``; a wrong token is 0.1 or more below."""
    seq = [int(t) for t in prompt] + got[:-1]
    logits = np.asarray(
        llama_forward(params, jnp.asarray([seq], jnp.int32), cfg)
    )[0, len(prompt) - 1:]
    gaps = logits.max(axis=-1) - logits[np.arange(len(got)), got]
    assert gaps.max() <= max_gap, (got, logits.argmax(axis=-1).tolist(), gaps)


ENGINE_GAP = 0.01  # a bfloat16 pool under a float32 oracle


def _paged_generate(cfg, params, prompt, steps, num_slots=2, total_pages=9):
    cache = pd.init_paged_cache(cfg, total_pages, PS, dtype=jnp.float32)
    alloc = pd.PageAllocator(total_pages)
    pages = alloc.alloc(4)
    assert pd.PageAllocator.TRASH_PAGE not in pages
    padded = np.zeros((1, BUCKET), np.int32)
    padded[0, :len(prompt)] = prompt
    logits, cache, _ = pd.paged_prefill(
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


def test_paged_matches_full_forward_greedy(setup):
    cfg, params = setup
    prompt = list(np.random.default_rng(0).integers(0, cfg.vocab_size, 13))
    paged = _paged_generate(cfg, params, prompt, T)
    assert len(paged) == 1 + T
    _assert_greedy_of_full_forward(cfg, params, prompt, paged)


def test_paged_crosses_page_boundary(setup):
    """Prompt of 13 + 6 tokens crosses the 16-row page boundary; a second
    chunk crosses into page 2."""
    cfg, params = setup
    prompt = list(np.random.default_rng(1).integers(0, cfg.vocab_size, 13))
    paged = _paged_generate(cfg, params, prompt, 24)
    assert len(paged) == 25
    _assert_greedy_of_full_forward(cfg, params, prompt, paged)


def test_inactive_slots_never_corrupt_live_pages(setup):
    """An inactive slot's frozen-position writes land in the trash page,
    not in a live slot's page 0 (the bug the trash page exists for)."""
    cfg, params = setup
    prompt = list(np.random.default_rng(2).integers(0, cfg.vocab_size, 9))
    # 7 slots, 6 of them inactive with zeroed table rows
    paged = _paged_generate(cfg, params, prompt, T, num_slots=7)
    _assert_greedy_of_full_forward(cfg, params, prompt, paged)


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
        jnp.asarray(positions, jnp.int32),
        jnp.asarray(table.any(axis=1)),  # a slot with pages is live
        jnp.asarray(table, jnp.int32), cfg, PS, use_kernel=False)
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
    decode through the gather path gives each the full forward's tokens."""
    rng = np.random.default_rng(3)
    prompts = [list(rng.integers(0, cfg.vocab_size, 13)),
               list(rng.integers(0, cfg.vocab_size, 21))]
    owned = [[1, 3, 5, 7], [2, 4, 6, 8]]
    cache = pd.init_paged_cache(cfg, P_TOTAL, PS, dtype=jnp.float32)
    padded = np.zeros((2, BUCKET), np.int32)
    for b, prompt in enumerate(prompts):
        padded[b, :len(prompt)] = prompt
    logits, cache, _ = pd.paged_prefill(
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
        _assert_greedy_of_full_forward(cfg, params, prompt, got)


@pytest.mark.parametrize("case", [_layer_block_case, _inactive_trash_case,
                                  _interleaved_case],
                         ids=["layer_block", "inactive_trash", "interleaved"])
def test_one_pool_keeps_layers_and_slots_apart(setup, case):
    case(*setup)


# --------------------------------------------------------------------------- #
# A prefill call costs the rows its prompts have, not the rows its bucket has
# --------------------------------------------------------------------------- #
PIECE = 16    # ``pd.PREFILL_PIECE`` in these tests: a bucket of 64 is 4 pieces
LONG = 64


def _prefill_group(cfg, params, lengths, piece, monkeypatch):
    """One prefill call over a bucket of ``LONG``: a prompt a length, then a
    pad row (length 1, the trash page), at ``PREFILL_PIECE`` = ``piece``.
    Returns (prompts, their pages, logits, cache, counters)."""
    monkeypatch.setattr(pd, "PREFILL_PIECE", piece)
    rng = np.random.default_rng(5)
    n_pages = LONG // PS
    tokens = np.zeros((len(lengths) + 1, LONG), np.int32)
    pages = np.zeros((len(lengths) + 1, n_pages), np.int32)
    prompts = []
    for row, n in enumerate(lengths):
        prompts.append(rng.integers(0, cfg.vocab_size, n))
        tokens[row, :n] = prompts[-1]
        pages[row] = 1 + row * n_pages + np.arange(n_pages)
    cache = pd.init_paged_cache(cfg, 1 + len(lengths) * n_pages, PS,
                                dtype=jnp.float32)
    logits, cache, counts = pd.paged_prefill(
        params, cache, jnp.asarray(tokens), jnp.asarray(pages),
        jnp.asarray(list(lengths) + [1], jnp.int32), cfg, PS)
    return prompts, pages, logits, cache, counts


@pytest.mark.parametrize("length", [1, PIECE - 1, PIECE, PIECE + 1, LONG])
def test_prefill_skips_the_pieces_past_a_prompt(setup, monkeypatch, length):
    """A group of mixed lengths in one bucket, with a pad row: every prompt's
    last-token logits are the full forward's, every LIVE row of its pages is
    what the whole-bucket computation (one piece: the bucket) leaves there,
    the pages past its last piece hold zeros, and ``prefill_rows_computed``
    is live pieces x piece rows over the call's rows, the pad row's one
    piece included."""
    cfg, params = setup
    lengths = (length, 40, 23)
    prompts, pages, logits, cache, counts = _prefill_group(
        cfg, params, lengths, PIECE, monkeypatch)
    *_, whole, whole_counts = _prefill_group(cfg, params, lengths, LONG,
                                             monkeypatch)
    per_layer = cache.k.shape[1] // cfg.num_layers
    for row, (n, prompt) in enumerate(zip(lengths, prompts)):
        want = np.asarray(llama_forward(
            params, jnp.asarray([prompt], jnp.int32), cfg))[0, n - 1]
        np.testing.assert_allclose(np.asarray(logits[row]), want, atol=2e-5)
        computed = -(-n // PIECE) * PIECE
        for layer in range(cfg.num_layers):
            mine = layer * per_layer + pages[row]
            for got, ref in ((cache.k, whole.k), (cache.v, whole.v)):
                got = np.asarray(got[:, mine]).reshape(got.shape[0], LONG, -1)
                ref = np.asarray(ref[:, mine]).reshape(got.shape)
                np.testing.assert_allclose(got[:, :n], ref[:, :n], atol=2e-6)
                # the bucket's padding past the last live piece: computed
                # there, never computed here
                assert ref[:, computed:].any() == (computed < LONG)
                assert not got[:, computed:].any()
    assert counts.tolist() == [sum(-(-n // PIECE) * PIECE
                                   for n in lengths + (1,))]
    assert whole_counts.tolist() == [(len(lengths) + 1) * LONG]


def test_engine_stats_carry_the_rows_prefill_computed(setup, monkeypatch):
    """``stats()["prefill_rows_computed"]``: the one-row bring-up call of the
    bucket (a pad row: one piece), then a prompt of 40 in a bucket of 64 at
    a piece of 16: 3 of its 4 pieces, beside the 64 tokens admission pads it
    to; the reply is the full forward's."""
    cfg, params = setup
    monkeypatch.setattr(pd, "PREFILL_PIECE", PIECE)
    engine = _engine(cfg, params, prefill_buckets=[LONG])
    try:
        prompt = [int(t) for t in
                  np.random.default_rng(6).integers(0, cfg.vocab_size, 40)]
        out = engine.generate(prompt, max_tokens=8, timeout=300)
        _assert_greedy_of_full_forward(
            cfg, params, prompt, out["tokens"], ENGINE_GAP)
        stats = engine.stats()
        assert stats["prefill_tokens_padded"] == LONG
        assert stats["prefill_rows_computed"] == PIECE + 3 * PIECE
    finally:
        engine.stop()


# --------------------------------------------------------------------------- #
# The pool through the engine: the one cache, the one admission
# --------------------------------------------------------------------------- #
def _engine(cfg, params, **kw):
    from ray_tpu.serve.llm import LLMEngine

    kw = {"num_slots": 2, "decode_chunk": 4, "max_seq_len": 128,
          "page_size": PS, "prefill_buckets": [PS], **kw}
    return LLMEngine(cfg, params, **kw)


def _engine_threads():
    return [t for t in threading.enumerate() if t.name == "llm-engine"]


def test_engine_has_no_dense_cache(setup):
    cfg, params = setup
    before = _engine_threads()
    with pytest.raises(ValueError, match="dense slot cache was removed"):
        _engine(cfg, params, paged=False)
    assert _engine_threads() == before
    named, default = _engine(cfg, params, paged=True), _engine(cfg, params)
    try:
        assert not hasattr(named, "paged")
        for attr in ("decode_attention", "total_pages", "pages_per_slot",
                     "prefill_buckets", "_prefill_rows"):
            assert getattr(named, attr) == getattr(default, attr), attr
        assert named.decode_attention == "gather"  # a CPU backend
        assert jax.tree.map(jnp.shape, named.cache) == \
            jax.tree.map(jnp.shape, default.cache)
        assert named.decode_program_text() == default.decode_program_text()
    finally:
        named.stop()
        default.stop()


def test_a_request_the_pool_cannot_hold_fails_alone(setup):
    """Three pages to hand out: a request that needs five is refused by
    name, and the engine goes on to answer the next."""
    cfg, params = setup
    engine = _engine(cfg, params, total_pages=4)
    try:
        prompt = [3, 14, 15, 92, 65, 35]
        with pytest.raises(ValueError, match="needs 5 KV pages but the pool has 3"):
            engine.generate(prompt, max_tokens=60, timeout=300)
        out = engine.generate(prompt, max_tokens=8, timeout=300)
        _assert_greedy_of_full_forward(
            cfg, params, prompt, out["tokens"], ENGINE_GAP)
        assert engine.stats()["admitted"] == 1
        assert engine.allocator.free_pages == 3
    finally:
        engine.stop()


def test_a_request_waits_at_the_head_for_pages(setup):
    """A pool that holds one request at a time (three pages, two a
    request): the second waits in the backlog, counted as queued, until the
    first retires, and both are answered, in order."""
    cfg, params = setup
    engine = _engine(cfg, params, total_pages=4, decode_chunk=2)
    prompts = [[3, 14, 15, 92, 65, 35], [2, 71, 82, 81, 82, 84]]
    done = []

    def ask(i):
        out = engine.generate(prompts[i], max_tokens=24, timeout=300)
        done.append(i)
        return out

    def wait_for(what):
        deadline = time.monotonic() + 120
        while not what():
            assert time.monotonic() < deadline, engine.stats()
            time.sleep(0.001)

    try:
        with cf.ThreadPoolExecutor(2) as pool:
            first = pool.submit(ask, 0)
            wait_for(lambda: engine.stats()["admitted"] == 1)
            second = pool.submit(ask, 1)
            wait_for(lambda: len(engine._admit_backlog) == 1)
            held = engine.stats()
            assert (held["queued"], held["active"], held["admitted"]) == (1, 1, 1)
            outs = [first.result(timeout=300), second.result(timeout=300)]
        assert done == [0, 1]
        for prompt, out in zip(prompts, outs):
            assert len(out["tokens"]) == 24
            _assert_greedy_of_full_forward(
                cfg, params, prompt, out["tokens"], ENGINE_GAP)
        after = engine.stats()
        assert (after["queued"], after["admitted"], after["retired"]) == (0, 2, 2)
        assert engine.allocator.free_pages == engine.total_pages - 1
    finally:
        engine.stop()


def test_a_prompt_over_the_largest_bucket_is_prefilled_whole(setup):
    """No configured bucket holds 40 tokens: the prompt is not cut to the
    largest (16) but given a bucket of its own, a page multiple that a
    slot's pages can hold, and the reply is the full forward's."""
    cfg, params = setup
    engine = _engine(cfg, params)
    try:
        assert engine.prefill_buckets == [PS]
        bucket = engine._bucket_for(40)
        assert bucket >= 40 and bucket % PS == 0
        assert bucket <= engine.pages_per_slot * PS
        prompt = [int(t) for t in
                  np.random.default_rng(4).integers(0, cfg.vocab_size, 40)]
        out = engine.generate(prompt, max_tokens=8, timeout=300)
        assert len(out["tokens"]) == 8
        _assert_greedy_of_full_forward(
            cfg, params, prompt, out["tokens"], ENGINE_GAP)
        assert engine.stats()["prefill_tokens_padded"] == bucket
    finally:
        engine.stop()
