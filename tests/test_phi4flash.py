"""The phi4flash family (``models/phi4flash.py``: a Mamba-1 self-decoder with
window differential attention, ONE full layer whose pages every cross layer
reads, Gated Memory Units, and a prefill that runs the cross-decoder over a
prompt's last row only) and what it made the shared ops gain: a selective
scan (``ops/ssm.py`` ``mamba1_prefill`` / ``mamba1_step``) and LayerNorm.
Seeded weights at small sizes on the CPU; the plain reference is
``benchmarks/families/phi4flash_reference.py``, which imports nothing of the
program and runs EVERY layer over EVERY row. Logits are compared, never
sampled tokens."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import phi4flash_reference as ref
from ray_tpu.models import phi4flash as pf
from ray_tpu.ops import ssm
from ray_tpu.ops.norms import layer_norm

PAGE, SLOTS, POOL, TABLE = 8, 3, 120, 32
# float32 program against the float32 reference through eight layers: what is
# left is the order of the sums (1e-5 read here: the scan's 150 steps, the
# online softmax). A bfloat16 program reads 3e-2 and more, every planted
# fault below 2e-2 and more
LOGIT_TOL = 1e-4


def _cfg_dict(config):
    return {f.name: getattr(config, f.name) for f in dataclasses.fields(config)}


# --------------------------------------------------------------------------- #
# ops/norms.py, ops/ssm.py
# --------------------------------------------------------------------------- #
def test_layer_norm_is_the_formula():
    rng = np.random.default_rng(0)
    x = rng.normal(2.0, 3.0, (5, 64)).astype(np.float32)
    w, b = rng.normal(size=(2, 64)).astype(np.float32)
    want = (x - x.mean(-1, keepdims=True)) / np.sqrt(
        x.var(-1, keepdims=True) + 1e-5) * w + b
    # float32 both sides
    np.testing.assert_allclose(layer_norm(jnp.asarray(x), w, b, 1e-5), want,
                               atol=2e-6)
    low = layer_norm(jnp.asarray(x, jnp.bfloat16), w, b)
    assert low.dtype == jnp.bfloat16  # statistics in float32, x's dtype out


def _scan_inputs(seed, bsz, s, din, n):
    ks = jax.random.split(jax.random.key(seed), 7)
    return dict(
        x=jax.random.normal(ks[0], (bsz, s, din)),
        dt=jax.nn.softplus(jax.random.normal(ks[1], (bsz, s, din)) - 2.0),
        a=-jnp.exp(jax.random.uniform(ks[2], (din, n), minval=0.0, maxval=2.5)),
        b=jax.random.normal(ks[3], (bsz, s, n)),
        c=jax.random.normal(ks[4], (bsz, s, n)),
        d=jax.random.normal(ks[5], (din,)),
        s0=jax.random.normal(ks[6], (bsz, n, din // 128, 128)))


def _scan_by_hand(p, length):
    """One row's recurrence a token at a time, state [Din, N] as written."""
    def step(state, part):
        x, dt, b, c = part
        state = jnp.exp(dt[:, None] * p["a"]) * state \
            + (dt * x)[:, None] * b[None, :]
        return state, state @ c + p["d"] * x

    return jax.lax.scan(step, p["s0"], tuple(
        p[k][:length] for k in ("x", "dt", "b", "c")))


@pytest.mark.parametrize("impl", ["reference", "pallas_interpret"])
def test_mamba1_prefill_is_the_scan_and_step_continues_it(impl):
    """``mamba1_prefill`` against a ``lax.scan`` a token, with a nonzero
    ``state0`` and ragged ``lengths`` (a row's state is what its LAST REAL
    token left, whatever the padding holds), channels over two 128-lane rows
    and time over two blocks; then ``mamba1_step`` moves that state by one
    token, in place in the array of every layer's state, and moves nothing
    else. float32 both sides: the order of a step's sums (1e-6 read)."""
    bsz, s, din, n = 3, 300, 256, 4
    p = _scan_inputs(7, bsz, s, din, n)
    lengths = np.array([300, 1, 170], np.int32)
    y, state = ssm.mamba1_prefill(p["x"], p["dt"], p["a"], p["b"], p["c"],
                                  p["d"], p["s0"], jnp.asarray(lengths),
                                  impl=impl)
    for row, length in enumerate(lengths):
        one = {k: (v[row] if k not in ("a", "d") else v) for k, v in p.items()}
        one["s0"] = p["s0"][row].reshape(n, din).T
        want_state, want_y = _scan_by_hand(one, int(length))
        np.testing.assert_allclose(y[row, :length], want_y, atol=2e-5)
        np.testing.assert_allclose(state[row].reshape(n, din).T, want_state,
                                   atol=2e-5)
    # one more token a row, the middle row held still by dt = 0
    q = _scan_inputs(8, bsz, 1, din, n)
    dt = q["dt"][:, 0].at[1].set(0.0)
    every = jnp.full((2, bsz + 1, n, din // 128, 128), 3.0).at[1, :bsz].set(state)
    y1, moved = ssm.mamba1_step(q["x"][:, 0], dt, p["a"], q["b"][:, 0],
                                q["c"][:, 0], p["d"], every, layer=1, impl=impl)
    for row in range(bsz):
        one = dict(x=q["x"][row], dt=dt[row][None], b=q["b"][row], c=q["c"][row],
                   a=p["a"], d=p["d"], s0=state[row].reshape(n, din).T)
        want_state, want_y = _scan_by_hand(one, 1)
        np.testing.assert_allclose(y1[row], want_y[0], atol=2e-5)
        np.testing.assert_allclose(moved[1, row].reshape(n, din).T, want_state,
                                   atol=2e-5)
    assert jnp.array_equal(moved[1, 1], state[1])       # dt = 0: it stays
    assert float(jnp.min(moved[0])) == float(jnp.max(moved[1, bsz])) == 3.0


def test_a_padded_query_against_a_packed_row_is_the_pairs_own_product():
    """``[q_1 | 0]`` and ``[0 | q_2]`` against ``[k_1 | k_2]``: the shared
    kernels see heads of ``2 D`` and compute ``q_i . k_i``."""
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(5, 8, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(5, 4, 16)), jnp.float32)
    packed = k.reshape(5, 2, 32)            # KV pair m: heads 2m, 2m + 1
    scores = jnp.einsum("tgd,td->tg", pf._padded_queries(q)[:, :4], packed[:, 0])
    # query heads 0..3 are pairs 0 and 1, both on KV pair 0
    want = jnp.stack([jnp.sum(q[:, h] * k[:, h % 2], -1) for h in range(4)], 1)
    np.testing.assert_allclose(scores, want, atol=1e-5)


# --------------------------------------------------------------------------- #
# models/phi4flash.py against the reference, through pages, rings and state
# --------------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def tiny():
    config = pf.Phi4FlashConfig.tiny(dtype=jnp.float32,
                                     attention_impl="reference",
                                     scan_impl="reference")
    params = pf.init_params(config, jax.random.key(3))
    seqs = np.random.default_rng(0).integers(1, 256, (SLOTS, 256), dtype=np.int32)
    return config, params, seqs


# a window of 32, pages of 8, a ring of 5: the long slot's ring wraps four
# times in prefill and again in decode, its pages are two dozen; one prompt
# is shorter than the window, one is no page multiple; all three are
# prefilled by ONE program and decode in one batch
LENGTHS = (150, 20, 77)


def _prefilled(config, params, seqs, lengths, **kw):
    """One prefill program over all the slots (rows right-padded to the
    longest's bucket). Returns (first-token logits [slot, V], cache, counts,
    table)."""
    bucket = -(-max(lengths) // 32) * 32
    toks = np.zeros((SLOTS, bucket), np.int32)
    for s, n in enumerate(lengths):
        toks[s, :n] = seqs[s, :n]
    table = np.arange(1, 1 + SLOTS * TABLE, dtype=np.int32).reshape(SLOTS, TABLE)
    logits, cache, counts = pf.make_paged_prefill_fn(config, PAGE, **kw)(
        params, pf.init_cache(config, SLOTS, POOL, PAGE), jnp.asarray(toks),
        jnp.asarray(table[:, : bucket // PAGE]), jnp.asarray(lengths, jnp.int32),
        jnp.arange(SLOTS, dtype=jnp.int32))
    return logits, cache, counts, table


def _served_logits(config, params, seqs, lengths, ticks):
    """Prefill, then ``ticks`` teacher-forced decode ticks of ALL slots in
    one batch. Returns (prefill logits, decode logits [tick][slot], counts)."""
    first, cache, _, table = _prefilled(config, params, seqs, lengths)
    one = jax.jit(lambda c, t, p: pf.paged_decode_one(
        params, c, t, p, jnp.ones((SLOTS,), bool), jnp.asarray(table), config,
        PAGE, False))
    pos = np.array(lengths, np.int32)
    later, counts = [], []
    for _ in range(ticks):
        toks = seqs[np.arange(SLOTS), pos]
        logits, cache, c = one(cache, jnp.asarray(toks), jnp.asarray(pos))
        pos = pos + 1
        later.append(logits)
        counts.append(np.asarray(c))
    return first, later, counts


def _worst_gap(config, params, seqs, lengths, ticks):
    """The largest logit difference between the served path and the
    reference's full forward, over the first token (the last-row-only path)
    and every 9th tick."""
    cfg = _cfg_dict(pf.Phi4FlashConfig.tiny())
    first, later, _ = _served_logits(config, params, seqs, lengths, ticks)
    worst_first = worst_later = 0.0
    for s, n in enumerate(lengths):
        want = ref.reference_logits(
            jax.tree.map(lambda a: a.astype(jnp.float32), params),
            jnp.asarray(seqs[s, : n + ticks]), cfg)
        worst_first = max(worst_first,
                          float(jnp.max(jnp.abs(first[s] - want[n - 1]))))
        for t in [t for t in range(ticks) if t % 9 == 0 or t == ticks - 1]:
            worst_later = max(worst_later, float(jnp.max(jnp.abs(
                later[t][s] - want[n + t]))))
    return worst_first, worst_later


def test_prefill_then_decode_through_rings_pages_and_state_is_the_reference(tiny):
    """40 decode ticks after a prefill of three unequal rows: window layers
    through rings that wrap, the full layer and the cross layers through ONE
    layer's pages, the scan through per-slot state and convolution rows; and
    the first token, which the program computes from layers 17.. over the
    LAST row only, is the last row of the reference's forward of every layer
    over every row."""
    config, params, seqs = tiny
    assert config.layer_kinds == ("mamba", "window", "mamba", "window",
                                  "mamba", "full", "gmu", "cross")
    assert pf.ring_pages(config, PAGE) == 5
    first, later = _worst_gap(config, params, seqs, LENGTHS, 40)
    assert first < LOGIT_TOL and later < LOGIT_TOL


def test_the_skip_leaves_the_cache_and_the_first_token_it_would_without(tiny):
    """The prefill that runs layers ``half + 1 ..`` over a prompt's last row
    only gives the first-token logits and leaves the cache (the one layer's
    pages, the rings, the scan state, the convolution rows) of the prefill
    that runs all eight layers over all rows, and counts one cross-decoder
    row a prompt where that one counts every row."""
    config, params, seqs = tiny
    logits, cache, counts, _ = _prefilled(config, params, seqs, LENGTHS)
    whole_logits, whole_cache, whole_counts, _ = _prefilled(
        config, params, seqs, LENGTHS, cross_over_all_rows=True)
    np.testing.assert_allclose(logits, whole_logits, atol=LOGIT_TOL)
    for name, got, want in zip(cache._fields, cache, whole_cache):
        assert float(jnp.max(jnp.abs(got))) > 0, name
        np.testing.assert_allclose(got, want, atol=1e-5, err_msg=name)
    names = pf.PREFILL_COUNTERS
    assert dict(zip(names, counts.tolist())) == {
        "prefill_rows_self": sum(LENGTHS), "prefill_rows_cross": len(LENGTHS)}
    assert dict(zip(names, whole_counts.tolist())) == {
        "prefill_rows_self": sum(LENGTHS), "prefill_rows_cross": sum(LENGTHS)}


def test_the_scan_walks_a_prompt_in_pieces_from_the_state_before(tiny, monkeypatch):
    """A bucket taller than ``SCAN_PREFILL_ROWS`` goes through the scan a
    piece at a time, each resuming from the last one's state (``state0``):
    five pieces of 32 rows, a row that ends inside the first piece and one
    that ends inside the third, leave the logits and the state of one call."""
    config, params, seqs = tiny
    logits, cache, _, _ = _prefilled(config, params, seqs, LENGTHS)
    monkeypatch.setattr(pf, "SCAN_PREFILL_ROWS", 32)
    pieces_logits, pieces_cache, _, _ = _prefilled(config, params, seqs, LENGTHS)
    np.testing.assert_allclose(pieces_logits, logits, atol=1e-5)
    np.testing.assert_allclose(pieces_cache.ssm, cache.ssm, atol=1e-5)


def test_decode_counts_the_rows_it_attends_and_the_states_it_moves(tiny):
    config, params, seqs = tiny
    _, _, counts = _served_logits(config, params, seqs, LENGTHS, 2)
    first = dict(zip(pf.DECODE_COUNTERS, counts[0].tolist()))
    # the full layer and one cross layer read the same pages; two window
    # layers see at most the window; three scan layers move every slot
    assert config.page_readers == 2
    assert first["attn_rows_shared"] == 2 * sum(n + 1 for n in LENGTHS)
    assert first["attn_rows_window"] == 2 * sum(min(n + 1, 32) for n in LENGTHS)
    assert first["scan_slots"] == 3 * SLOTS


def test_the_scan_kernels_serve_the_model_as_the_scan_does(tiny):
    """The Pallas scan (interpret mode) in the model's prefill and decode:
    the same logits as the ``lax.scan`` path to float32 rounding."""
    config, params, seqs = tiny
    kernel = dataclasses.replace(config, scan_impl="pallas_interpret")
    lengths = (60, 20, 33)
    want_first, want_later, _ = _served_logits(config, params, seqs, lengths, 3)
    first, later, _ = _served_logits(kernel, params, seqs, lengths, 3)
    np.testing.assert_allclose(first, want_first, atol=1e-5)
    np.testing.assert_allclose(later[-1], want_later[-1], atol=1e-5)


def _swap_v_halves(pack):
    def swapped(config, kv):
        k, v = pack(config, kv)
        d = config.head_dim
        return k, jnp.concatenate([v[..., d:], v[..., :d]], axis=-1)
    return swapped


def _absent_for_cross(attend, readers):
    """``attend`` with the K/V argument zeroed in every call but a tick's or
    a prefill's first (the full layer's own): a cross layer that reads its
    own, absent, K/V. The calls are counted at trace time."""
    calls = []

    def faulty(q, k, v, *args, **kw):
        if kw.get("starts") is not None:
            return attend(q, k, v, *args, **kw)
        calls.append(1)
        if len(calls) % readers != 1:
            k, v = jnp.zeros_like(k), jnp.zeros_like(v)
        return attend(q, k, v, *args, **kw)
    return faulty


FAULTS = ("window_off_by_one", "lam0_of_the_wrong_layer", "m_after_the_gate",
          "v_halves_swapped", "cross_reads_its_own_kv", "bfloat16_for_float32")


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_fails_the_tolerance(tiny, fault, monkeypatch):
    """Each departure from the layer equations, and the precision below the
    one stated, is another result by more than ``LOGIT_TOL`` by a wide
    factor: the comparison above would not pass with it."""
    config, params, seqs = tiny
    if fault == "window_off_by_one":
        config = dataclasses.replace(config, sliding_window=33)
    elif fault == "lam0_of_the_wrong_layer":
        right = pf.lambda_init
        monkeypatch.setattr(pf, "lambda_init", lambda layer: right(layer + 1))
    elif fault == "m_after_the_gate":
        monkeypatch.setattr(pf, "_memory", lambda s, z: s * jax.nn.silu(z))
    elif fault == "v_halves_swapped":
        monkeypatch.setattr(pf, "_pack", _swap_v_halves(pf._pack))
    elif fault == "cross_reads_its_own_kv":
        monkeypatch.setattr(pf, "_paged_attention", _absent_for_cross(
            pf._paged_attention, config.page_readers))
        monkeypatch.setattr(pf, "_one_row_attention", _absent_for_cross(
            pf._one_row_attention, config.page_readers))
    else:
        config = dataclasses.replace(config, dtype=jnp.bfloat16)
        params = jax.tree.map(lambda a: a.astype(jnp.bfloat16)
                              if a.dtype == jnp.float32 and a.ndim > 1 else a,
                              params)
    first, later = _worst_gap(config, params, seqs, LENGTHS, 12)
    assert max(first, later) > 50 * LOGIT_TOL
    if fault in ("m_after_the_gate", "cross_reads_its_own_kv",
                 "lam0_of_the_wrong_layer", "bfloat16_for_float32"):
        # what touches the cross-decoder shows in the first token alone
        assert first > 50 * LOGIT_TOL


def test_engine_serves_the_family_through_its_normal_path(tiny):
    """``LLMEngine`` over a ``Phi4FlashConfig``: the same admission, allocator
    and phases; the tokens it emits are the reference's choices (teacher
    forced: gap 0 up to float32 rounding), and ``stats()`` tells the layers
    that keep pages from those that read them."""
    from benchmarks.harness import reference as href
    from ray_tpu.serve.llm import LLMEngine, model_presets

    config, params, seqs = tiny
    assert isinstance(model_presets()["phi4flash_tiny"](), pf.Phi4FlashConfig)
    engine = LLMEngine(config, params, num_slots=4, max_seq_len=192,
                       decode_chunk=4, prefill_buckets=[32, 96, 160],
                       page_size=PAGE)
    try:
        prompts = [seqs[0, :150].tolist(), seqs[1, :20].tolist(),
                   seqs[2, :77].tolist()]
        import concurrent.futures as cf
        with cf.ThreadPoolExecutor(3) as pool:
            outs = list(pool.map(
                lambda p: engine.generate(tokens=p, max_tokens=24), prompts))
        stats = engine.stats()
    finally:
        engine.stop()
    gap_fn = ref.make_gap_fn(_cfg_dict(pf.Phi4FlashConfig.tiny()))
    for prompt, out in zip(prompts, outs):
        assert len(out["tokens"]) == 24
        gaps = href.teacher_forced_gaps(gap_fn, params, prompt, out["tokens"], 192)
        assert max(gaps) < LOGIT_TOL
    ring = pf.ring_pages(config, PAGE)
    # ONE layer keeps pages: 2 KV pairs x 2 heads x 8 wide x float32, K and V
    assert stats["kv_bytes_per_token"] == 2 * 4 * 8 * 4
    assert stats["window_ring_pages"] == 2 * (4 + 1) * ring
    assert stats["window_state_bytes"] == 2 * 2 * 2 * 5 * ring * PAGE * 16 * 4
    scan_bytes = 3 * 5 * (4 * 128 * 4 + 3 * 128 * 4)  # state, conv rows
    assert stats["state_bytes"] == stats["window_state_bytes"] + scan_bytes
    assert stats["kv_pages_total"] == 4 * 24 and stats["kv_pages_in_use"] == 0
    assert stats["state_slots"] == 4
    # every prompt ran the self-decoder whole and the cross-decoder one row
    assert stats["prefill_rows_self"] == 150 + 20 + 77
    assert stats["prefill_rows_cross"] == 3
    # two layers read the pages; a window layer never more than the window
    assert stats["attn_rows_shared"] > stats["attn_rows_window"] > 0
    assert stats["scan_slots"] > 0 and stats["attn_rows_full"] == 0
