"""The Laguna family (``models/laguna.py``) and what it made the shared ops
gain: a window in the paged-attention kernel and in the flash forward, rotary
over a part of the head with YaRN frequencies, SwiGLU experts behind a softmax
router. Seeded weights at small sizes on the CPU; the plain reference is
``benchmarks/families/laguna_reference.py``, which imports nothing of the
program. Logits are compared, never sampled tokens."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import laguna_reference as ref
from ray_tpu.models import laguna as lg
from ray_tpu.models import paged_decode as pd
from ray_tpu.ops import moe
from ray_tpu.ops.attention import flash_attention, reference_attention
from ray_tpu.ops.paged_attention import paged_attention
from ray_tpu.ops.rope import apply_rope, rope_frequencies, yarn_inv_freq

PAGE, SLOTS, POOL, TABLE = 8, 3, 120, 32
# float32 program against the float32 reference through five layers: what is
# left is the order of the sums (6e-6 read here). A bfloat16 program reads
# 3e-2 and more, every planted fault below 1e-2 and more
LOGIT_TOL = 1e-4


def _cfg_dict(config):
    cfg = {f.name: getattr(config, f.name) for f in dataclasses.fields(config)}
    cfg["held_experts"] = list(config.held_experts)
    return cfg


# --------------------------------------------------------------------------- #
# ops/paged_attention.py: starts
# --------------------------------------------------------------------------- #
def _dense_window(q, k_pool, v_pool, lengths, starts, table):
    """softmax(q k^T) v over rows [start, length) of each slot, in float32."""
    nb, nh, d = q.shape
    nkv, _, ps, _ = k_pool.shape
    out = np.zeros((nb, nh, d), np.float32)
    for b in range(nb):
        if lengths[b] == 0:
            continue
        k = np.asarray(k_pool, np.float32)[:, table[b]].reshape(nkv, -1, d)
        v = np.asarray(v_pool, np.float32)[:, table[b]].reshape(nkv, -1, d)
        for h in range(nh):
            kv = h // (nh // nkv)
            s = np.asarray(q, np.float32)[b, h] @ k[kv, starts[b]:lengths[b]].T
            p = np.exp(s - s.max())
            out[b, h] = (p / p.sum()) @ v[kv, starts[b]:lengths[b]]
    return out


@pytest.mark.parametrize("group", [6, 8])
def test_paged_attention_attends_from_starts(group):
    """The kernel (interpret mode) with ``starts`` against a dense masked
    softmax: query groups of 6 and 8, a dead slot, a start inside the first
    fetched page, starts past whole blocks (pages_per_block 2: blocks before
    the start are neither fetched nor multiplied), a one-row window."""
    nkv, d, ps, pages_per_slot = 2, 128, 16, 9
    rng = np.random.default_rng(group)
    lengths = np.array([100, 0, 37, 144, 16, 90], np.int32)
    starts = np.array([36, 0, 5, 80, 15, 89], np.int32)
    nb = len(lengths)
    q = jnp.asarray(rng.normal(size=(nb, nkv * group, d)) * d ** -0.5, jnp.bfloat16)
    k_pool = jnp.asarray(rng.normal(size=(nkv, 64, ps, d)), jnp.bfloat16)
    v_pool = jnp.asarray(rng.normal(size=(nkv, 64, ps, d)), jnp.bfloat16)
    table = rng.permutation(np.arange(1, 64))[: nb * pages_per_slot].reshape(
        nb, pages_per_slot).astype(np.int32)
    want = _dense_window(q, k_pool, v_pool, lengths, starts, table)
    got = paged_attention(q, k_pool, v_pool, jnp.asarray(lengths),
                          jnp.asarray(table), starts=jnp.asarray(starts),
                          pages_per_block=2, interpret=True)
    # bfloat16 probabilities and output: 2^-8 relative of values of order 1
    np.testing.assert_allclose(np.asarray(got, np.float32), want, atol=2e-2)
    assert not np.asarray(got, np.float32)[1].any()  # the dead slot: zeros
    fallback = pd._paged_attention_reference(
        q, k_pool, v_pool, jnp.asarray(table), jnp.asarray(lengths), 1.0,
        jnp.asarray(starts))
    np.testing.assert_allclose(np.asarray(fallback, np.float32), want, atol=2e-2)
    # without starts the kernel is the one it was: rows [0, length)
    whole = paged_attention(q, k_pool, v_pool, jnp.asarray(lengths),
                            jnp.asarray(table), pages_per_block=2, interpret=True)
    np.testing.assert_allclose(
        np.asarray(whole, np.float32),
        _dense_window(q, k_pool, v_pool, lengths, np.zeros_like(starts), table),
        atol=2e-2)


# --------------------------------------------------------------------------- #
# ops/attention.py: window
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("window", [1, 48, 64, 200])
def test_flash_window_matches_the_masked_reference(window):
    rng = np.random.default_rng(window)
    q = jnp.asarray(rng.normal(size=(2, 256, 6, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 256, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 256, 2, 32)), jnp.float32)
    got = flash_attention(q, k, v, block_q=32, block_k=64, interpret=True,
                          window=window)
    want = reference_attention(q, k, v, window=window)
    # float32 both sides: the online softmax's order of sums
    np.testing.assert_allclose(got, want, atol=2e-5)
    # off by one is another result, so the mask is the one asked for
    assert float(jnp.max(jnp.abs(
        want - reference_attention(q, k, v, window=window + 1)))) > 1e-3


def test_flash_without_a_window_is_the_call_it_was():
    """``window=None`` traces to the kernel it was (the lowered text of every
    accepted program is held in ``tests/test_chip_compile.py``), and a window
    that hides nothing gives its result bit for bit."""
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, 128, 4, 32)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(1, 128, 2, 32)), jnp.bfloat16)
    plain = flash_attention(q, k, k, block_q=32, block_k=64, interpret=True)
    wide = flash_attention(q, k, k, block_q=32, block_k=64, interpret=True,
                           window=128)
    assert jnp.array_equal(plain, wide)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, k, causal=False, window=8)


# --------------------------------------------------------------------------- #
# ops/rope.py: a part of the head, YaRN
# --------------------------------------------------------------------------- #
def test_yarn_table_is_the_formula_and_half_a_head_passes_through():
    rp = lg.ROPE_XS2[lg.FULL]
    d_r, theta = 64, 500000.0
    # a direct transcription of the published scheme, in float64
    i = np.arange(d_r // 2)
    f = theta ** (-2.0 * i / d_r)

    def dim_of(beta):
        return d_r * math.log(4096 / (beta * 2 * math.pi)) / (2 * math.log(theta))

    low, high = math.floor(dim_of(64)), math.ceil(dim_of(1))
    m = 1 - np.clip((i - low) / (high - low), 0, 1)
    inv = f / 64 * (1 - m) + f * m
    np.testing.assert_allclose(yarn_inv_freq(d_r, theta, rp), inv, rtol=1e-6)
    assert 0 < low < high < d_r // 2 and m[0] == 1 and m[-1] == 0
    cos, sin = rope_frequencies(128, 300, theta, rotary_dim=d_r, yarn=rp)
    assert cos.shape == sin.shape == (300, 32)
    factor = 0.1 * math.log(64) + 1
    assert abs(rp["attention_factor"] - factor) < 1e-12
    t = np.arange(300)[:, None]
    np.testing.assert_allclose(cos, np.cos(t * inv) * factor, atol=2e-4)
    np.testing.assert_allclose(sin, np.sin(t * inv) * factor, atol=2e-4)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(300, 3, 128)),
                    jnp.float32)
    turned = apply_rope(x, cos, sin)
    assert jnp.array_equal(turned[..., 64:], x[..., 64:])
    # the turned half keeps its norm times the attention factor
    np.testing.assert_allclose(
        jnp.linalg.norm(turned[..., :64], axis=-1),
        factor * jnp.linalg.norm(x[..., :64], axis=-1), rtol=1e-4)
    # the whole head with plain frequencies is the table it always was
    c0, s0 = rope_frequencies(128, 300, 10000.0)
    c1, s1 = rope_frequencies(128, 300, 10000.0, rotary_dim=128)
    assert jnp.array_equal(c0, c1) and jnp.array_equal(s0, s1)


# --------------------------------------------------------------------------- #
# ops/moe.py: softmax router, SwiGLU experts, the eight shares
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("impl", ["ragged", "dense"])
def test_eight_shares_and_the_shared_expert_once_are_the_uncut_layer(impl):
    """What each of eight chips computes of a 16-expert layer (its two
    experts' part of the sum, router and normalisation over all 16), summed,
    plus the shared expert counted once, is the uncut reference's layer."""
    h, f, r, k, t = 32, 16, 16, 4, 40
    keys = jax.random.split(jax.random.key(5), 8)
    router = {"w": jax.random.normal(keys[0], (h, r), jnp.float32)}
    experts = {name: jax.random.normal(kk, shape, jnp.float32) * 0.2
               for name, kk, shape in (("w_gate", keys[1], (r, h, f)),
                                       ("w_up", keys[2], (r, h, f)),
                                       ("w_down", keys[3], (r, f, h)))}
    shared = {name: jax.random.normal(kk, shape, jnp.float32) * 0.2
              for name, kk, shape in (("w_gate", keys[4], (h, f)),
                                      ("w_up", keys[5], (h, f)),
                                      ("w_down", keys[6], (f, h)))}
    x = jax.random.normal(keys[7], (t, h), jnp.float32)
    total = moe.swiglu_mlp(x, **shared)
    for chip in range(8):
        lo, hi = 2 * chip, 2 * chip + 2
        total = total + moe.routed_experts(
            x, router, {n: w[lo:hi] for n, w in experts.items()},
            held=(lo, hi), top_k=k, scale=2.5, impl=impl, scoring="softmax",
            form="swiglu")
    cfg = {"held_experts": [0, r], "num_experts_per_tok": k,
           "moe_routed_scaling_factor": 2.5}
    lp = {"router": router, "experts": experts, "shared": shared}
    want = ref.routed_sum(lp, x, cfg, None) + ref._swiglu(x, shared, None)
    np.testing.assert_allclose(total, want, atol=2e-5)  # float32 sums
    chosen, weights = moe.route(x, router, k, 2.5, "softmax")
    np.testing.assert_allclose(jnp.sum(weights, axis=-1), 2.5, rtol=1e-6)
    assert chosen.shape == (t, k)
    with pytest.raises(ValueError, match="scoring"):
        moe.route(x, router, k, 2.5, "tanh")


# --------------------------------------------------------------------------- #
# models/laguna.py against the reference, through pages and rings
# --------------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def tiny():
    config = lg.LagunaConfig.tiny(dtype=jnp.float32, attention_impl="reference")
    params = lg.init_params(config, jax.random.key(3))
    seqs = np.random.default_rng(0).integers(1, 256, (SLOTS, 256), dtype=np.int32)
    return config, params, seqs


def _served_logits(config, params, seqs, lengths, ticks, params_ref=None,
                   decode_of=None):
    """Prefill each slot at its length (one program a slot, a pad row
    beside it), then ``ticks`` teacher-forced decode ticks of ALL slots in
    one batch. Returns (prefill logits a slot, decode logits [ticks, slot])."""
    cache = lg.init_cache(config, SLOTS, POOL, PAGE)
    prefill = lg.make_paged_prefill_fn(config, PAGE)
    table = np.arange(1, 1 + SLOTS * TABLE, dtype=np.int32).reshape(SLOTS, TABLE)
    first = []
    for s, n in enumerate(lengths):
        bucket = -(-n // 32) * 32
        toks = np.zeros((2, bucket), np.int32)
        toks[0, :n] = seqs[s, :n]
        pages = np.zeros((2, bucket // PAGE), np.int32)
        pages[0] = table[s, : bucket // PAGE]
        logits, cache, _ = prefill(
            params, cache, jnp.asarray(toks), jnp.asarray(pages),
            jnp.asarray([n, 1], jnp.int32), jnp.asarray([s, SLOTS], jnp.int32))
        first.append(logits[0])
    one = jax.jit(lambda c, t, p: (decode_of or lg.paged_decode_one)(
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


def _worst_gap(config, params, seqs, lengths, ticks, **kw):
    """The largest logit difference between the served path and the
    reference's full forward, over the first token and every 9th tick."""
    truth = lg.LagunaConfig.tiny(dtype=jnp.float32, attention_impl="reference")
    cfg = _cfg_dict(truth)
    first, later, _ = _served_logits(config, params, seqs, lengths, ticks, **kw)
    worst = 0.0
    for s, n in enumerate(lengths):
        ticks_seen = [t for t in range(ticks) if t % 9 == 0 or t == ticks - 1]
        want = ref.reference_logits(
            jax.tree.map(lambda a: a.astype(jnp.float32), params),
            jnp.asarray(seqs[s, : n + ticks]), cfg)
        worst = max(worst, float(jnp.max(jnp.abs(first[s] - want[n - 1]))))
        for t in ticks_seen:
            worst = max(worst, float(jnp.max(jnp.abs(
                later[t][s] - want[n + t]))))
    return worst


# a window of 32, pages of 8, a ring of 5: the long slot's ring wraps five
# times in prefill and again in decode, its full layers hold two dozen pages;
# one prompt is shorter than the window, one is no page multiple; all three
# decode in one batch
LENGTHS = (150, 20, 77)


def test_prefill_then_decode_through_rings_and_pages_is_the_reference(tiny):
    config, params, seqs = tiny
    assert lg.ring_pages(config, PAGE) == 5
    assert _worst_gap(config, params, seqs, LENGTHS, 40) < LOGIT_TOL


def test_decode_counts_the_rows_it_attends(tiny):
    config, params, seqs = tiny
    _, _, counts = _served_logits(config, params, seqs, LENGTHS, 2)
    names = lg.DECODE_COUNTERS
    first = dict(zip(names, counts[0].tolist()))
    # 2 full layers see every cached row, 3 sliding ones at most the window
    assert first["attn_rows_full"] == 2 * sum(n + 1 for n in LENGTHS)
    assert first["attn_rows_window"] == 3 * sum(min(n + 1, 32) for n in LENGTHS)
    assert first["moe_assignments"] == 4 * SLOTS * 2  # 4 sparse layers, top 2
    assert 0 < first["moe_assignments_held"] <= first["moe_assignments"]


FAULTS = {
    "window_off_by_one": dict(config=dict(sliding_window=33)),
    "routed_factor_dropped": dict(config=dict(moe_routed_scaling_factor=1.0)),
    "yarn_factor_dropped": dict(config=dict(rope_parameters={
        lg.FULL: {**lg.LagunaConfig.tiny().rope_parameters[lg.FULL],
                  "attention_factor": 1.0},
        lg.SLIDING: lg.ROPE_XS2[lg.SLIDING]})),
    "bfloat16_for_float32": dict(config=dict(dtype=jnp.bfloat16)),
    "ring_page_order": dict(patch="ring"),
    "gate_dropped": dict(patch="gate"),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_fails_the_tolerance(tiny, fault, monkeypatch):
    """Each departure from the published layer, and the precision below the
    one stated, is another result by more than ``LOGIT_TOL``: the comparison
    above would not pass with it."""
    config, params, seqs = tiny
    spec = FAULTS[fault]
    config = dataclasses.replace(config, **spec.get("config", {}))
    if config.dtype == jnp.bfloat16:
        params = jax.tree.map(lambda a: a.astype(jnp.bfloat16)
                              if a.dtype == jnp.float32 and a.ndim > 1 else a,
                              params)
    if spec.get("patch") == "ring":
        attend = lg._paged_attention
        monkeypatch.setattr(
            lg, "_paged_attention",
            lambda q, k, v, table, *a, starts=None, **kw: attend(
                q, k, v, table if starts is None else jnp.roll(table, 1, axis=1),
                *a, starts=starts, **kw))
    if spec.get("patch") == "gate":
        out = lg._attn_out
        monkeypatch.setattr(lg, "_attn_out",
                            lambda lp, o, gate: out(lp, o, jnp.ones_like(gate)))
    assert _worst_gap(config, params, seqs, LENGTHS, 12) > 50 * LOGIT_TOL


def test_engine_serves_the_family_through_its_normal_path(tiny):
    """``LLMEngine`` over a ``LagunaConfig``: the same admission, allocator
    and phases; the tokens it emits are the reference's choices (teacher
    forced: gap 0 up to float32 rounding), and ``stats()`` has the rings and
    the attended rows."""
    from benchmarks.harness import reference as href
    from ray_tpu.serve.llm import LLMEngine, model_presets

    config, params, seqs = tiny
    assert isinstance(model_presets()["laguna_tiny"](), lg.LagunaConfig)
    engine = LLMEngine(config, params, num_slots=4, max_seq_len=192,
                       decode_chunk=4, prefill_buckets=[32, 96, 160],
                       page_size=PAGE)
    try:
        assert engine.stats()["kv_pages_in_use"] == 0
        prompts = [seqs[0, :150].tolist(), seqs[1, :20].tolist(),
                   seqs[2, :77].tolist()]
        import concurrent.futures as cf
        with cf.ThreadPoolExecutor(3) as pool:
            outs = list(pool.map(
                lambda p: engine.generate(tokens=p, max_tokens=24), prompts))
        stats = engine.stats()
    finally:
        engine.stop()
    gap_fn = ref.make_gap_fn(_cfg_dict(config))
    for prompt, out in zip(prompts, outs):
        assert len(out["tokens"]) == 24
        gaps = href.teacher_forced_gaps(gap_fn, params, prompt, out["tokens"], 192)
        assert max(gaps) < LOGIT_TOL
    ring = lg.ring_pages(config, PAGE)
    assert stats["window_ring_pages"] == 3 * (4 + 1) * ring
    assert stats["window_state_bytes"] == 2 * 2 * 3 * 5 * ring * PAGE * 32 * 4
    assert stats["state_bytes"] == stats["window_state_bytes"]
    assert stats["kv_bytes_per_token"] == 2 * 2 * 2 * 32 * 4  # 2 full layers
    assert stats["kv_pages_total"] == 4 * 24 and stats["kv_pages_in_use"] == 0
    assert stats["attn_rows_full"] > stats["attn_rows_window"] > 0
    assert stats["moe_assignments"] > 0 and stats["state_slots"] == 4


def test_engine_counts_the_blocks_of_a_compacted_share(monkeypatch):
    """An eighth of the router's outputs held (2 of 16, top 2; tiles of 8
    rows here for the grouped product's 512): a prefill's routed choices are
    compacted to a block before the products, the tokens are still the
    reference's choices, and ``stats()`` sums the prefill program's own
    count of the products' calls with the decode program's (whose 8 choices
    a tick are under a block: none)."""
    from benchmarks.harness import reference as href
    from ray_tpu.serve.llm import LLMEngine

    monkeypatch.setattr(moe, "RAGGED_TILE", 8)
    config = lg.LagunaConfig.tiny(
        dtype=jnp.float32, attention_impl="reference", num_experts=2,
        n_router_outputs=16, held_experts=(0, 2))
    assert moe._capacity(96 * 2, 2, 16) == 48 < 96 * 2
    assert lg.PREFILL_COUNTERS == lg.DECODE_COUNTERS[4:6]
    params = lg.init_params(config, jax.random.key(4))
    prompt = np.random.default_rng(1).integers(1, 256, 77).tolist()
    engine = LLMEngine(config, params, num_slots=4, max_seq_len=192,
                       decode_chunk=4, prefill_buckets=[96], page_size=PAGE)
    try:
        before = engine.stats()
        out = engine.generate(tokens=prompt, max_tokens=12)
        stats = engine.stats()
    finally:
        engine.stop()
    assert before["moe_blocks"] == before["moe_blocks_extra"] == 0
    gaps = href.teacher_forced_gaps(ref.make_gap_fn(_cfg_dict(config)), params,
                                    prompt, out["tokens"], 192)
    assert max(gaps) < LOGIT_TOL
    # 4 expert layers a call: the bucket's two programs brought up, then the
    # request's one-row call
    assert stats["moe_blocks"] == 4 * 3 and stats["moe_blocks_extra"] == 0
    assert 0 < stats["moe_assignments_held"] < stats["moe_assignments"]


def test_other_families_count_no_attended_rows():
    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.serve.llm import LLMEngine

    engine = LLMEngine(LlamaConfig.tiny(attention_impl="reference"),
                       num_slots=2, decode_chunk=4, max_seq_len=128,
                       prefill_buckets=[64])
    try:
        engine.generate(tokens=[1, 2, 3], max_tokens=4)
        stats = engine.stats()
    finally:
        engine.stop()
    assert stats["attn_rows_full"] == stats["attn_rows_window"] == 0
    assert stats["moe_blocks"] == stats["moe_blocks_extra"] == 0
    assert stats["window_ring_pages"] == stats["window_state_bytes"] == 0
    assert stats["kv_pages_total"] == engine.total_pages - 1
