"""The Kimi-K2 family (``models/kimi_k2.py``) and what it made the shared ops
gain: a latent pool in the paged-attention kernel (the values a prefix of the
key row) and a v width of its own in the flash forward. Seeded weights at
small sizes on the CPU; the plain reference is
``benchmarks/families/kimi_k2_reference.py``, UNABSORBED, which imports
nothing of the program. Logits are compared, never sampled tokens: prefill
(unabsorbed) then decode (absorbed) through the one cache against the
reference's full forward is the test that the two attention paths are one
function."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import kimi_k2_reference as ref
from ray_tpu.models import cache_rows, kimi_k2 as km
from ray_tpu.ops import moe
from ray_tpu.ops.attention import flash_attention, reference_attention
from ray_tpu.ops.paged_attention import paged_attention_latent

PAGE, SLOTS, POOL, TABLE = 8, 3, 120, 32
# float32 program against the float32 reference through four layers: what is
# left is the order of the sums and the absorbed products' (q W_uk) c for
# q (W_uk c) (1e-5 read here). A bfloat16 program reads 3e-2 and more, every
# planted fault below 5e-3 and more
LOGIT_TOL = 1e-4


def _cfg_dict(config):
    cfg = {f.name: getattr(config, f.name) for f in dataclasses.fields(config)}
    cfg["held_experts"] = list(config.held_experts)
    return cfg


# --------------------------------------------------------------------------- #
# ops/paged_attention.py: a latent pool; ops/attention.py: a v width of its own
# --------------------------------------------------------------------------- #
# 0: dead; 1: one row; 16 / 17: a page edge; 32 / 33: a block edge (2 pages a
# block); 100: several blocks. Dead and live slots interleave.
LATENT_LENGTHS = [0, 1, 16, 17, 0, 32, 33, 100]


def test_latent_kernel_reads_each_row_once_for_scores_and_values():
    """``paged_attention_latent`` (interpret mode) at the published shape of a
    row (640 stored, the first 512 the values) and a query group of 64 against
    a dense masked softmax in float32; pages that hold no live row are NaN, so
    reading one shows."""
    g, w, vw, ps, slot_pages = 64, 640, 512, 16, 7
    rng = np.random.default_rng(0)
    nb = len(LATENT_LENGTHS)
    total = 1 + nb * slot_pages
    pool = rng.standard_normal((1, total, ps, w)).astype(np.float32)
    q = (rng.standard_normal((nb, g, w)) * w ** -0.5).astype(np.float32)
    table = np.zeros((nb, slot_pages), np.int32)
    pages = rng.permutation(np.arange(1, total))
    owned = np.zeros((total,), bool)
    for b, n in enumerate(LATENT_LENGTHS):
        mine = pages[b * slot_pages: b * slot_pages + -(-n // ps)]
        table[b, :len(mine)] = mine
        owned[mine] = True
    pool[:, ~owned] = np.nan
    lengths = np.asarray(LATENT_LENGTHS, np.int32)
    got = paged_attention_latent(
        jnp.asarray(q), jnp.asarray(pool), jnp.asarray(lengths),
        jnp.asarray(table), v_width=vw, pages_per_block=2, interpret=True)
    assert got.shape == (nb, g, vw)
    for b, n in enumerate(LATENT_LENGTHS):
        if n == 0:
            assert not np.asarray(got[b]).any()  # exact zeros, nothing read
            continue
        rows = pool[0, table[b]].reshape(-1, w)[:n]
        s = q[b] @ rows.T
        p = np.exp(s - s.max(axis=-1, keepdims=True))
        want = (p / p.sum(axis=-1, keepdims=True)) @ rows[:, :vw]
        # float32 in, float32 sums in another order (blocks of two pages)
        np.testing.assert_allclose(got[b], want, atol=2e-5)
    fallback = cache_rows.latent_attention_reference(
        jnp.asarray(q), jnp.asarray(np.nan_to_num(pool)), jnp.asarray(table),
        jnp.asarray(lengths), vw)
    np.testing.assert_allclose(got, fallback, atol=2e-5)
    with pytest.raises(ValueError, match="latent pool"):
        paged_attention_latent(jnp.asarray(q), jnp.asarray(pool[..., :512]),
                               jnp.asarray(lengths), jnp.asarray(table),
                               v_width=vw)


@pytest.mark.parametrize("seq,lengths", [(256, None), (200, None),
                                         (256, (256, 70)), (256, (1, 129))])
def test_flash_forward_takes_a_v_width_of_its_own(seq, lengths):
    """q and k of 192 (128 + the 64 rotated) and v of 128, as the unabsorbed
    prefill hands them over: the kernel (interpret mode) against the plain
    reference, at a block multiple and at a length that is padded; and, told
    the rows' ``lengths``, the q blocks (of 64) that hold a real row as ever
    and exact zeros in the blocks past them."""
    rng = np.random.default_rng(seq)
    q = jnp.asarray(rng.standard_normal((2, seq, 4, 192)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, seq, 4, 192)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, seq, 4, 128)), jnp.float32)
    got = flash_attention(
        q, k, v, scale=0.1, block_q=64, block_k=128, interpret=True,
        lengths=None if lengths is None else jnp.asarray(lengths, jnp.int32))
    assert got.shape == (2, seq, 4, 128)
    want = reference_attention(q, k, v, scale=0.1)
    for b, n in enumerate(lengths or (seq, seq)):
        live = -(-n // 64) * 64
        # float32 both: the online softmax's sums in blocks of 128 keys
        np.testing.assert_allclose(got[b, :live], want[b, :live], atol=2e-5)
        assert not np.asarray(got[b, live:]).any()


# --------------------------------------------------------------------------- #
# ops/moe.py on a share: sigmoid scores with a bias, SwiGLU experts
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("impl", ["ragged", "dense"])
def test_shares_and_the_shared_expert_once_are_the_uncut_layer(impl):
    """What each of eight chips computes of a 32-expert layer at ``tiny()``
    widths (its four experts' part of the sum; router, bias and normalisation
    over all 32), summed, plus the shared expert counted once, is the uncut
    reference's whole layer."""
    config = km.KimiK2Config.tiny(
        dtype=jnp.float32, n_routed_experts=32, n_router_outputs=32,
        held_experts=(0, 32), num_experts_per_tok=4)
    lp = jax.tree.map(lambda a: a[0],
                      km.init_params(config, jax.random.key(5))["layers"])
    x = jax.random.normal(jax.random.key(6), (40, config.hidden_size))
    total = moe.swiglu_mlp(x, **lp["shared"])
    for chip in range(8):
        lo, hi = 4 * chip, 4 * chip + 4
        total = total + moe.routed_experts(
            x, lp["router"], {n: w[lo:hi] for n, w in lp["experts"].items()},
            held=(lo, hi), top_k=4, scale=config.routed_scaling_factor,
            impl=impl, scoring="sigmoid_bias", form="swiglu")
    cfg = _cfg_dict(config)
    want = ref.routed_sum(lp, x, cfg, None) + ref._swiglu(x, lp["shared"], None)
    np.testing.assert_allclose(total, want, atol=2e-5)  # float32 sums
    chosen, weights = ref.routing(lp, x, cfg)
    np.testing.assert_allclose(jnp.sum(weights, axis=-1), 2.827, rtol=1e-6)
    # the bias moves the choice of some token, and never its weights
    plain = jax.lax.top_k(jax.nn.sigmoid(x @ lp["router"]["w"]), 4)[1]
    assert not jnp.array_equal(jnp.sort(chosen), jnp.sort(plain))


# --------------------------------------------------------------------------- #
# models/kimi_k2.py against the reference, through the latent pages
# --------------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def tiny():
    config = km.KimiK2Config.tiny(dtype=jnp.float32, attention_impl="reference")
    params = km.init_params(config, jax.random.key(3))
    seqs = np.random.default_rng(0).integers(1, 256, (SLOTS, 256), dtype=np.int32)
    return config, params, seqs


@pytest.fixture(autouse=True)
def short_walks(monkeypatch):
    """Pieces of 16 rows and groups of 2 heads, so that a prompt of 150
    crosses the row walk and every prefill the head walk."""
    monkeypatch.setattr(km, "PREFILL_ROWS", 16)
    monkeypatch.setattr(km, "PREFILL_HEADS", 2)


def _served_logits(config, params, seqs, lengths, ticks):
    """Prefill each slot at its length (one program a slot, a pad row beside
    it), then ``ticks`` teacher-forced decode ticks of ALL slots in one batch.
    Returns (prefill logits a slot, decode logits [ticks, slot], the prefill
    and the decode counters)."""
    cache = km.init_cache(config, SLOTS, POOL, PAGE)
    prefill = km.make_paged_prefill_fn(config, PAGE)
    table = np.arange(1, 1 + SLOTS * TABLE, dtype=np.int32).reshape(SLOTS, TABLE)
    first, pre_counts = [], []
    for s, n in enumerate(lengths):
        bucket = -(-n // 32) * 32
        toks = np.zeros((2, bucket), np.int32)
        toks[0, :n] = seqs[s, :n]
        pages = np.zeros((2, bucket // PAGE), np.int32)
        pages[0] = table[s, : bucket // PAGE]
        logits, cache, c = prefill(params, cache, jnp.asarray(toks),
                                   jnp.asarray(pages),
                                   jnp.asarray([n, 1], jnp.int32))
        first.append(logits[0])
        pre_counts.append(np.asarray(c))
    one = jax.jit(lambda c, t, p: km.paged_decode_one(
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
    return first, later, pre_counts, counts


def _worst_gap(config, params, seqs, lengths, ticks, params_ref=None):
    """The largest logit difference between the served path and the
    reference's full forward, over the first token and every 9th tick."""
    cfg = _cfg_dict(km.KimiK2Config.tiny())
    first, later, _, _ = _served_logits(config, params, seqs, lengths, ticks)
    worst = 0.0
    for s, n in enumerate(lengths):
        want = ref.reference_logits(
            jax.tree.map(lambda a: a.astype(jnp.float32), params_ref or params),
            jnp.asarray(seqs[s, : n + ticks]), cfg)
        worst = max(worst, float(jnp.max(jnp.abs(first[s] - want[n - 1]))))
        for t in range(ticks):
            if t % 9 == 0 or t == ticks - 1:
                worst = max(worst, float(jnp.max(jnp.abs(
                    later[t][s] - want[n + t]))))
    return worst


# three slots of unequal length in one decode batch: a prompt of 150 (19
# pages, ten row pieces of 16 after its bucket of 160), one shorter than a
# row piece, one that is no page multiple
LENGTHS = (150, 12, 77)


def test_prefill_then_decode_through_the_latent_cache_is_the_reference(tiny):
    config, params, seqs = tiny
    assert _worst_gap(config, params, seqs, LENGTHS, 40) < LOGIT_TOL


def test_programs_count_what_they_attend(tiny):
    config, params, seqs = tiny
    _, _, pre, dec = _served_logits(config, params, seqs, LENGTHS, 2)
    first = dict(zip(km.DECODE_COUNTERS, dec[0].tolist()))
    # 4 layers see every cached row, the tick's own among them
    assert first["attn_rows_latent"] == 4 * sum(n + 1 for n in LENGTHS)
    assert first["moe_assignments"] == 3 * SLOTS * 2  # 3 expert layers, top 2
    assert 0 < first["moe_assignments_held"] <= first["moe_assignments"]
    for n, c in zip(LENGTHS, pre):
        counts = dict(zip(km.PREFILL_COUNTERS, c.tolist()))
        # the prompt and the pad row beside it (one token, one pair)
        assert counts["prefill_rows"] == n + 1
        assert counts["prefill_attn_pairs"] == n * (n + 1) // 2 + 1


def test_a_prefill_skips_the_pieces_past_its_prompt(tiny):
    """One bucket for every length: a prompt of 37 in a bucket of 96 (pieces
    of 16 rows) runs its first three pieces; the rows of the other three are
    never computed, which shows in the cache as exact zeros where a computed
    pad row (37-47, inside the third piece) left values. The last token's
    logits are those of the same prompt in a bucket of its own."""
    config, params, seqs = tiny
    prefill = km.make_paged_prefill_fn(config, PAGE)
    pages = np.arange(1, 13, dtype=np.int32)[None]
    toks = np.zeros((1, 96), np.int32)
    toks[0, :37] = seqs[0, :37]
    logits, cache, counts = prefill(
        params, km.init_cache(config, SLOTS, POOL, PAGE), jnp.asarray(toks),
        jnp.asarray(pages), jnp.asarray([37], jnp.int32))
    assert dict(zip(km.PREFILL_COUNTERS, counts.tolist()))["prefill_rows"] == 37
    for layer in range(config.num_hidden_layers):
        rows = np.asarray(cache.k[0, layer * POOL + pages[0]]).reshape(96, -1)
        assert np.abs(rows[:48, :40]).min() > 0    # computed: real and pad rows
        assert not rows[48:].any()                 # skipped: the pool's zeros
        assert not rows[:, 40:].any()              # the lanes past 32 + 8
    alone, _, _ = prefill(
        params, km.init_cache(config, SLOTS, POOL, PAGE),
        jnp.asarray(toks[:, :48]), jnp.asarray(pages[:, :6]),
        jnp.asarray([37], jnp.int32))
    np.testing.assert_allclose(logits, alone, atol=1e-5)


def test_the_cache_is_one_latent_row_a_token_a_layer(tiny):
    config = km.KimiK2Config()
    cache = jax.eval_shape(lambda: km.init_cache(config, 16, 9, 64))
    assert cache._fields == ("k",)                      # no V pool
    assert cache.k.shape == (1, 61 * 9, 64, 640)        # 576 in whole tiles
    assert config.latent_width * 2 == 1280              # bytes a token a layer
    assert abs(config.softmax_scale - 0.14468) < 1e-5   # 192^-0.5 x 1.4159^2
    # the decode program expands no K and no V: nothing in it is as tall as
    # the cached rows times the heads
    config, params, _ = tiny
    text = jax.jit(lambda c, t, p: km.paged_decode_one(
        params, c, t, p, jnp.ones((SLOTS,), bool),
        jnp.zeros((SLOTS, TABLE), jnp.int32), config, PAGE, False)).lower(
        km.init_cache(config, SLOTS, POOL, PAGE), jnp.zeros((SLOTS,), jnp.int32),
        jnp.zeros((SLOTS,), jnp.int32)).as_text()
    heads, rows = config.num_attention_heads, TABLE * PAGE
    assert f"{SLOTS}x{rows}x128x" in text        # the gathered latent rows
    for wide in (16, 24):  # a head's k (16 + 8) or v (16) over the rows
        assert not re.search(rf"({rows}x{heads}|{heads}x{rows})x{wide}x", text)


def _route_with(bias_in_weights=False, normalised=True):
    def route(x, router, top_k, scale, scoring="sigmoid_bias", groups=None):
        scores = jax.nn.sigmoid(x.astype(jnp.float32) @ router["w"])
        biased = scores + router["bias"]
        _, chosen = jax.lax.top_k(biased, top_k)
        weights = jnp.take_along_axis(biased if bias_in_weights else scores,
                                      chosen, axis=-1)
        if normalised:
            weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        return chosen.astype(jnp.int32), weights * scale
    return route


def _plant(fault, monkeypatch, config, params):
    """-> (config, params) the served path runs with ``fault``."""
    m = 0.1 * np.log(config.rope_scaling["factor"]) + 1.0
    plain = (config.qk_nope_head_dim + config.qk_rope_head_dim) ** -0.5
    cls = km.KimiK2Config
    if fault == "scale_without_m2":
        monkeypatch.setattr(cls, "softmax_scale", property(lambda self: plain))
    elif fault == "m_on_cos_and_sin":
        # Laguna's way: the factor on the tables, so only the ROTATED part of
        # a score carries m^2
        tables = km._rope_tables
        monkeypatch.setattr(cls, "softmax_scale", property(lambda self: plain))
        monkeypatch.setattr(km, "_rope_tables", lambda c, n: tuple(
            t * m for t in tables(c, n)))
    elif fault == "k_r_left_unrotated":
        rope = cache_rows.apply_rope
        monkeypatch.setattr(cache_rows, "apply_rope", lambda x, *a: x
                            if x.shape[-2] == 1 else rope(x, *a))
    elif fault == "c_cached_before_its_norm":
        norm = cache_rows.rms_norm
        monkeypatch.setattr(cache_rows, "rms_norm", lambda x, w, eps: x
                            if x.shape[-1] == config.kv_lora_rank
                            else norm(x, w, eps))
    elif fault == "values_from_the_rotated_columns_too":
        # the values read across the whole stored row: a shifted window of it
        attend = cache_rows.latent_attention_reference
        monkeypatch.setattr(
            cache_rows, "latent_attention_reference",
            lambda q, pool, table, lengths, vw: attend(
                q, pool, table, lengths, pool.shape[-1])[
                    ..., config.qk_rope_head_dim:][..., :vw])
    elif fault == "b_left_in_the_weights":
        monkeypatch.setattr(moe, "route", _route_with(bias_in_weights=True))
    elif fault == "weights_not_normalised":
        monkeypatch.setattr(moe, "route", _route_with(normalised=False))
    elif fault == "shared_expert_scaled":
        mlp = km.swiglu_mlp
        monkeypatch.setattr(
            km, "swiglu_mlp", lambda x, w_gate, w_up, w_down: mlp(
                x, w_gate, w_up, w_down) * (
                config.routed_scaling_factor
                if w_gate.shape[-1] == config.moe_intermediate_size else 1.0))
    elif fault == "dense_layer_given_experts":
        dense = params["dense_layers"][0]
        first = jax.tree.map(lambda a: a[0], params["layers"])
        swapped = {**{k: v for k, v in dense.items() if k != "mlp"},
                   **{k: first[k] for k in ("router", "experts", "shared")}}
        params = {**params, "dense_layers": [swapped]}
    elif fault == "bfloat16_for_float32":
        config = dataclasses.replace(config, dtype=jnp.bfloat16)
        params = jax.tree.map(lambda a: a.astype(jnp.bfloat16)
                              if a.dtype == jnp.float32 and a.ndim > 1 else a,
                              params)
    else:
        raise KeyError(fault)
    return config, params


FAULTS = ("scale_without_m2", "m_on_cos_and_sin", "k_r_left_unrotated",
          "c_cached_before_its_norm", "values_from_the_rotated_columns_too",
          "b_left_in_the_weights", "weights_not_normalised",
          "shared_expert_scaled", "dense_layer_given_experts",
          "bfloat16_for_float32")


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_fails_the_tolerance(tiny, fault, monkeypatch):
    """Each departure from the published layer, and the precision below the
    one stated, is another result by far more than ``LOGIT_TOL``: the
    comparison above would not pass with it."""
    config, params, seqs = tiny
    served_config, served = _plant(fault, monkeypatch, config, params)
    gap = _worst_gap(served_config, served, seqs, LENGTHS, 12,
                     params_ref=params)
    assert gap > 50 * LOGIT_TOL, gap


def test_engine_serves_the_family_through_its_normal_path(tiny):
    """``LLMEngine`` over a ``KimiK2Config``: the same admission, allocator
    and phases; the tokens it emits are the reference's choices (teacher
    forced: gap 0 up to float32 rounding), and ``stats()`` has the latent
    pool's one side and the attended rows and pairs."""
    from benchmarks.harness import reference as href
    from ray_tpu.serve.llm import LLMEngine, model_presets

    config, params, seqs = tiny
    assert isinstance(model_presets()["kimi_k2_tiny"](), km.KimiK2Config)
    engine = LLMEngine(config, params, num_slots=4, max_seq_len=192,
                       decode_chunk=4, prefill_buckets=[32, 96, 160],
                       page_size=PAGE)
    try:
        prompts = [seqs[0, :150].tolist(), seqs[1, :12].tolist(),
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
    # ONE pool: 4 layers x the stored row (40 values in a tile of 128) x 4 B
    assert stats["kv_bytes_per_token"] == 4 * 128 * 4
    assert stats["state_bytes"] == stats["state_slots"] == 0
    assert stats["kv_pages_total"] == 4 * 24 and stats["kv_pages_in_use"] == 0
    assert stats["attn_rows_latent"] > 0 and stats["attn_rows_full"] == 0
    assert stats["prefill_rows"] >= 150 + 12 + 77
    assert stats["prefill_attn_pairs"] >= sum(
        n * (n + 1) // 2 for n in (150, 12, 77))
    assert stats["moe_assignments"] > 0
