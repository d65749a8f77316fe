"""The Ling-3.0 hybrid family (``models/ling_hybrid.py``) on the CPU at tiny
sizes: the served path through BOTH kinds of cache (latent rows in pages for
the MLA layers, delta-rule state and convolution rows by slot for the KDA
layers) against the plain float32 reference
(``benchmarks/families/ling_hybrid_reference.py``, which imports nothing of
the program), the kernels interpreted; the share of the experts; and planted
faults that the comparison has to see."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import ling_hybrid_reference as ref
from ray_tpu.models import ling_hybrid as lh
from ray_tpu.ops import kda
from ray_tpu.serve.llm import LLMEngine, model_presets

PAGE = 8


def _cfg(config):
    """The reference's dict of a program configuration."""
    keys = ("vocab_size", "hidden_size", "num_hidden_layers",
            "first_k_dense_replace", "layer_group_size", "num_attention_heads",
            "head_dim", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "short_conv_kernel_size", "kda_lower_bound",
            "num_experts", "n_router_outputs", "num_experts_per_tok",
            "n_group", "topk_group", "routed_scaling_factor", "rms_norm_eps",
            "rope_theta")
    return {**{k: getattr(config, k) for k in keys},
            "held_experts": list(config.held_experts)}


@pytest.fixture(scope="module")
def tiny():
    config = lh.LingHybridConfig.tiny(
        dtype=jnp.float32, attention_impl="reference",
        kda_impl="pallas_interpret")
    params = lh.init_params(config, jax.random.key(5))
    tokens = np.random.default_rng(0).integers(1, 256, 200, dtype=np.int32)
    return config, params, tokens


def test_ling_preset_and_layer_kinds():
    config = model_presets()["ling_hybrid_tiny"]()
    assert [config.is_mla(i) for i in range(6)] == [
        False, False, True, False, False, True]
    assert (config.count(True), config.count(False)) == (2, 4)
    whole = lh.LingHybridConfig()
    assert [i for i in range(42) if whole.is_mla(i)] == [5, 11, 17, 23, 29, 35, 41]
    assert whole.latent_width == 640 and abs(whole.softmax_scale - 192 ** -0.5) < 1e-9
    with pytest.raises(ValueError, match="e\\^80"):
        lh.LingHybridConfig.tiny(kda_lower_bound=-6.0)
    with pytest.raises(ValueError, match="held_experts"):
        lh.LingHybridConfig.tiny(held_experts=(0, 3))
    cache = jax.eval_shape(lambda: lh.init_cache(config, 3, 17, PAGE))
    assert cache.k.shape == (1, 2 * 17, PAGE, 128)
    assert cache.kda.shape == (4, 4, 4, 16, 16) and cache.kda.dtype == jnp.float32
    assert cache.conv.shape == (4, 4, 3, 3 * 64)


def _prefill_then_decode(config, params, tokens, n, steps, monkeypatch, piece=64):
    """One prompt of ``n`` tokens through a bucket of 192 in pieces of
    ``piece`` rows into slot 1, then ``steps`` teacher-forced ticks.
    Returns logits [1 + steps, V]."""
    monkeypatch.setattr(lh, "PREFILL_ROWS", piece)
    bucket, slots = 192, 2
    cache = lh.init_cache(config, slots, 97, PAGE)
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :n] = tokens[:n]
    pages = np.arange(1, 1 + bucket // PAGE, dtype=np.int32)[None]
    logits, cache, counts = lh.make_paged_prefill_fn(config, PAGE)(
        params, cache, jnp.asarray(padded), jnp.asarray(pages),
        jnp.asarray([n]), jnp.asarray([1]))
    assert counts.shape == (len(lh.PREFILL_COUNTERS),)
    assert counts[2] == n and counts[4] == n * config.count(False)
    out = [logits[0]]
    table = np.zeros((slots, 32), np.int32)
    table[1, :24] = pages[0]
    table[1, 24:] = np.arange(50, 58)
    active = jnp.asarray([False, True])
    for i in range(steps):
        tok = np.zeros((slots,), np.int32)
        pos = np.zeros((slots,), np.int32)
        tok[1], pos[1] = tokens[n + i], n + i
        step, cache, c = lh.paged_decode_one(
            params, cache, jnp.asarray(tok), jnp.asarray(pos), active,
            jnp.asarray(table), config, PAGE, False)
        assert c[-1] == config.count(False)       # one live slot a KDA layer
        assert c[-2] == config.count(True) * (n + i + 1)
        out.append(step[1])
    return jnp.stack(out)


def test_ling_prefill_over_three_pieces_and_decode_match_the_reference(
        tiny, monkeypatch):
    """A prompt of 150 tokens spans three pieces of 64 (the delta-rule state
    and the convolution's rows carried across them, the MLA layers' flash
    call over the rows so far), then 12 ticks through state and latent rows:
    the logits are the reference's full forward's."""
    config, params, tokens = tiny
    got = _prefill_then_decode(config, params, tokens, 150, 12, monkeypatch)
    want = ref.reference_logits(params, jnp.asarray(tokens[:162]), _cfg(config))
    np.testing.assert_allclose(got, want[149:162], atol=2e-4)


def test_ling_fallback_recurrence_runs_the_same_function(tiny, monkeypatch):
    config, params, tokens = tiny
    plain = dataclasses.replace(config, kda_impl="reference")
    got = _prefill_then_decode(plain, params, tokens, 70, 3, monkeypatch)
    want = ref.reference_logits(params, jnp.asarray(tokens[:73]), _cfg(config))
    np.testing.assert_allclose(got, want[69:73], atol=2e-4)


def test_ling_two_requests_through_one_slot_need_no_clearing(tiny):
    """ONE slot: the second request's prefill overwrites what the first left
    in the slot's state and convolution rows, and its tokens are the
    reference's choices; the engine reports both caches."""
    config, params, tokens = tiny
    engine = LLMEngine(config, params, num_slots=1, max_seq_len=256,
                       decode_chunk=4, prefill_buckets=[128], page_size=PAGE)
    try:
        stats = engine.stats()
        assert stats["kv_bytes_per_token"] == 2 * 128 * 4   # two MLA layers
        assert stats["state_slots"] == 1
        assert stats["state_bytes"] == 2 * 4 * (4 * 16 * 16 * 4 + 3 * 192 * 4)
        for prompt in (tokens[:90].tolist(), tokens[100:170].tolist()):
            out = engine.generate(tokens=prompt, max_tokens=9, eos_token=None,
                                  timeout=600)["tokens"]
            logits = ref.reference_logits(
                params, jnp.asarray(prompt + out), _cfg(config))
            rows = logits[len(prompt) - 1:len(prompt) + 8]
            gap = jnp.max(rows, axis=-1) - jnp.take_along_axis(
                rows, jnp.asarray(out)[:, None], axis=-1)[:, 0]
            assert len(out) == 9 and float(gap.max()) < 1e-4
        stats = engine.stats()
        assert stats["kda_rows"] >= 160 * 4 and stats["kda_state_updates"] > 0
        assert stats["attn_rows_latent"] > 0 and stats["prefill_rows"] >= 160
    finally:
        engine.stop()


def test_ling_shares_of_the_experts_add_up_to_the_uncut_layer(tiny):
    """The four shares of a layer's 8 experts (2 a share, a router group
    each), the shared expert counted once, add up to the reference's uncut
    layer: program and reference alike."""
    config, _params, _ = tiny
    whole = dataclasses.replace(config, num_experts=8, held_experts=(0, 8))
    params = lh.init_params(whole, jax.random.key(9))
    lp = params["layers"][1]
    y = jax.random.normal(jax.random.key(1), (48, 64), jnp.float32)
    cfg = _cfg(whole)
    uncut = ref.routed_sum(lp, y, cfg, None, held=[0, 8])
    shared = ref._swiglu(y, ref._f32(lp["shared"]), None)
    parts, served = [], []
    for lo in range(0, 8, 2):
        share = dataclasses.replace(whole, num_experts=2,
                                    held_experts=(lo, lo + 2))
        held = {**lp, "experts": jax.tree.map(lambda a: a[lo:lo + 2],
                                              lp["experts"])}
        parts.append(ref.routed_sum(held, y, cfg, None, held=[lo, lo + 2]))
        out, _ = lh._ffn(share, held, y, "ragged", jnp.ones((48,), bool))
        served.append(out - lh.swiglu_mlp(y, **lp["shared"]))
    np.testing.assert_allclose(sum(parts), uncut, atol=1e-5)
    np.testing.assert_allclose(sum(served) + shared, uncut + shared, atol=1e-4)
    # the limit is real at this size: some token's free top 2 is not its choice
    chosen, _ = ref.routing(lp, y, cfg)
    free, _ = ref.routing(lp, y, {**cfg, "n_group": 1, "topk_group": 1})
    assert bool(jnp.any(jnp.sort(chosen, -1) != jnp.sort(free, -1)))


def _decay_after_the_write(q, k, v, a, beta, state0):
    """``kda_recurrence`` with the decay applied AFTER the delta step."""
    def step(state, part):
        qt, kt, vt, at, bt = part
        seen = jnp.einsum("bhk,bhkv->bhv", kt, state)
        state = state + kt[..., None] * (bt[..., None] * (vt - seen))[..., None, :]
        state = jnp.exp(at)[..., None] * state
        return state, jnp.einsum("bhk,bhkv->bhv", qt, state)

    parts = [jnp.moveaxis(t.astype(jnp.float32), 1, 0) for t in (q, k, v, a, beta)]
    state, o = jax.lax.scan(step, state0.swapaxes(-1, -2), parts)
    return jnp.moveaxis(o, 0, 1), state.swapaxes(-1, -2)


FAULTS = ("decay_after_the_write", "conv_rows_not_carried", "no_group_limit",
          "mla_gate_left_out", "q_not_scaled")


@pytest.mark.parametrize("fault", FAULTS)
def test_ling_planted_faults_are_seen(tiny, monkeypatch, fault):
    """Each fault moves the logits by far more than the sound program's
    float32 noise (2e-4 above)."""
    config, params, tokens = tiny
    config = dataclasses.replace(config, kda_impl="reference")
    if fault == "decay_after_the_write":
        # (the jitted entry points keep their traces: plant it in front)
        def prefill(q, k, v, a, beta, state0, lengths, impl):
            real = (jnp.arange(q.shape[1])[None] < lengths[:, None])[..., None]
            return _decay_after_the_write(
                q, k, v, jnp.where(real[..., None], a, 0.0),
                jnp.where(real, beta, 0.0), state0)

        def step(q, k, v, a, beta, state, layer, impl):
            o, moved = _decay_after_the_write(
                q[:, None], k[:, None], v[:, None], a[:, None], beta[:, None],
                state[layer, :q.shape[0]])
            return o[:, 0], state.at[layer, :q.shape[0]].set(moved)

        monkeypatch.setattr(kda, "kda_prefill", prefill)
        monkeypatch.setattr(kda, "kda_step", step)
    elif fault == "conv_rows_not_carried":
        real = lh.ssm.causal_conv_prefill
        monkeypatch.setattr(lh.ssm, "causal_conv_prefill", lambda x, w, b, n: (
            real(x.at[:, :3].set(0), w, b, n)))
    elif fault == "no_group_limit":
        config = dataclasses.replace(config, n_group=1, topk_group=1)
    elif fault == "mla_gate_left_out":
        monkeypatch.setattr(jax.nn, "sigmoid", lambda x: jnp.ones_like(x)
                            if x.shape[-1] == config.num_attention_heads
                            and x.ndim == 2 and x.shape[0] > 8
                            else jax.lax.logistic(x))
    elif fault == "q_not_scaled":
        monkeypatch.setattr(lh, "_kda_qkv", (lambda f: lambda c, x: (
            lambda q, k, v: (q * c.head_dim ** 0.5, k, v))(*f(c, x)))(lh._kda_qkv))
    got = _prefill_then_decode(config, params, tokens, 150, 2, monkeypatch)
    want = ref.reference_logits(params, jnp.asarray(tokens[:152]),
                                _cfg(tiny[0]))
    assert float(jnp.max(jnp.abs(got - want[149:152]))) > 5e-3
