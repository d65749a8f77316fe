"""The Olmo-Hybrid family on the training path (``models/olmo_hybrid.py``
through ``train/step.py``) against its plain float32 reference
(``benchmarks/families/olmo_hybrid_reference.py``, which imports nothing of
the program and runs the delta rule A TOKEN A STEP) at ``tiny()`` widths: two
periods deep (L L L F L L L F), key 24 / value 48 (0.75 and 1.5 of a tile of
32, as 96 / 192 are of 128), 3 heads, a sequence of 80 tokens (a whole chunk
of 64 and a part of one), the chunk kernels forward AND reverse and the flash
kernels in interpret mode.

Tolerances. Both sides are float32 with ``highest`` products, so what
separates them is the order of the sums (the chunked rule against the
recurrence, the flash kernel against the masked softmax): the worst leaf of
the gradient (``q_norm``) reads 3.3e-5 of its norm apart, the loss 5e-7
(measured when the test was written). ``TOL`` = 3e-4 leaves that an order of
room. The gradient of this block is SENSITIVE: eight layers deep, the
reference with bfloat16 inputs to its products (``quant="bf16"``: what
computing below the stated precision would read) moves every leaf but the
head's by 0.7 to 2.1 of its norm (a relative 1e-3 on ``w_qkv`` alone moves
four linear layers' gradient by 0.06), so the lower precision fails ``TOL``
by three orders, and every planted fault below by two or more."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import olmo_hybrid_reference as ref
from ray_tpu.models import olmo_hybrid as oh

TOL = 3e-4
WIDE = 100  # bfloat16, or a planted fault, fails TOL by this factor or more


def _cfg(config, **changed):
    """The source's key names, as a configuration file gives the reference."""
    return {**dict(
        num_hidden_layers=len(config.layer_types),
        layer_types=list(config.layer_types),
        num_attention_heads=config.num_attention_heads,
        linear_num_value_heads=config.linear_num_value_heads,
        linear_key_head_dim=config.linear_key_head_dim,
        linear_value_head_dim=config.linear_value_head_dim,
        linear_allow_neg_eigval=config.linear_allow_neg_eigval,
        rms_norm_eps=config.rms_norm_eps), **changed}


@pytest.fixture(scope="module")
def setup():
    config = oh.OlmoHybridConfig.tiny(
        dtype=jnp.float32, attention_impl="flash_interpret",
        gdn_impl="pallas_interpret")
    params = oh.init_params(config, jax.random.key(1))
    seqs = np.random.default_rng(0).integers(0, 256, (2, 81), dtype=np.int32)
    return config, params, seqs[:, :-1], seqs[:, 1:]


def _program(config, params, tokens, targets):
    with jax.default_matmul_precision("highest"):
        (loss, counted), grads = jax.jit(jax.value_and_grad(
            lambda p: oh.loss_and_counters(p, tokens, targets, config),
            has_aux=True))(params)
    return float(loss), grads, counted


def _reference(cfg, params, tokens, targets, quant=None):
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: ref.reference_loss(p, tokens, targets, cfg, quant)))(params)
    return float(loss), grads


def _worst(got, want):
    """(the worst leaf's |got - want| / |want|, its path)."""
    flat = zip(jax.tree.flatten_with_path(got)[0], jax.tree.leaves(want))
    return max((float(jnp.linalg.norm(a.astype(jnp.float32) - b)
                      / jnp.linalg.norm(b)), jax.tree_util.keystr(path))
               for (path, a), b in flat)


@pytest.fixture(scope="module")
def sound(setup):
    config, params, tokens, targets = setup
    return (_program(config, params, tokens, targets),
            _reference(_cfg(config), params, tokens, targets))


def test_loss_and_every_gradient_leaf_match_the_reference(setup, sound):
    config, params, tokens, _ = setup
    (loss, grads, _), (ref_loss, ref_grads) = sound
    assert abs(loss - ref_loss) < TOL
    assert jax.tree.structure(grads) == jax.tree.structure(params)
    for (path, a), b in zip(jax.tree.flatten_with_path(grads)[0],
                            jax.tree.leaves(ref_grads)):
        err = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
        assert float(jnp.linalg.norm(b)) > 1e-4 and err < TOL, (path, err)
    assert tokens.shape[1] % 64 and tokens.shape[1] > 64


def test_bfloat16_where_float32_is_stated_fails_the_tolerance(setup, sound):
    """The tolerance is tight enough: the reference with every product's
    inputs rounded to bfloat16 is what a program in the lower precision would
    read, and it is two orders outside."""
    config, params, tokens, targets = setup
    _, rounded = _reference(_cfg(config), params, tokens, targets, "bf16")
    err, path = _worst(rounded, sound[1][1])
    assert err > WIDE * TOL, (err, path)


def test_the_layers_follow_layer_types(setup):
    """The pattern is read from ``layer_types``: the published one is three
    linear layers and a full one to a period; another pattern builds another
    block, and its loss is another's."""
    config, params, tokens, targets = setup
    assert config.period == (oh.LINEAR,) * 3 + (oh.FULL,)
    assert oh.OlmoHybridConfig().layer_types == config.period * 8
    assert params["linear"]["w_qkv"].shape[0] == 6
    assert params["full"]["wq"].shape[0] == 2
    other = oh.OlmoHybridConfig.tiny(
        dtype=jnp.float32, layer_types=(oh.LINEAR, oh.FULL) * 2,
        attention_impl="reference", gdn_impl="reference")
    assert other.period == (oh.LINEAR, oh.FULL)
    other_params = oh.init_params(other, jax.random.key(1))
    got = _program(other, other_params, tokens, targets)
    want = _reference(_cfg(other), other_params, tokens, targets)
    assert abs(got[0] - want[0]) < TOL
    err, path = _worst(got[1], want[1])
    assert err < TOL, (err, path)
    with pytest.raises(ValueError, match="layer types"):
        oh.OlmoHybridConfig.tiny(layer_types=("sliding_attention",) * 4)


def test_the_vocabulary_is_the_slice_held(setup):
    """``vocab_size`` is the rows held: embedding and head have that many,
    the loss is over them (a uniform head reads ln of the slice)."""
    config, params, tokens, targets = setup
    assert params["embed_tokens"].shape == (256, config.hidden_size)
    assert params["lm_head"].shape == (config.hidden_size, 256)
    flat = {**params, "lm_head": jnp.zeros_like(params["lm_head"])}
    value = oh.loss(flat, tokens, targets, config)
    assert abs(float(value) - np.log(256)) < 1e-5
    assert oh.forward(params, tokens, config).shape == (2, 80, 256)


def test_the_counters_are_a_hand_count(setup, sound):
    """``gdn_decay_mean`` and ``gdn_beta_over_one_share`` of the FIRST linear
    layer by hand from the embedding (its input), and the step's output
    holds both names beside the loss."""
    config, params, tokens, targets = setup
    counted = sound[0][2]
    assert set(counted) == set(oh.GDN_COUNTERS)
    one = oh.OlmoHybridConfig.tiny(
        dtype=jnp.float32, layer_types=(oh.LINEAR,),
        attention_impl="reference", gdn_impl="reference")
    first = jax.tree.map(lambda a: a[:1], params["linear"])
    _, got = oh.loss_and_counters(
        {**params, "linear": first,
         "full": jax.tree.map(lambda a: a[:0], params["full"])},
        tokens, targets, one)
    x = np.asarray(params["embed_tokens"])[tokens].astype(np.float64)
    ab = x @ np.asarray(first["w_ab"][0], np.float64)
    heads = config.linear_num_value_heads
    g = -np.exp(np.asarray(first["a_log"][0], np.float64)) * np.logaddexp(
        0.0, ab[..., :heads] + np.asarray(first["dt_bias"][0], np.float64))
    np.testing.assert_allclose(float(got["gdn_decay_mean"]),
                               np.exp(g).mean(), rtol=1e-5)
    np.testing.assert_allclose(float(got["gdn_beta_over_one_share"]),
                               (ab[..., heads:] > 0).mean(), atol=1e-6)
    assert 0.0 < float(counted["gdn_decay_mean"]) < 1.0
    assert 0.0 < float(counted["gdn_beta_over_one_share"]) < 1.0

    from ray_tpu.train.step import (
        TrainState, default_optimizer, make_train_step)
    quick = oh.OlmoHybridConfig.tiny(
        dtype=jnp.float32, attention_impl="reference", gdn_impl="reference")
    opt = default_optimizer(warmup_steps=10, total_steps=1000)
    state = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                       opt_state=opt.init(params))
    _, out = make_train_step(quick, opt, donate=False)(state, tokens, targets)
    assert set(out) == {"loss", "grad_norm", "step", *oh.GDN_COUNTERS}
    assert abs(float(out["loss"]) - sound[1][0]) < TOL


@pytest.mark.parametrize("fault", ["beta_not_doubled", "decay_ignored",
                                   "conv_not_causal", "norm_before_mixer"])
def test_a_planted_fault_fails_wide(setup, sound, fault, monkeypatch):
    """The comparison sees what it should: each fault, planted in the
    PROGRAM (or, for the doubled beta, told to the reference), moves some
    gradient leaf ``WIDE`` x ``TOL`` or more."""
    config, params, tokens, targets = setup
    quick = oh.OlmoHybridConfig.tiny(
        dtype=jnp.float32, attention_impl="reference", gdn_impl="reference")
    if fault == "beta_not_doubled":
        got = _reference(_cfg(config, linear_allow_neg_eigval=False), params,
                         tokens, targets)[1]
    else:
        if fault == "decay_ignored":
            real = oh.gdn_chunk
            monkeypatch.setattr(oh, "gdn_chunk", lambda q, k, v, g, beta, **kw:
                                real(q, k, v, jnp.zeros_like(g), beta, **kw))
        elif fault == "conv_not_causal":
            monkeypatch.setattr(oh, "_short_conv", lambda x, taps: sum(
                jnp.roll(x, -j, axis=1) * taps[j] for j in range(taps.shape[0])))
        else:
            real_norm = oh.rms_norm
            monkeypatch.setattr(
                oh, "_linear_mixer", lambda c, x, lp, real=oh._linear_mixer:
                real(c, real_norm(x, lp["mixer_norm"], c.rms_norm_eps), lp))
        got = _program(quick, params, tokens, targets)[1]
    err, path = _worst(got, sound[1][1])
    assert err > WIDE * TOL, (fault, err, path)
