"""The Mellum family on the training path (``models/mellum.py`` through
``train/step.py``) against its plain float32 reference
(``benchmarks/families/mellum_reference.py``, which imports nothing of the
program) at ``tiny()`` widths: two periods deep (S S S F S S S F), a sequence
of 80 tokens over a window of 24 (longer than two windows, and than YaRN's
original 32), the flash kernels in interpret mode so that the backward under
a window is the Pallas one, the expert product on its compacted path (4 of
16 held).

Tolerances. Both sides are float32 with ``highest`` products, so what
separates them is the order of the sums: every leaf of the gradient reads
1e-6 of its norm apart, the loss 1e-6 (measured when the test was written).
``TOL`` = 1e-4 leaves that two orders of room, and every planted fault below
moves some leaf by 2e-2 or more: two orders beyond it."""
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import mellum_reference as ref
from ray_tpu.models import mellum as ml
from ray_tpu.ops import moe

TOL = 1e-4
WIDE = 100  # a planted fault fails TOL by this factor or more


def _cfg(config):
    """The source's key names, as a configuration file gives the reference."""
    return dict(
        num_attention_heads=config.num_attention_heads,
        num_key_value_heads=config.num_key_value_heads,
        head_dim=config.head_dim, rms_norm_eps=config.rms_norm_eps,
        layer_types=list(config.layer_types),
        mlp_layer_types=list(config.mlp_layer_types),
        sliding_window=config.sliding_window,
        rope_parameters={k: dict(v) for k, v in config.rope_parameters.items()},
        num_experts_per_tok=config.num_experts_per_tok,
        held_experts=list(config.held_experts))


@pytest.fixture(scope="module")
def setup():
    config = ml.MellumConfig.tiny(dtype=jnp.float32,
                                  attention_impl="flash_interpret")
    params = ml.init_params(config, jax.random.key(1))
    seqs = np.random.default_rng(0).integers(0, 256, (2, 81), dtype=np.int32)
    return config, params, seqs[:, :-1], seqs[:, 1:]


def _program(config, params, tokens, targets):
    with jax.default_matmul_precision("highest"):
        (loss, counted), grads = jax.value_and_grad(
            lambda p: ml.loss_and_counters(p, tokens, targets, config),
            has_aux=True)(params)
    return float(loss), grads, counted["expert_counts"]


def _reference(cfg, params, tokens, targets, quant=None):
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: ref.reference_loss(p, tokens, targets, cfg, quant)))(params)
    return float(loss), grads


def _worst(got, want):
    """(the worst leaf's |got - want| / |want|, its path)."""
    flat = zip(jax.tree.flatten_with_path(got)[0], jax.tree.leaves(want))
    errs = [(float(jnp.linalg.norm(a.astype(jnp.float32) - b)
                   / jnp.linalg.norm(b)), jax.tree_util.keystr(path))
            for (path, a), b in flat]
    return max(errs)


@pytest.fixture(scope="module")
def sound(setup):
    config, params, tokens, targets = setup
    return (_program(config, params, tokens, targets),
            _reference(_cfg(config), params, tokens, targets))


def test_loss_and_every_gradient_leaf_match_the_reference(setup, sound):
    config, params, tokens, _ = setup
    (loss, grads, counts), (ref_loss, ref_grads) = sound
    assert abs(loss - ref_loss) < TOL
    assert jax.tree.structure(grads) == jax.tree.structure(params)
    for (path, a), b in zip(jax.tree.flatten_with_path(grads)[0],
                            jax.tree.leaves(ref_grads)):
        err = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
        assert float(jnp.linalg.norm(b)) > 1e-3 and err < TOL, (path, err)
    assert config.period == (ml.SLIDING,) * 3 + (ml.FULL,)
    assert tokens.shape[1] > 2 * config.sliding_window
    assert tokens.shape[1] > config.rope_parameters[ml.FULL][
        "original_max_position_embeddings"]


def test_expert_counts_are_a_hand_count(setup, sound):
    """What the step counts beside its loss. Over the eight layers: what the
    sums must be. Layer 0 alone (the model cut to it, the same weights): a
    count by hand from the REFERENCE's routing of the reference's hidden
    states, a bincount of the choices that fall on experts 0-3."""
    config, params, tokens, _ = setup
    counts = np.asarray(sound[0][2])
    layers, t, k = len(config.layer_types), tokens.size, config.num_experts_per_tok
    assert counts.shape == (len(ml.EXPERT_COUNTS),) and counts.dtype == np.int32
    assert counts[0] == layers * t * k                   # every routed choice
    assert 0 < counts[1] < counts[0] and counts[4] == 0  # a quarter, one block
    assert counts[2] <= layers * config.num_experts and counts[3] <= counts[1]
    # layer 0 by hand: rms(embedding) -> the reference's router -> held 0..3
    one = ml.MellumConfig.tiny(
        dtype=jnp.float32, attention_impl="reference",
        layer_types=config.layer_types[:1], mlp_layer_types=("sparse",))
    cut = {**params, "layers": jax.tree.map(lambda a: a[:1], params["layers"])}
    _, _, first = _program(one, cut, tokens, tokens)
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    attn = {n: lp[n] for n in ("wq", "wk", "wv", "wo")}
    rope = ref.rope_table(one.rope_parameters[ml.SLIDING], one.head_dim,
                          tokens.shape[1])
    with jax.default_matmul_precision("highest"):
        rows = []
        for row in np.asarray(tokens):
            h = jnp.asarray(np.asarray(params["embed_tokens"])[row])
            u = ref._rms(h, lp["attn_norm"], 1e-6)
            h = h + ref._attention(attn, u, _cfg(one), rope,
                                   one.sliding_window, None, 256)
            chosen, _ = ref.routing(
                lp["router"], ref._rms(h, lp["mlp_norm"], 1e-6), _cfg(one))
            rows.append(np.asarray(chosen))
    chosen = np.concatenate(rows).reshape(-1)
    load = np.bincount(chosen[chosen < 4], minlength=4)
    assert first.tolist() == [t * k, int(load.sum()), int((load > 0).sum()),
                              int(load.max()), 0]


def test_ten_steps_of_the_real_train_step_lower_the_loss(setup):
    """``make_train_step`` with the family's own loss (found from the
    configuration's module), AdamW, one batch repeated; the step's output
    carries what the loss counted."""
    from ray_tpu.train.step import (
        default_optimizer, make_train_state_factory, make_train_step)

    config, _, tokens, targets = setup
    config = ml.MellumConfig.tiny(dtype=jnp.float32, attention_impl="reference")
    opt = default_optimizer(lr=3e-3, warmup_steps=2, total_steps=100)
    state = make_train_state_factory(config, opt)(jax.random.key(1))
    step = make_train_step(config, opt, donate=False)
    losses = []
    for _ in range(10):
        state, out = step(state, tokens, targets)
        losses.append(float(out["loss"]))
    assert set(out) == {"loss", "grad_norm", "step", "expert_counts"}
    assert out["expert_counts"].shape == (5,) and int(out["step"]) == 10
    assert all(np.isfinite(losses)) and losses[-1] < losses[0] - 0.3, losses


# --------------------------------------------------------------------------- #
# planted faults: each fails TOL by WIDE or more
# --------------------------------------------------------------------------- #
def _assert_fails_wide(got, want, what):
    loss_gap = abs(got[0] - want[0])
    err, path = _worst(got[1], want[1])
    assert max(loss_gap, err) > WIDE * TOL, (what, loss_gap, err, path)


def _reference_with(setup, **changed):
    config, params, tokens, targets = setup
    return _reference({**_cfg(config), **changed}, params, tokens, targets)


def test_fault_the_window_off_by_one(setup, sound):
    _assert_fails_wide(sound[0], _reference_with(setup, sliding_window=25),
                       "window + 1")


def test_fault_yarn_factor_left_off_k(setup, sound, monkeypatch):
    """The reference rotates k with cos and sin WITHOUT the attention factor
    (a table's cos at position 0 is the factor itself: 1 on a sliding
    layer)."""
    config = setup[0]
    rotate = ref._rotate

    def off_k(x, cos, sin):
        if x.shape[1] == config.num_key_value_heads:
            cos, sin = cos / cos[0, 0], sin / cos[0, 0]
        return rotate(x, cos, sin)

    monkeypatch.setattr(ref, "_rotate", off_k)
    _assert_fails_wide(sound[0], _reference_with(setup), "no factor on k")


def test_fault_yarn_on_a_sliding_layer(setup, sound):
    rope = dict(_cfg(setup[0])["rope_parameters"])
    rope[ml.SLIDING] = rope[ml.FULL]
    _assert_fails_wide(sound[0], _reference_with(setup, rope_parameters=rope),
                       "yarn on sliding layers")


def test_fault_weights_not_normalised_over_the_chosen(setup, sound, monkeypatch):
    def raw(router, u, cfg):
        p = jax.nn.softmax(jnp.matmul(u, router, precision="highest"), axis=-1)
        weights, chosen = jax.lax.top_k(p, cfg["num_experts_per_tok"])
        return chosen, weights

    monkeypatch.setattr(ref, "routing", raw)
    _assert_fails_wide(sound[0], _reference_with(setup), "unnormalised weights")


def test_fault_the_gradient_to_the_router_cut(setup, sound, monkeypatch):
    """``stop_gradient`` on the chosen weights in the PROGRAM: the loss is
    unmoved and the router's leaf (and what flows on through it) is not."""
    route = moe.route

    def cut(*args, **kw):
        chosen, weights = route(*args, **kw)
        return chosen, jax.lax.stop_gradient(weights)

    monkeypatch.setattr(moe, "route", cut)
    got = _program(*setup)
    assert abs(got[0] - sound[1][0]) < TOL
    err = float(jnp.linalg.norm(got[1]["layers"]["router"]))
    assert err == 0.0
    _assert_fails_wide(got, sound[1], "router gradient cut")


def test_fault_scale_as_lagunas(setup, sound, monkeypatch):
    routed = ml.routed_experts
    monkeypatch.setattr(ml, "routed_experts",
                        lambda *a, **kw: routed(*a, **{**kw, "scale": 2.5}))
    _assert_fails_wide(_program(*setup), sound[1], "scale 2.5")


def test_fault_bfloat16_for_float32(setup, sound):
    config, params, tokens, targets = setup
    low = ml.MellumConfig.tiny(dtype=jnp.bfloat16,
                               attention_impl="flash_interpret")
    cast = jax.tree.map(
        lambda a: a if a.shape[-1] == config.n_router_outputs and a.ndim == 3
        else a.astype(jnp.bfloat16), params)
    _assert_fails_wide(_program(low, cast, tokens, targets), sound[1], "bfloat16")


@pytest.fixture
def several_blocks(monkeypatch):
    """Blocks of 80 rows for some 170 held assignments a layer: three trips
    of the compacted product, forward and reverse."""
    monkeypatch.setattr(moe, "RAGGED_TILE", 8)
    monkeypatch.setattr(moe, "COMPACT_SLACK", 0.5)


def test_a_product_of_several_blocks_is_dropless_both_ways(setup, sound,
                                                           several_blocks):
    got = _program(*setup)
    assert got[2][4] >= len(setup[0].layer_types)  # blocks beyond the first
    assert abs(got[0] - sound[1][0]) < TOL and _worst(got[1], sound[1][1])[0] < TOL


def test_fault_a_block_dropped_in_the_backward_only(setup, sound, monkeypatch,
                                                    several_blocks):
    """The reverse pass plans one block too few: its held assignments end at
    the last whole block. The loss is the forward's and unmoved."""
    plan = moe._block_plan
    cap = moe._capacity(160 * 4, 4, 16)

    def short(local, n):
        order, starts, ends = plan(local, n)
        if sys._getframe(1).f_code.co_name == "_compacted_bwd":
            ends = jnp.minimum(ends, (ends[-1] - 1) // cap * cap)
            starts = jnp.minimum(starts, ends)
        return order, starts, ends

    monkeypatch.setattr(moe, "_block_plan", short)
    got = _program(*setup)
    assert abs(got[0] - sound[1][0]) < TOL
    _assert_fails_wide(got, sound[1], "a backward block dropped")


# --------------------------------------------------------------------------- #
# the share is a share of the model
# --------------------------------------------------------------------------- #
def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_reference(monkeypatch):
    """16 routed experts, four shares of 4: the expert layer's partial sums
    over held ranges that cover every expert add up to the UNCUT reference's
    whole layer, and so do the gradients to x and to the router's matrix;
    each expert's matrices get their gradient from their own share alone."""
    monkeypatch.setattr(moe, "RAGGED_TILE", 8)  # 384 choices, blocks of 192
    h, f, r, k, t = 64, 32, 16, 4, 96
    keys = jax.random.split(jax.random.key(5), 6)
    x = jax.random.normal(keys[0], (t, h), jnp.float32)
    router = jax.random.normal(keys[1], (h, r), jnp.float32) * h ** -0.5
    experts = {"w_gate": jax.random.normal(keys[2], (r, h, f)) * h ** -0.5,
               "w_up": jax.random.normal(keys[3], (r, h, f)) * h ** -0.5,
               "w_down": jax.random.normal(keys[4], (r, f, h)) * f ** -0.5}
    probe = jax.random.normal(keys[5], (t, h), jnp.float32)
    cfg = {"num_experts_per_tok": k}

    def whole(x, router, experts):
        lp = {"router": router, **experts}
        return jnp.sum(ref.routed_sum(lp, x, cfg, None, held=(0, r)) * probe)

    def share(lo, hi):
        def f(x, router, held):
            out = moe.routed_experts(
                x, {"w": router}, held, held=(lo, hi), top_k=k, scale=1.0,
                impl="ragged", scoring="softmax", form="swiglu")
            return jnp.sum(out * probe)
        held = {name: w[lo:hi] for name, w in experts.items()}
        assert moe._capacity(t * k, hi - lo, r) < t * k  # the compacted path
        return jax.value_and_grad(f, argnums=(0, 1, 2))(x, router, held)

    with jax.default_matmul_precision("highest"):
        want, (dx, drouter, dexperts) = jax.value_and_grad(
            whole, argnums=(0, 1, 2))(x, router, experts)
        parts = [share(lo, lo + 4) for lo in range(0, r, 4)]
    np.testing.assert_allclose(sum(p[0] for p in parts), want, rtol=1e-5)
    np.testing.assert_allclose(sum(p[1][0] for p in parts), dx, atol=1e-5)
    np.testing.assert_allclose(sum(p[1][1] for p in parts), drouter, atol=1e-5)
    for i, (_, (_, _, held)) in enumerate(parts):
        for name in experts:
            np.testing.assert_allclose(held[name], dexperts[name][4 * i:4 * i + 4],
                                       atol=1e-5)
