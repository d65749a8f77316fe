"""The Mellum family (``model_type`` mellum: three sliding-window layers and a
full one to a period, two rotary schemes over the whole head, every
feed-forward a softmax-routed mixture of SwiGLU experts, no shared expert)
over ``ray_tpu.models.mellum`` and ``ray_tpu.train.step``.
``families/__init__.py`` says what a family gives; this one gives the
``train`` surface (serving of the family is not written in the program). On a
commit whose program lacks the family (the parent of the PR that added it)
``program_config`` raises at once and the benchmark's command exits non-zero.

The weights are the program's seeded ``init_params`` (traceable, so one
jitted program makes them), handed to the trainer's state and, the same
values, to the plain reference (``mellum_reference.py``).

The configuration file states the chip's share: ``num_experts`` and
``vocab_size`` are what is HELD here; ``n_router_outputs`` and
``held_experts`` say of how many, and which.

The operations a trained token and each kernel NEED (``train_mfu`` and the
per-layer metrics' rooflines) are at the bottom. They count the EXPECTED held
share of the routed choices (``tokens x num_experts_per_tok x num_experts /
n_router_outputs``): the train runner passes none of the step's counters on,
so what a run really held is in the step's ``expert_counts`` and not here.
"""

from __future__ import annotations

import weakref
from typing import Any, Dict

from benchmarks.families.mellum_reference import (  # noqa: F401 - the surface
    not_for_the_compile_cache, make_gap_fn, make_greedy_fn, reference_logits,
    reference_loss)
from benchmarks.harness.weights import seed_key

SLIDING = "sliding_attention"
# a program configuration -> the learning rate its file states
# (``deployment.learning_rate``): the program's configuration is the model's
# and holds no optimizer, and ``make_train_step`` is handed that object alone
_STATED_RATE: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
# what ``benchmarks/runners/train.py`` gives the program's
# ``default_optimizer`` (``at_stated_rate`` holds the runner to it)
RUNNER_SCHEDULE = {"warmup_steps": 10, "total_steps": 1000}


def _program():
    try:
        from ray_tpu.models import mellum
    except ImportError as e:
        raise RuntimeError(
            "this program has no ray_tpu.models.mellum: it cannot run a "
            "configuration of the mellum family") from e
    return mellum


def _layer_kinds(cfg: Dict[str, Any], key: str):
    """``cfg[key]`` at ``num_hidden_layers`` entries: a tool that sizes
    another depth changes the count alone, and the pattern goes on."""
    kinds, n = list(cfg[key]), cfg["num_hidden_layers"]
    return tuple((kinds * (-(-n // len(kinds))))[:n])


# ------------------------------------------------------ configuration, weights
def program_config(cfg: Dict[str, Any]):
    """The program's ``MellumConfig`` from a configuration file that uses the
    source's key names."""
    import jax.numpy as jnp

    ml = _program()
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg["torch_dtype"]]
    dep = cfg["deployment"]
    config = ml.MellumConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=_layer_kinds(cfg, "layer_types"),
        mlp_layer_types=_layer_kinds(cfg, "mlp_layer_types"),
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], sliding_window=cfg["sliding_window"],
        rope_parameters=cfg["rope_parameters"],
        num_experts=cfg["num_experts"],
        n_router_outputs=cfg["n_router_outputs"],
        held_experts=tuple(cfg["held_experts"]),
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        max_seq_len=dep["max_seq_len"], dtype=dtype,
        attention_impl=dep.get("attention_impl", "auto"),
        moe_tokens=dep.get("moe_tokens", 4096))
    _STATED_RATE[config] = dep.get("learning_rate")
    return config


def init_weights(config, key) -> Dict[str, Any]:
    return _program().init_params(config, key)


def make_weights(config, seed: int) -> Dict[str, Any]:
    """One jitted call from the seed, in the dtype the weights are trained in."""
    import jax

    return jax.jit(lambda k: init_weights(config, k))(seed_key(seed))


# ---------------------------------------------------------------------- train
def loss(params, tokens, targets, config):
    """The program's loss as ``correct`` differentiates it: one row, once a
    run, after the window. Like the reference it is compared with it stays
    out of the persistent compile cache (9.1 MB of the 26.8 the cell wrote
    with it in, PERF.md 6, PR 46); the STEP is the program's own and is
    cached."""
    not_for_the_compile_cache()
    return _program().loss(params, tokens, targets, config)


def _same_updates(a, b, steps: int = 12) -> bool:
    """Whether two optimizers keep the same state and give the same updates,
    on a two-number probe over ``steps`` steps (across the runner's warm-up,
    the gradient clipped in some and not in others): one small program."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    probe = {"w": jnp.asarray([0.5, -2.0], jnp.float32)}
    if jax.tree.structure(jax.eval_shape(a.init, probe)) \
            != jax.tree.structure(jax.eval_shape(b.init, probe)):
        return False

    def updates(opt):
        def one(state, i):
            grads = {"w": jnp.cos(probe["w"] * i) * (1.0 + i % 2)}
            out, state = opt.update(grads, state, probe)
            return state, out["w"]
        return jax.lax.scan(one, opt.init(probe),
                            jnp.arange(1.0, steps + 1.0))[1]

    got, want = jax.jit(lambda: (updates(a), updates(b)))()
    return bool(np.allclose(got, want, rtol=1e-6, atol=0.0))


def at_stated_rate(config, optimizer):
    """The program's ``default_optimizer`` at the learning rate the
    configuration's file states, where it states one, under the runner's
    schedule (``RUNNER_SCHEDULE``). ``optimizer`` is the runner's: its
    ``init`` makes the state this one updates, so it has to BE
    ``default_optimizer`` under that schedule at its default peak. That is
    checked, state for state and update for update on a probe, and a runner
    that has changed its rate or its schedule fails here, loudly, where it
    would otherwise step the cell at a rate nobody stated."""
    from ray_tpu.train.step import default_optimizer

    rate = _STATED_RATE.get(config)
    if rate is None:
        return optimizer
    if not _same_updates(optimizer, default_optimizer(**RUNNER_SCHEDULE)):
        raise RuntimeError(
            "the train runner's optimizer is no longer the program's "
            f"default_optimizer(**{RUNNER_SCHEDULE}): deployment.learning_rate "
            "is stated against that schedule (benchmarks/families/mellum.py)")
    return default_optimizer(lr=rate, **RUNNER_SCHEDULE)


def make_train_step(config, optimizer, mesh=None):
    from ray_tpu.train.step import make_train_step as make

    return make(config, at_stated_rate(config, optimizer), mesh=mesh)


def state_shardings(config, optimizer, mesh):
    from ray_tpu.parallel.sharding import DEFAULT_LLM_RULES
    from ray_tpu.train.step import _state_shardings, state_logical_axes

    return _state_shardings(state_logical_axes(config, optimizer), mesh,
                            DEFAULT_LLM_RULES)


# ------------------------------------------------- what the mathematics needs
def held_share(cfg: Dict[str, Any]) -> float:
    """The share of a token's routed choices this chip expects to hold."""
    return cfg["num_experts"] / cfg["n_router_outputs"]


def expert_matmul_params(cfg: Dict[str, Any]) -> int:
    """One expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def window_pairs(cfg: Dict[str, Any], seq: int) -> int:
    """(query, key) pairs a sliding layer admits in a sequence of ``seq``:
    query ``i`` sees ``min(i + 1, window)`` keys."""
    w = min(cfg["sliding_window"], seq)
    return w * (w + 1) // 2 + (seq - w) * w


def layer_matmul_params(cfg: Dict[str, Any]) -> float:
    """Matrix parameters a token meets in one layer HERE: attention, the
    router, and the expected held share of its chosen experts."""
    h, nh, nkv, hd = (cfg["hidden_size"], cfg["num_attention_heads"],
                      cfg["num_key_value_heads"], cfg["head_dim"])
    attention = 2 * h * nh * hd + 2 * h * nkv * hd
    router = h * cfg["n_router_outputs"]
    return attention + router + cfg["num_experts_per_tok"] * held_share(cfg) \
        * expert_matmul_params(cfg)


def train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    """Forward and backward, no recompute, no embedding gather: 6 flops a
    matrix parameter met (the layers as ``layer_matmul_params`` counts them,
    held experts only, and the head over the vocabulary held), plus attention
    (forward once, backward twice) over the causal pairs of a full layer and
    the window's pairs of a sliding one."""
    kinds = _layer_kinds(cfg, "layer_types")
    dense = len(kinds) * layer_matmul_params(cfg) \
        + cfg["hidden_size"] * cfg["vocab_size"]
    heads, hd = cfg["num_attention_heads"], cfg["head_dim"]
    pairs = sum(window_pairs(cfg, seq) if kind == SLIDING
                else seq * (seq + 1) // 2 for kind in kinds)
    return 6 * dense + 3 * 4 * heads * hd * pairs / seq


def flash_window_fwd_flops(cfg, batch: int, heads: int, seq: int,
                           head_dim: int) -> float:
    """One call of the windowed flash forward: QK^T and PV, 2 products x 2
    flops, over the pairs the window admits."""
    return 4.0 * batch * heads * head_dim * window_pairs(cfg, seq)


def flash_window_bwd_kernel_flops(cfg, batch: int, heads: int, seq: int,
                                  head_dim: int) -> float:
    """ONE of the windowed backward's two kernels (``flash_window_bwd_dq``,
    ``flash_window_bwd_dkv``): half of the five products a backward NEEDS
    over the pairs the window admits (the scores again, dP, dV, dQ, dK: 10 x
    batch x heads x head_dim x pairs for the pair of kernels). The kernels
    multiply seven between them, and read lower for it."""
    return 5.0 * batch * heads * head_dim * window_pairs(cfg, seq)


def flash_full_bwd_kernel_flops(_cfg, batch: int, heads: int, seq: int,
                                head_dim: int) -> float:
    """ONE of the full layers' two backward kernels (dQ, and dK/dV; a profile
    names the calls ``attn_full.N`` after their scope): half of the five
    products over the causal pairs, as ``harness/roofline.py``
    ``flash_bwd_flops`` counts them for the dense family's cell."""
    return 5.0 * batch * heads * head_dim * (seq * (seq + 1) // 2)


# grouped products (``ragged-dot``) an expert layer's chunk runs in a step,
# and those the mathematics needs: three forward and six in reverse are
# needed; the reverse pass multiplies by W_up and W_gate once more
# (``ops/moe.py`` ``_compacted_bwd``: nothing of the forward is kept)
GROUPED_PRODUCTS_RUN, GROUPED_PRODUCTS_NEEDED = 11, 9


def experts_grouped_flops(cfg, *_shape) -> float:
    """What ONE call of a grouped product is credited with, so that the
    calls of a step add up to what its expert layers NEED: 3 x 3 products of
    2 flops x hidden x expert width an EXPECTED held assignment
    (``moe_tokens x num_experts_per_tok x held_share`` a chunk), whatever rows
    the call's block has, spread over the ``GROUPED_PRODUCTS_RUN`` calls a
    chunk makes."""
    assignments = cfg["deployment"]["moe_tokens"] \
        * cfg["num_experts_per_tok"] * held_share(cfg)
    return 2.0 * cfg["hidden_size"] * cfg["moe_intermediate_size"] \
        * assignments * GROUPED_PRODUCTS_NEEDED / GROUPED_PRODUCTS_RUN
