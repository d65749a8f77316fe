"""The Olmo-Hybrid family (``model_type`` olmo_hybrid: three linear-attention
layers, a gated delta rule with one decay a head, and a full-attention layer
to a period; post-norm blocks; every feed-forward a SwiGLU) over
``ray_tpu.models.olmo_hybrid`` and ``ray_tpu.train.step``.
``families/__init__.py`` says what a family gives; this one gives the
``train`` surface (serving of the family is not written in the program). On a
commit whose program lacks the family (the parent of the PR that added it)
``program_config`` raises at once and the benchmark's command exits non-zero.

The weights are the program's seeded ``init_params`` (traceable, so one
jitted program makes them), handed to the trainer's state and, the same
values, to the plain reference (``olmo_hybrid_reference.py``). The cell steps
under the runner's own optimizer at its own rate: the family is dense, and
nothing routes that a rate could move.

The configuration file states the chip's share: ``vocab_size`` is what is
HELD here. The operations a trained token and each kernel NEED (``train_mfu``
and the per-layer metrics' rooflines) are at the bottom.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmarks.families.olmo_hybrid_reference import (  # noqa: F401 - the surface
    layer_kinds, make_gap_fn, make_greedy_fn, not_for_the_compile_cache,
    reference_logits, reference_loss)
from benchmarks.harness.weights import seed_key

LINEAR = "linear_attention"
# rows the delta rule takes at a time, whatever implements it: its products
# are counted a chunk of this many
CHUNK = 64


def _program():
    try:
        from ray_tpu.models import olmo_hybrid
    except ImportError as e:
        raise RuntimeError(
            "this program has no ray_tpu.models.olmo_hybrid: it cannot run a "
            "configuration of the olmo_hybrid family") from e
    return olmo_hybrid


# ------------------------------------------------------ configuration, weights
def program_config(cfg: Dict[str, Any]):
    """The program's ``OlmoHybridConfig`` from a configuration file that uses
    the source's key names."""
    import jax.numpy as jnp

    oh = _program()
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg["torch_dtype"]]
    dep = cfg["deployment"]
    if cfg["rope_parameters"].get("rope_theta") is not None:
        raise ValueError("the program's full layers rotate nothing")
    return oh.OlmoHybridConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        layer_types=tuple(layer_kinds(cfg)),
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        linear_num_key_heads=cfg["linear_num_key_heads"],
        linear_num_value_heads=cfg["linear_num_value_heads"],
        linear_key_head_dim=cfg["linear_key_head_dim"],
        linear_value_head_dim=cfg["linear_value_head_dim"],
        linear_conv_kernel_dim=cfg["linear_conv_kernel_dim"],
        linear_allow_neg_eigval=cfg["linear_allow_neg_eigval"],
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        max_seq_len=dep["max_seq_len"], dtype=dtype,
        attention_impl=dep.get("attention_impl", "auto"),
        gdn_impl=dep.get("gdn_impl", "auto"))


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
    out of the persistent compile cache; the STEP is the program's own and is
    cached."""
    not_for_the_compile_cache()
    return _program().loss(params, tokens, targets, config)


def make_train_step(config, optimizer, mesh=None):
    from ray_tpu.train.step import make_train_step as make

    return make(config, optimizer, mesh=mesh)


def state_shardings(config, optimizer, mesh):
    from ray_tpu.parallel.sharding import DEFAULT_LLM_RULES
    from ray_tpu.train.step import _state_shardings, state_logical_axes

    return _state_shardings(state_logical_axes(config, optimizer), mesh,
                            DEFAULT_LLM_RULES)


# ------------------------------------------------- what the mathematics needs
def _linear_widths(cfg: Dict[str, Any]):
    return (cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
            cfg["linear_value_head_dim"])


def layer_matmul_params(cfg: Dict[str, Any], kind: str) -> int:
    """Matrix parameters a token meets in one layer of ``kind``: the mixer's
    projections (linear: q, k, v, the gate and W_o, and the two narrow ones
    that feed the decay and beta; full: q, k, v, o) and the SwiGLU's three.
    The convolution's taps, the norms and the decay's vectors are no
    products."""
    h = cfg["hidden_size"]
    mlp = 3 * h * cfg["intermediate_size"]
    if kind != LINEAR:
        return 4 * h * h + mlp
    heads, dk, dv = _linear_widths(cfg)
    return h * (2 * heads * dk + 3 * heads * dv + 2 * heads) + mlp


def gdn_chunk_flops(cfg: Dict[str, Any]) -> float:
    """What ONE head's chunk of ``CHUNK`` rows NEEDS of the rule's forward in
    its chunked form, at the rule's own widths (no tile's padding): the pair
    products ``Kb K^T`` and ``Q K^T`` (2 x 2 C^2 d_k), the state read by the
    keys and by the queries and written once (3 x 2 C d_k d_v), and ``T``
    and the pair matrix against the chunk's writes (2 x 2 C^2 d_v). The
    inverse ``T`` itself is the implementation's and is not counted. The
    reverse pass needs twice this (two products a forward product)."""
    _, dk, dv = _linear_widths(cfg)
    c = CHUNK
    return 4.0 * c * c * dk + 6.0 * c * dk * dv + 4.0 * c * c * dv


def gdn_chunk_fwd_flops(cfg, batch: int, heads: int, seq: int) -> float:
    """One call of the forward chunk kernel over [batch, heads, seq, ...]."""
    return batch * heads * (seq / CHUNK) * gdn_chunk_flops(cfg)


def gdn_chunk_bwd_flops(cfg, batch: int, heads: int) -> float:
    """ONE of the reverse pass's two kernels (``gdn_chunk_bwd_states``, which
    writes the chunks' starting states, and ``gdn_chunk_bwd``): half of what a
    reverse pass NEEDS, which is twice the forward's. Their results differ in
    shape, so the rows are the deployment's (a call takes whole rows)."""
    seq = cfg["deployment"]["max_seq_len"]
    return gdn_chunk_fwd_flops(cfg, batch, heads, seq)


def train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    """Forward and backward, no recompute, no embedding gather: 6 flops a
    matrix parameter met (the layers as ``layer_matmul_params`` counts them
    and the head over the vocabulary held), plus attention (forward once,
    backward twice) over the causal pairs of a full layer, plus the delta
    rule's chunk products (forward once, backward twice) of a linear one."""
    kinds = layer_kinds(cfg)
    dense = sum(layer_matmul_params(cfg, kind) for kind in kinds) \
        + cfg["hidden_size"] * cfg["vocab_size"]
    heads = cfg["num_attention_heads"]
    hd = cfg["hidden_size"] // heads
    full = sum(kind != LINEAR for kind in kinds)
    rule = 3.0 * (len(kinds) - full) * cfg["linear_num_value_heads"] \
        * gdn_chunk_flops(cfg) / CHUNK
    return 6.0 * dense + full * 3 * 4 * heads * hd * (seq + 1) / 2 + rule


def flash_full_bwd_kernel_flops(_cfg, batch: int, heads: int, seq: int,
                                head_dim: int) -> float:
    """ONE of the full layers' two backward kernels (dQ, and dK/dV; a profile
    names the calls ``attn_full.N`` after their scope): half of the five
    products over the causal pairs, as ``families/mellum.py`` counts them."""
    return 5.0 * batch * heads * head_dim * (seq * (seq + 1) // 2)
