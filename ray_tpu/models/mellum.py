"""Mellum class decoder on the TRAINING path (``model_type`` mellum,
JetBrains/Mellum2-12B-A2.5B-Instruct): pre-norm residual blocks whose mixer
and feed-forward are chosen BY LAYER from two patterns, trained through
``train/step.py`` like the dense family.

- ``layer_types[l]`` is ``sliding_attention`` or ``full_attention`` (published:
  three sliding layers, then a full one, seven times). Both are grouped-query
  causal attention over the same heads (32 over 4 of 128); a sliding layer's
  query sees its ``sliding_window`` newest keys, itself among them, and
  rotates the whole head with plain frequencies; a full layer rotates the
  whole head with YaRN's frequencies and its attention factor on cos and sin,
  q and k alike (``ops/rope.py``). No biases, no q/k norm, no gate.
- ``mlp_layer_types[l]`` is ``sparse`` on every published layer: the top
  ``num_experts_per_tok`` of a float32 softmax over ``n_router_outputs``,
  normalised over the chosen (``norm_topk_prob``), no further scale, each
  expert a SwiGLU of ``moe_intermediate_size``; no shared expert
  (``ops/moe.py``: ``scoring="softmax"``, ``form="swiglu"``, ``scale=1.0``).
  The gradient reaches the router through the chosen weights. There is no
  auxiliary load-balance term: the loss is the next token's cross-entropy.

The block is built from the pattern (``_layer``: a layer type says whether
the attention has a window and which rotary scheme it rotates by; the one
feed-forward kind is the sparse one). One PERIOD of the pattern (S S S F) is
one scanned body: the layers' parameters are stacked ``[L, ...]``, read as
``[periods, period, ...]``, and ``lax.scan`` runs over the periods, so depth
compiles once. Each layer of the body is rematerialised on its own, under
one policy (``_remat``): the flash kernel's output and log-sum-exp are kept,
the rest is computed again; the expert product's reverse pass multiplies by
W_up and W_gate again by itself (``ops/moe.py`` ``_compacted_bwd``). Its
eleven grouped products a chunk (three forward, eight in reverse: the
cotangent back through W_down, W_up and W_gate over the stacks' LAST
dimension, and each held expert's three matrix gradients from its own rows)
are one Pallas kernel (``ops/grouped_matmul.py``) that reads the stacks as
they lie: no stack is transposed, copied or re-laid for a product, and the
rows of a block that hold no assignment are not multiplied.

``num_experts`` is the experts HELD here, ``held_experts`` which of the
router's ``n_router_outputs`` they are; ``vocab_size`` the rows of the
vocabulary held here (one chip's share of a deployment that splits each
layer's experts and the vocabulary; nothing stands in for the absent chips).
There is no cache anywhere: serving this family is not written.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
from typing import Any, Dict, Mapping, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import attention
from ray_tpu.ops.moe import routed_experts
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.rope import apply_rope, rope_frequencies

FULL, SLIDING = "full_attention", "sliding_attention"
PERIOD = (SLIDING, SLIDING, SLIDING, FULL)
ROPE_MELLUM2 = {
    FULL: {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
           "original_max_position_embeddings": 8192, "beta_fast": 32,
           "beta_slow": 1, "attention_factor": 1.2772588722239782},
    SLIDING: {"rope_type": "default", "rope_theta": 500000},
}
# what the loss counts beside itself, summed over the expert layers of the
# forward pass (``ops/moe.py``'s counters): the routed choices, those that
# fell on experts held here, held experts reached, the fullest held expert's
# count, and the compacted product's blocks beyond its first
EXPERT_COUNTS = ("moe_assignments", "moe_assignments_held",
                 "moe_experts_touched", "moe_expert_load_max",
                 "moe_blocks_extra")


@dataclasses.dataclass(frozen=True, eq=False)
class MellumConfig:
    """The source's key names (``config.json`` of ``model_type`` mellum); the
    defaults are Mellum2-12B-A2.5B whole."""
    vocab_size: int = 98304
    hidden_size: int = 2304
    layer_types: Tuple[str, ...] = PERIOD * 7
    mlp_layer_types: Tuple[str, ...] = ("sparse",) * 28
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 1024
    rope_parameters: Mapping[str, Mapping[str, Any]] = dataclasses.field(
        default_factory=lambda: ROPE_MELLUM2)
    num_experts: int = 64
    n_router_outputs: int = 64
    held_experts: Tuple[int, int] = (0, 64)
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 896
    rms_norm_eps: float = 1e-6
    max_seq_len: int = 8192
    dtype: Any = jnp.bfloat16
    attention_impl: str = "auto"
    # tokens the routed experts take at a time: the sorted copy of a chunk's
    # held rows and the float32 results around it are ``_capacity`` rows
    # tall, half of the chunk's 8 x tokens at a quarter share
    moe_tokens: int = 4096

    def __post_init__(self):
        lo, hi = self.held_experts
        if len(self.mlp_layer_types) != len(self.layer_types):
            raise ValueError("layer_types and mlp_layer_types differ in length")
        if not set(self.layer_types) <= {FULL, SLIDING}:
            raise ValueError(f"layer types are {FULL} and {SLIDING}")
        if set(self.mlp_layer_types) != {"sparse"}:
            raise ValueError("every feed-forward is sparse: no other is written")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must divide over the KV heads")
        if not (0 <= lo < hi <= self.n_router_outputs
                and hi - lo == self.num_experts):
            raise ValueError(
                f"held_experts {self.held_experts} must be num_experts "
                f"({self.num_experts}) of the router's "
                f"{self.n_router_outputs} outputs")

    @property
    def period(self) -> Tuple[str, ...]:
        """The shortest run of layer types that the layers repeat: one
        scanned body."""
        kinds = tuple(self.layer_types)
        for p in range(1, len(kinds) + 1):
            if len(kinds) % p == 0 and kinds == kinds[:p] * (len(kinds) // p):
                return kinds[:p]
        return kinds

    @classmethod
    def tiny(cls, **kw) -> "MellumConfig":
        """CPU tests: two periods, query groups of 4, a window of 24, YaRN
        over a whole head of 32, 4 of 16 experts held, top-4."""
        kw.setdefault("max_seq_len", 128)
        rope = {FULL: {**ROPE_MELLUM2[FULL], "factor": 4,
                       "original_max_position_embeddings": 32,
                       "attention_factor": 1.1386294361119891},
                SLIDING: ROPE_MELLUM2[SLIDING]}
        return cls(**{**dict(
            vocab_size=256, hidden_size=64, layer_types=PERIOD * 2,
            mlp_layer_types=("sparse",) * 8, num_attention_heads=8,
            num_key_value_heads=2, head_dim=32, sliding_window=24,
            rope_parameters=rope, num_experts=4, n_router_outputs=16,
            held_experts=(0, 4), num_experts_per_tok=4,
            moe_intermediate_size=32), **kw})


def init_params(config: MellumConfig, key) -> Dict[str, Any]:
    """Seeded weights: normal / sqrt(fan_in) matrices, norms of one, the
    router in float32; every layer's stacked on a leading ``[L]``. Traceable:
    call it under ``jit``."""
    h, dt, hd = config.hidden_size, config.dtype, config.head_dim
    nh, nkv = config.num_attention_heads, config.num_key_value_heads
    f, e, r = (config.moe_intermediate_size, config.num_experts,
               config.n_router_outputs)
    n = len(config.layer_types)
    keys = jax.random.split(key, 10)

    def normal(k, shape, fan_in, dtype=dt):
        return (jax.random.normal(k, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(dtype)

    return {
        "embed_tokens": normal(keys[0], (config.vocab_size, h), h),
        "layers": {
            "attn_norm": jnp.ones((n, h), dt),
            "wq": normal(keys[1], (n, h, nh * hd), h),
            "wk": normal(keys[2], (n, h, nkv * hd), h),
            "wv": normal(keys[3], (n, h, nkv * hd), h),
            "wo": normal(keys[4], (n, nh * hd, h), nh * hd),
            "mlp_norm": jnp.ones((n, h), dt),
            "router": normal(keys[5], (n, h, r), h, jnp.float32),
            "w_gate": normal(keys[6], (n, e, h, f), h),
            "w_up": normal(keys[7], (n, e, h, f), h),
            "w_down": normal(keys[8], (n, e, f, h), f),
        },
        "final_norm": jnp.ones((h,), dt),
        "lm_head": normal(keys[9], (h, config.vocab_size), h),
    }


def logical_axes(config: MellumConfig) -> Dict[str, Any]:
    """Logical-axis names parallel to ``init_params``' tree
    (``parallel/sharding.py``): the experts ride the ``expert`` axis."""
    return {
        "embed_tokens": ("vocab", "embed"),
        "layers": {
            "attn_norm": ("layers", "embed"),
            "wq": ("layers", "embed", "heads"),
            "wk": ("layers", "embed", "kv_heads"),
            "wv": ("layers", "embed", "kv_heads"),
            "wo": ("layers", "heads", "embed"),
            "mlp_norm": ("layers", "embed"),
            "router": ("layers", "embed", None),
            "w_gate": ("layers", "expert", "embed", "mlp"),
            "w_up": ("layers", "expert", "embed", "mlp"),
            "w_down": ("layers", "expert", "mlp", "embed"),
        },
        "final_norm": ("embed",),
        "lm_head": ("embed", "vocab"),
    }


# --------------------------------------------------------------------------- #
# The block, from the pattern
# --------------------------------------------------------------------------- #
def _attention_layer(config: MellumConfig, kind: str, rope, x, lp):
    """``x + Attn(RMS(x))``: GQA, rotary over the whole head by the layer
    type's scheme (``rope``: its cos and sin), a window on a sliding layer."""
    b, s, _ = x.shape
    nh, nkv, hd = (config.num_attention_heads, config.num_key_value_heads,
                   config.head_dim)
    cos, sin = rope
    with jax.named_scope("attn_window" if kind == SLIDING else "attn_full"):
        y = rms_norm(x, lp["attn_norm"], config.rms_norm_eps)
        q = apply_rope((y @ lp["wq"]).reshape(b, s, nh, hd), cos, sin)
        k = apply_rope((y @ lp["wk"]).reshape(b, s, nkv, hd), cos, sin)
        v = (y @ lp["wv"]).reshape(b, s, nkv, hd)
        o = attention(q, k, v, causal=True, impl=config.attention_impl,
                      window=config.sliding_window if kind == SLIDING else None)
        return x + o.reshape(b, s, nh * hd) @ lp["wo"]


def _sparse_layer(config: MellumConfig, x, lp):
    """``x + MoE(RMS(x))`` over the experts held here, ``moe_tokens`` tokens
    at a time, and what it counted (summed over the chunks)."""
    b, s, h = x.shape
    chunk = min(config.moe_tokens, b * s)
    if (b * s) % chunk:
        raise ValueError(f"{b * s} tokens are no multiple of moe_tokens {chunk}")
    y = rms_norm(x, lp["mlp_norm"], config.rms_norm_eps)
    experts = {name: lp[name] for name in ("w_gate", "w_up", "w_down")}
    def one(rows):
        with jax.named_scope("experts"):
            return routed_experts(
                rows, {"w": lp["router"]}, experts, held=config.held_experts,
                top_k=config.num_experts_per_tok, scale=1.0, impl="ragged",
                scoring="softmax", form="swiglu",
                counted=jnp.ones((chunk,), bool))

    out, counts = jax.lax.map(one, y.reshape(b * s // chunk, chunk, h))
    # of ops/moe.py's six: not its count of compacted calls
    counts = jnp.sum(counts, axis=0)[jnp.array([0, 1, 2, 3, 5])]
    return x + out.reshape(b, s, h), counts


def _ropes(config: MellumConfig, seq: int):
    """cos and sin [seq, head_dim / 2] of each layer type's rotary scheme."""
    out = {}
    for kind in set(config.layer_types):
        scheme = config.rope_parameters[kind]
        out[kind] = rope_frequencies(
            config.head_dim, seq, float(scheme["rope_theta"]),
            yarn=scheme if scheme["rope_type"] == "yarn" else None)
    return out


def _layer(config: MellumConfig, kind: str, rope, x, lp):
    """One layer of type ``kind`` (its rotary scheme's cos and sin in
    ``rope``) -> (x, int32 [5] as ``EXPERT_COUNTS``)."""
    return _sparse_layer(config, _attention_layer(config, kind, rope, x, lp), lp)


def _remat(fn):
    """The one policy: keep the flash kernel's output and log-sum-exp
    (``ops/attention.py`` names them), compute the rest of a layer again."""
    return jax.checkpoint(
        fn, policy=jax.checkpoint_policies.save_only_these_names(
            "flash_out", "flash_lse"))


def hidden(params: Dict[str, Any], tokens, config: MellumConfig):
    """tokens: [B, S] int32 -> (final-norm hidden states [B, S, H], int32 [5]
    as ``EXPERT_COUNTS``)."""
    _, s = tokens.shape
    x = params["embed_tokens"][tokens].astype(config.dtype)
    ropes = _ropes(config, s)
    period = config.period
    stacked = jax.tree.map(
        lambda a: a.reshape(a.shape[0] // len(period), len(period), *a.shape[1:]),
        params["layers"])

    # the period as runs of one kind (S S S, F): a run is an inner scan over
    # its layers, so the body holds one layer of each kind and not four
    runs, first = [], 0
    for kind, run in itertools.groupby(period):
        runs.append((kind, first, len(list(run))))
        first += runs[-1][2]

    def one_layer(kind):
        layer = _remat(functools.partial(_layer, config, kind, ropes[kind]))

        def step(carry, lp):
            x, counted = layer(carry[0], lp)
            return (x, carry[1] + counted), None
        return step

    steps = [(one_layer(kind), first, n) for kind, first, n in runs]

    def one_period(carry, pp):
        for step, first, n in steps:
            carry, _ = jax.lax.scan(
                step, carry, jax.tree.map(lambda a: a[first:first + n], pp))
        return carry, None

    (x, counts), _ = jax.lax.scan(
        one_period, (x, jnp.zeros((len(EXPERT_COUNTS),), jnp.int32)), stacked)
    return rms_norm(x, params["final_norm"], config.rms_norm_eps), counts


def forward(params: Dict[str, Any], tokens, config: MellumConfig):
    """tokens: [B, S] int32 -> logits [B, S, vocab] (float32)."""
    x, _ = hidden(params, tokens, config)
    return (x @ params["lm_head"]).astype(jnp.float32)


def loss_and_counters(params, tokens, targets, config: MellumConfig, mesh=None,
                      rules=None, mask=None):
    """The mean cross-entropy of the next token over the vocabulary held here
    (the fused, sequence-chunked head of ``ops/loss.py``), and
    ``{"expert_counts": int32 [5]}`` as ``EXPERT_COUNTS``. One chip's
    program: a mesh is not written (``parallel/sharding.py``'s ``expert``
    axis has no exchange yet)."""
    from ray_tpu.ops.loss import fused_cross_entropy

    if mesh is not None:
        raise NotImplementedError(
            "the mellum family trains on one chip's share: no exchange "
            "between the chips that share an expert layer is written")
    x, counts = hidden(params, tokens, config)
    with jax.named_scope("head"):
        value = fused_cross_entropy(x, params["lm_head"], targets, mask)
    return value, {"expert_counts": counts}


def loss(params, tokens, targets, config: MellumConfig, mesh=None, rules=None,
         mask=None):
    return loss_and_counters(params, tokens, targets, config, mesh, rules,
                             mask)[0]
