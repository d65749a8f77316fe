"""Olmo-Hybrid class decoder on the TRAINING path (``model_type``
olmo_hybrid, allenai/Olmo-Hybrid-7B): post-norm residual blocks whose mixer is
chosen BY LAYER from ``layer_types`` (published: three linear-attention layers,
then a full one, eight times), every feed-forward a SwiGLU, trained through
``train/step.py`` like the dense family and Mellum.

- ``linear_attention``: a gated delta rule with ONE decay a head
  (``ops/gdn.py``). For a row ``x`` and each of ``linear_num_value_heads``
  heads: q, k (``linear_key_head_dim``) and v (``linear_value_head_dim``) are
  projections of ``x`` through a causal depthwise convolution of
  ``linear_conv_kernel_dim`` taps and a SiLU; q and k are l2-normalised a
  head, q scaled by ``d_k^-1/2``; ``g = -exp(A_log) softplus(x W_a +
  dt_bias)`` (float32, no lower bound), ``beta = sigmoid(x W_b)``, doubled
  where ``linear_allow_neg_eigval``; the rule's output is RMS-normalised a
  head, gated by ``silu(x W_g)`` and projected by ``W_o``.
- ``full_attention``: ``num_attention_heads`` query heads over as many K/V
  heads (no grouping), an RMSNorm over the whole q and the whole k before the
  split into heads, causal softmax, NO rotary (``rope_theta`` is null).
- the block: ``h = x + norm(mixer(x))``, ``out = h + norm(mlp(h))`` (the
  Olmo family's: the norm is on the branch's OUTPUT).

One PERIOD of the pattern (L L L F) is one scanned body, its runs of one
kind an inner scan, as ``models/mellum.py`` does it. Each layer is
rematerialised on its own under one policy (``_remat``): the chunk kernel's
output and the flash kernel's output and log-sum-exp are kept, the rest is
computed again. The mixer's projections keep shapes no other layer has: q, k
and v are ONE product ``[hidden, 2 d_k H + d_v H]`` (11,520 columns as
published; the convolution runs over its result), the gate 5,760, beside
the MLP's 11,008: a profile's readers find them by those.

``vocab_size`` is the rows of the vocabulary held here (one chip's share of a
deployment that splits embedding and head in row slices; nothing stands in
for the absent chips). There is no cache anywhere: serving this family is not
written.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import attention
from ray_tpu.ops.gdn import gdn_chunk
from ray_tpu.ops.norms import rms_norm

FULL, LINEAR = "full_attention", "linear_attention"
PERIOD = (LINEAR, LINEAR, LINEAR, FULL)
# what the loss counts beside itself, each a mean over the linear layers'
# rows and heads of the forward pass: ``exp(g)`` (what a row keeps of the
# state), and the share of rows whose ``beta`` is over 1 (where ``I - beta k
# k^T`` turns a component of the state around)
GDN_COUNTERS = ("gdn_decay_mean", "gdn_beta_over_one_share")


@dataclasses.dataclass(frozen=True, eq=False)
class OlmoHybridConfig:
    """The source's key names (``config.json`` of ``model_type``
    olmo_hybrid); the defaults are Olmo-Hybrid-7B whole."""
    vocab_size: int = 100352
    hidden_size: int = 3840
    intermediate_size: int = 11008
    layer_types: Tuple[str, ...] = PERIOD * 8
    num_attention_heads: int = 30
    num_key_value_heads: int = 30
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    rms_norm_eps: float = 1e-6
    max_seq_len: int = 65536
    dtype: Any = jnp.bfloat16
    attention_impl: str = "auto"
    gdn_impl: str = "auto"

    def __post_init__(self):
        if not set(self.layer_types) <= {FULL, LINEAR}:
            raise ValueError(f"layer types are {FULL} and {LINEAR}")
        if self.num_key_value_heads != self.num_attention_heads:
            raise ValueError("the full layers have a K/V head a query head")
        if self.linear_num_key_heads != self.linear_num_value_heads:
            raise ValueError("the linear layers have a key head a value head")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError("the hidden size must divide over the heads")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

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
    def tiny(cls, **kw) -> "OlmoHybridConfig":
        """CPU tests: two periods, 3 heads, key 24 / value 48 (0.75 and 1.5
        of a tile of 32, as 96 / 192 are of 128)."""
        kw.setdefault("max_seq_len", 128)
        return cls(**{**dict(
            vocab_size=256, hidden_size=96, intermediate_size=160,
            layer_types=PERIOD * 2, num_attention_heads=3,
            num_key_value_heads=3, linear_num_key_heads=3,
            linear_num_value_heads=3, linear_key_head_dim=24,
            linear_value_head_dim=48), **kw})


def _widths(config: OlmoHybridConfig):
    """(heads, d_k, d_v, columns of q and k together, of v) of a linear layer."""
    h, dk, dv = (config.linear_num_value_heads, config.linear_key_head_dim,
                 config.linear_value_head_dim)
    return h, dk, dv, 2 * h * dk, h * dv


def init_params(config: OlmoHybridConfig, key) -> Dict[str, Any]:
    """Seeded weights: normal / sqrt(fan_in) matrices and convolution taps,
    norms of one; the decay's parameters as the family publishes them (``A``
    uniform in (0, 16), ``dt`` log-uniform in (0.001, 0.1), ``dt_bias`` its
    inverse softplus), in float32 with the narrow projection that feeds the
    decay and ``beta``. Each kind of layer has its own stack (``linear``,
    ``full``), ``[layers of the kind, ...]`` in the order of ``layer_types``.
    Traceable: call it under ``jit``."""
    h, dt, f = config.hidden_size, config.dtype, config.intermediate_size
    heads, dk, dv, qk, vw = _widths(config)
    taps = config.linear_conv_kernel_dim
    n_lin = sum(kind == LINEAR for kind in config.layer_types)
    n_full = len(config.layer_types) - n_lin
    keys = iter(jax.random.split(key, 24))

    def normal(shape, fan_in, dtype=dt):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * (fan_in ** -0.5)).astype(dtype)

    def mlp(n):
        return {"mixer_norm": jnp.ones((n, h), dt),
                "mlp_norm": jnp.ones((n, h), dt),
                "w_gate": normal((n, h, f), h), "w_up": normal((n, h, f), h),
                "w_down": normal((n, f, h), f)}

    a = jax.random.uniform(next(keys), (n_lin, heads), jnp.float32, 0.0, 16.0)
    step = jnp.exp(jax.random.uniform(
        next(keys), (n_lin, heads), jnp.float32,
        jnp.log(0.001), jnp.log(0.1)))
    return {
        "embed_tokens": normal((config.vocab_size, h), h),
        "linear": {
            **mlp(n_lin),
            "w_qkv": normal((n_lin, h, qk + vw), h),
            "conv": normal((n_lin, taps, qk + vw), taps),
            "w_ab": normal((n_lin, h, 2 * heads), h, jnp.float32),
            "a_log": jnp.log(jnp.maximum(a, 1e-4)),
            # softplus(dt_bias) = dt
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "w_g": normal((n_lin, h, vw), h),
            "head_norm": jnp.ones((n_lin, dv), dt),
            "w_o": normal((n_lin, vw, h), vw),
        },
        "full": {
            **mlp(n_full),
            "wq": normal((n_full, h, h), h), "wk": normal((n_full, h, h), h),
            "wv": normal((n_full, h, h), h), "wo": normal((n_full, h, h), h),
            "q_norm": jnp.ones((n_full, h), dt),
            "k_norm": jnp.ones((n_full, h), dt),
        },
        "final_norm": jnp.ones((h,), dt),
        "lm_head": normal((h, config.vocab_size), h),
    }


def logical_axes(config: OlmoHybridConfig) -> Dict[str, Any]:
    """Logical-axis names parallel to ``init_params``' tree
    (``parallel/sharding.py``)."""
    mlp = {"mixer_norm": ("layers", "embed"), "mlp_norm": ("layers", "embed"),
           "w_gate": ("layers", "embed", "mlp"),
           "w_up": ("layers", "embed", "mlp"),
           "w_down": ("layers", "mlp", "embed")}
    return {
        "embed_tokens": ("vocab", "embed"),
        "linear": {
            **mlp,
            "w_qkv": ("layers", "embed", "heads"),
            "conv": ("layers", None, "heads"),
            "w_ab": ("layers", "embed", None),
            "a_log": ("layers", None), "dt_bias": ("layers", None),
            "w_g": ("layers", "embed", "heads"),
            "head_norm": ("layers", None),
            "w_o": ("layers", "heads", "embed"),
        },
        "full": {
            **mlp,
            "wq": ("layers", "embed", "heads"),
            "wk": ("layers", "embed", "heads"),
            "wv": ("layers", "embed", "heads"),
            "wo": ("layers", "heads", "embed"),
            "q_norm": ("layers", "embed"), "k_norm": ("layers", "embed"),
        },
        "final_norm": ("embed",),
        "lm_head": ("embed", "vocab"),
    }


# --------------------------------------------------------------------------- #
# The block, from the pattern
# --------------------------------------------------------------------------- #
def _short_conv(x, taps):
    """Causal depthwise convolution: ``y_t = sum_j taps[j] x_{t - (n - 1) +
    j}`` with zeros before the row's start. x: [B, S, C]; taps: [n, C]."""
    n = taps.shape[0]
    padded = jnp.pad(x, ((0, 0), (n - 1, 0), (0, 0)))
    s = x.shape[1]
    return sum(padded[:, j:j + s] * taps[j] for j in range(n))


def _l2norm(x):
    x32 = x.astype(jnp.float32)
    return x32 * jax.lax.rsqrt(jnp.sum(x32 * x32, axis=-1, keepdims=True) + 1e-6)


def _linear_mixer(config: OlmoHybridConfig, x, lp):
    """The gated delta rule's layer over x [B, S, hidden] -> ([B, S, hidden],
    float32 [2] as ``GDN_COUNTERS``: the SUMS over this layer's rows and
    heads)."""
    b, s, _ = x.shape
    heads, dk, dv, qk, vw = _widths(config)
    dt = x.dtype
    with jax.named_scope("gdn_proj"):
        mixed = jax.nn.silu(_short_conv(x @ lp["w_qkv"], lp["conv"]))
        q, k = (_l2norm(t.reshape(b, s, heads, dk))
                for t in jnp.split(mixed[..., :qk], 2, axis=-1))
        q = (q * dk ** -0.5).astype(dt)
        v = mixed[..., qk:].reshape(b, s, heads, dv)
        ab = x.astype(jnp.float32) @ lp["w_ab"]               # [B, S, 2 H]
        g = -jnp.exp(lp["a_log"]) * jax.nn.softplus(
            ab[..., :heads] + lp["dt_bias"])
        beta = jax.nn.sigmoid(ab[..., heads:])
        if config.linear_allow_neg_eigval:
            beta = 2.0 * beta
        gate = jax.nn.silu(x @ lp["w_g"]).reshape(b, s, heads, dv)
    with jax.named_scope("gdn"):
        o = gdn_chunk(q, k.astype(dt), v, g, beta, impl=config.gdn_impl)
    with jax.named_scope("gdn_proj"):
        o = rms_norm(o, lp["head_norm"], config.rms_norm_eps) * gate
        out = o.reshape(b, s, vw) @ lp["w_o"]
    counted = jnp.stack([jnp.sum(jnp.exp(g)),
                         jnp.sum((beta > 1.0).astype(jnp.float32))])
    return out, jax.lax.stop_gradient(counted)


def _full_mixer(config: OlmoHybridConfig, x, lp):
    """Causal softmax attention, a K/V head a query head, q and k normalised
    over their whole width, nothing rotated."""
    b, s, _ = x.shape
    nh, hd = config.num_attention_heads, config.head_dim
    with jax.named_scope("attn_full"):
        q = rms_norm(x @ lp["wq"], lp["q_norm"], config.rms_norm_eps)
        k = rms_norm(x @ lp["wk"], lp["k_norm"], config.rms_norm_eps)
        o = attention(q.reshape(b, s, nh, hd), k.reshape(b, s, nh, hd),
                      (x @ lp["wv"]).reshape(b, s, nh, hd), causal=True,
                      impl=config.attention_impl)
        return o.reshape(b, s, nh * hd) @ lp["wo"]


def _layer(config: OlmoHybridConfig, kind: str, x, lp):
    """One layer of type ``kind`` -> (x, float32 [2] as ``GDN_COUNTERS``,
    sums)."""
    eps = config.rms_norm_eps
    if kind == LINEAR:
        mixed, counted = _linear_mixer(config, x, lp)
    else:
        mixed, counted = _full_mixer(config, x, lp), jnp.zeros((2,), jnp.float32)
    x = x + rms_norm(mixed, lp["mixer_norm"], eps)
    with jax.named_scope("mlp"):
        y = (jax.nn.silu(x @ lp["w_gate"]) * (x @ lp["w_up"])) @ lp["w_down"]
    return x + rms_norm(y, lp["mlp_norm"], eps), counted


def _remat(fn):
    """The one policy: keep the chunk kernel's output (``ops/gdn.py`` names
    it) and the flash kernel's output and log-sum-exp (``ops/attention.py``),
    compute the rest of a layer again."""
    return jax.checkpoint(
        fn, policy=jax.checkpoint_policies.save_only_these_names(
            "gdn_out", "flash_out", "flash_lse"))


def hidden(params: Dict[str, Any], tokens, config: OlmoHybridConfig):
    """tokens: [B, S] int32 -> (final-norm hidden states [B, S, H], float32
    [2] as ``GDN_COUNTERS``: means over the linear layers' rows and heads)."""
    b, s = tokens.shape
    x = params["embed_tokens"][tokens].astype(config.dtype)
    period = config.period
    periods = len(config.layer_types) // len(period)
    # the period as runs of one kind (L L L, F): a run is an inner scan over
    # its layers, so the body holds one layer of each kind and not four
    runs, seen = [], {LINEAR: 0, FULL: 0}
    for kind, run in itertools.groupby(period):
        n = len(list(run))
        runs.append((kind, seen[kind], n))
        seen[kind] += n

    def stacked(kind):
        return jax.tree.map(
            lambda a: a.reshape(periods, seen[kind], *a.shape[1:]),
            params["linear" if kind == LINEAR else "full"])

    def one_layer(kind):
        layer = _remat(functools.partial(_layer, config, kind))

        def step(carry, lp):
            x, counted = layer(carry[0], lp)
            return (x, carry[1] + counted), None
        return step

    steps = [(one_layer(kind), kind, first, n) for kind, first, n in runs]

    def one_period(carry, pp):
        for step, kind, first, n in steps:
            carry, _ = jax.lax.scan(
                step, carry,
                jax.tree.map(lambda a: a[first:first + n], pp[kind]))
        return carry, None

    (x, counted), _ = jax.lax.scan(
        one_period, (x, jnp.zeros((2,), jnp.float32)),
        {kind: stacked(kind) for kind in seen if seen[kind]})
    rows = b * s * config.linear_num_value_heads \
        * max(1, sum(kind == LINEAR for kind in config.layer_types))
    return rms_norm(x, params["final_norm"], config.rms_norm_eps), counted / rows


def forward(params: Dict[str, Any], tokens, config: OlmoHybridConfig):
    """tokens: [B, S] int32 -> logits [B, S, vocab] (float32)."""
    x, _ = hidden(params, tokens, config)
    return (x @ params["lm_head"]).astype(jnp.float32)


def loss_and_counters(params, tokens, targets, config: OlmoHybridConfig,
                      mesh=None, rules=None, mask=None):
    """The mean cross-entropy of the next token over the vocabulary held here
    (the fused, sequence-chunked head of ``ops/loss.py``), and the step's
    ``GDN_COUNTERS`` by name. One chip's program: a mesh is not written."""
    from ray_tpu.ops.loss import fused_cross_entropy

    if mesh is not None:
        raise NotImplementedError(
            "the olmo_hybrid family trains on one chip's share: no exchange "
            "between the chips that share the vocabulary is written")
    x, counted = hidden(params, tokens, config)
    with jax.named_scope("head"):
        value = fused_cross_entropy(x, params["lm_head"], targets, mask)
    return value, dict(zip(GDN_COUNTERS, counted))


def loss(params, tokens, targets, config: OlmoHybridConfig, mesh=None,
         rules=None, mask=None):
    return loss_and_counters(params, tokens, targets, config, mesh, rules,
                             mask)[0]
