"""Nemotron-H class hybrid decoder for the serving engine: every layer is ONE
mixer behind a pre-norm and a residual, and ``pattern`` says which: ``M`` a
Mamba-2 mixer, ``E`` a mixture of experts (routed + one shared), ``*`` causal
grouped-query attention WITHOUT rotary embedding (the ``nemotron_h``
reference applies none in these layers; position is carried by the Mamba
layers). Then ``norm_f`` and an untied head.

What a request keeps between steps is of two kinds, in one donated cache
(``HybridCache``):

- pages of K/V for the attention layers, in ``models/paged_decode.py``'s pool
  layout ``[n_kv, L_attn * P, ps, D]`` (layer blocks counted over the
  attention layers only), written and read with that module's token write
  and paged attention;
- per SLOT, for each Mamba layer, the state ``S`` ``[H, P, N]`` in float32
  and the last ``conv_kernel - 1`` inputs of the convolution, whatever the
  length: ``ssm [L_m, slots + 1, H, P, N]``, ``conv [L_m, slots + 1, K-1, C]``.
  Row ``slots`` is the trash row: a padded prefill row writes there, as a
  padded row's pages are the trash page. Prefill OVERWRITES the state of the
  slots it admits, so a retired slot needs no clearing; a decode tick moves
  the state of active slots only (an inactive row takes ``dt = 0``).

The layer loop is unrolled over the pattern (13 layers in the benchmark's
cut), not scanned: the kinds differ. Weights are a list of per-layer dicts.

The expert layers hold ``held_experts = (lo, hi)`` of the ``n_router_outputs``
experts the router scores (``ops/moe.py``): the chip's share of an
expert-parallel deployment, or all of them. ``vocab_size`` is the rows of the
vocabulary held here.

Training of this family is not written: ``train/step.py`` would step its
loss, and the backward of the chunked scan and of its kernels is what is
missing.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models.cache_rows import (
    init_slot_state, put_prompt_state, put_slot_state, slot_state)
from ray_tpu.models.paged_decode import (
    _live_lengths, _paged_attention, _scatter_prompt_rows_full,
    _scatter_token_rows, counted_decode_steps)
from ray_tpu.ops import ssm
from ray_tpu.ops.moe import relu2_mlp, routed_experts
from ray_tpu.ops.norms import rms_norm


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    """The source's key names (``config.json`` of ``model_type`` nemotron_h).
    ``n_routed_experts`` is the experts HELD here, ``held_experts`` which of
    the router's ``n_router_outputs`` they are."""
    vocab_size: int = 131072
    hidden_size: int = 2688
    pattern: str = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    n_routed_experts: int = 128
    n_router_outputs: int = 128
    held_experts: Tuple[int, int] = (0, 128)
    num_experts_per_tok: int = 6
    routed_scaling_factor: float = 2.5
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    norm_eps: float = 1e-5
    max_seq_len: int = 4096
    dtype: Any = jnp.bfloat16
    attention_impl: str = "auto"

    def __post_init__(self):
        lo, hi = self.held_experts
        if not set(self.pattern) <= set("ME*"):
            raise ValueError(f"layer kinds are M, E and *: {self.pattern!r}")
        if not (0 <= lo < hi <= self.n_router_outputs
                and hi - lo == self.n_routed_experts):
            raise ValueError(
                f"held_experts {self.held_experts} must be n_routed_experts "
                f"({self.n_routed_experts}) of the router's "
                f"{self.n_router_outputs} outputs")

    @property
    def mamba_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_channels(self) -> int:
        return self.mamba_inner + 2 * self.n_groups * self.ssm_state_size

    def count(self, kind: str) -> int:
        return self.pattern.count(kind)

    @classmethod
    def tiny(cls, **kw) -> "NemotronHConfig":
        """CPU tests: every kind of layer, 4 of 8 experts held, top-2."""
        kw.setdefault("max_seq_len", 512)
        return cls(**{**dict(
            vocab_size=256, hidden_size=64, pattern="ME*M", mamba_num_heads=4,
            mamba_head_dim=16, ssm_state_size=16, n_groups=2, chunk_size=16,
            num_attention_heads=4, num_key_value_heads=2, head_dim=32,
            n_routed_experts=4, n_router_outputs=8, held_experts=(0, 4),
            num_experts_per_tok=2, moe_intermediate_size=32,
            moe_shared_expert_intermediate_size=64), **kw})


SLOT_STATE = True  # serve/llm.py: prefill is told each row's slot
# Seeded weights only. Top 6 of 128 near-tied sigmoid scores: a bfloat16
# residual moves a score by a few thousandths and 1-3% of the tokens of an
# expert layer choose another sixth expert than a float32 computation of the
# same weights (the spacing of the 6th and 7th of 128 normal scores is 0.07 of
# their spread). At fan-in scale a routed expert is a sixth of a layer's
# output and the layer most of the residual, so each such choice moved the
# token's residual by a quarter, the next layer's choices followed, and by the
# fifth expert layer half the tokens had another set (PERF.md section 6,
# PR 29). At a quarter of fan-in scale a routed expert's share of the residual
# is small, as in a trained network, and a near-tie costs what it costs there.
ROUTED_OUT_SCALE = 0.25


class HybridCache(NamedTuple):
    k: jax.Array     # [n_kv, L_attn * total_pages, page_size, D]
    v: jax.Array
    ssm: jax.Array   # [L_m, slots + 1, H, P, N] float32
    conv: jax.Array  # [L_m, slots + 1, K - 1, C]


def init_cache(config: NemotronHConfig, num_slots: int, total_pages: int,
               page_size: int) -> HybridCache:
    pool = (config.num_key_value_heads, config.count("*") * total_pages,
            page_size, config.head_dim)
    lm = config.count("M")
    return HybridCache(
        k=jnp.zeros(pool, config.dtype), v=jnp.zeros(pool, config.dtype),
        ssm=init_slot_state(lm, num_slots, (
            config.mamba_num_heads, config.mamba_head_dim,
            config.ssm_state_size), jnp.float32),
        conv=init_slot_state(lm, num_slots, (
            config.conv_kernel - 1, config.conv_channels), config.dtype))


def init_params(config: NemotronHConfig, key) -> Dict[str, Any]:
    """Seeded weights: normal / sqrt(fan_in) matrices; the Mamba layers'
    ``dt_bias`` so that softplus gives a step log-uniform in
    [time_step_min, time_step_max], ``A`` uniform in [-16, -1], ``D`` one
    (the published initialisation); the router's score-correction bias small
    and nonzero, so that the choice and the weights differ; the routed
    experts' ``w_down`` at ``ROUTED_OUT_SCALE`` of that. Traceable: call it
    under ``jit``."""
    h, dt = config.hidden_size, config.dtype
    heads, inner = config.mamba_num_heads, config.mamba_inner
    nh, nkv, hd = (config.num_attention_heads, config.num_key_value_heads,
                   config.head_dim)
    f, fs = config.moe_intermediate_size, config.moe_shared_expert_intermediate_size
    e, r = config.n_routed_experts, config.n_router_outputs

    def normal(k, shape, fan_in, dtype=dt):
        return (jax.random.normal(k, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(dtype)

    def mamba(k):
        ks = jax.random.split(k, 6)
        step = jnp.exp(jax.random.uniform(
            ks[3], (heads,), jnp.float32, jnp.log(config.time_step_min),
            jnp.log(config.time_step_max)))
        return {
            "norm": jnp.ones((h,), dt),
            "w_in": normal(ks[0], (h, inner + config.conv_channels + heads), h),
            "conv_w": normal(ks[1], (config.conv_kernel, config.conv_channels),
                             config.conv_kernel),
            "conv_b": normal(ks[2], (config.conv_channels,), 100.0),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),  # softplus^-1
            "a_log": jnp.log(jax.random.uniform(ks[4], (heads,), jnp.float32,
                                                1.0, 16.0)),
            "d": jnp.ones((heads,), jnp.float32),
            "gate_norm": jnp.ones((inner,), dt),
            "w_out": normal(ks[5], (inner, h), inner),
        }

    def moe(k):
        ks = jax.random.split(k, 6)
        return {
            "norm": jnp.ones((h,), dt),
            "router": {"w": normal(ks[0], (h, r), h, jnp.float32),
                       "bias": 0.05 * jax.random.normal(ks[1], (r,), jnp.float32)},
            "experts": {"w_up": normal(ks[2], (e, h, f), h),
                        "w_down": normal(ks[3], (e, f, h),
                                         f / ROUTED_OUT_SCALE ** 2)},
            "shared": {"w_up": normal(ks[4], (h, fs), h),
                       "w_down": normal(ks[5], (fs, h), fs)},
        }

    def attn(k):
        ks = jax.random.split(k, 4)
        return {
            "norm": jnp.ones((h,), dt),
            "wq": normal(ks[0], (h, nh * hd), h),
            "wk": normal(ks[1], (h, nkv * hd), h),
            "wv": normal(ks[2], (h, nkv * hd), h),
            "wo": normal(ks[3], (nh * hd, h), nh * hd),
        }

    make = {"M": mamba, "E": moe, "*": attn}
    return {
        "embed_tokens": normal(jax.random.fold_in(key, 1000),
                               (config.vocab_size, h), h),
        "layers": [make[kind](jax.random.fold_in(key, i))
                   for i, kind in enumerate(config.pattern)],
        "final_norm": jnp.ones((h,), dt),
        "lm_head": normal(jax.random.fold_in(key, 1001),
                          (h, config.vocab_size), h),
    }


# --------------------------------------------------------------------------- #
# Mixers
# --------------------------------------------------------------------------- #
def _mamba_parts(config: NemotronHConfig, lp, y):
    """y: [..., h] normed -> z [..., inner], xBC [..., C], dt [..., H] raw."""
    inner, c = config.mamba_inner, config.conv_channels
    parts = y @ lp["w_in"]
    return parts[..., :inner], parts[..., inner:inner + c], parts[..., inner + c:]


def _mamba_split(config: NemotronHConfig, xbc):
    """Convolved, activated xBC [..., C] -> X [..., H, P], B, C [..., G, N]."""
    inner, gn = config.mamba_inner, config.n_groups * config.ssm_state_size
    lead = xbc.shape[:-1]
    x = xbc[..., :inner].reshape(*lead, config.mamba_num_heads, config.mamba_head_dim)
    b = xbc[..., inner:inner + gn].reshape(*lead, config.n_groups, config.ssm_state_size)
    c = xbc[..., inner + gn:].reshape(*lead, config.n_groups, config.ssm_state_size)
    return x, b, c


def _step_size(lp, dt_raw):
    return jax.nn.softplus(dt_raw.astype(jnp.float32) + lp["dt_bias"])


def _mamba_out(config: NemotronHConfig, lp, y, z):
    """y, z: [..., inner]. Gate, then RMS norm over each of the n_groups
    groups of the inner width, then the output projection."""
    g = config.n_groups
    gated = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    grouped = gated.reshape(*gated.shape[:-1], g, gated.shape[-1] // g)
    var = jnp.mean(grouped * grouped, axis=-1, keepdims=True)
    normed = (grouped * jax.lax.rsqrt(var + config.norm_eps)).reshape(gated.shape)
    return (normed * lp["gate_norm"].astype(jnp.float32)).astype(config.dtype) \
        @ lp["w_out"]


def _moe(config: NemotronHConfig, lp, y, impl: str, counted=None):
    """y: [T, h] normed. Routed experts held here + the shared expert.
    ``impl``: prefill sorts its thousands of tokens by expert ("ragged");
    decode multiplies its 128 rows with every held expert ("dense": 18.3 ms
    a step against 71.6 for XLA's grouped product of the time, which re-laid
    every expert matrix a call; my chip run, PR 29. The grouped product is
    since PR 47 ``ops/grouped_matmul.py``'s kernel, which re-lays nothing;
    decode through it: not measured)."""
    routed = routed_experts(
        y, lp["router"], lp["experts"], held=config.held_experts,
        top_k=config.num_experts_per_tok, scale=config.routed_scaling_factor,
        impl=impl, counted=counted)
    shared = relu2_mlp(y, lp["shared"]["w_up"], lp["shared"]["w_down"])
    if counted is None:
        return routed + shared
    return routed[0] + shared, routed[1]


def _qkv(config: NemotronHConfig, lp, y):
    """y: [B, T, h] -> q [B, T, nh, D], k, v [B, T, nkv, D]. No rotary."""
    b, t, _ = y.shape
    nh, nkv, hd = (config.num_attention_heads, config.num_key_value_heads,
                   config.head_dim)
    return ((y @ lp["wq"]).reshape(b, t, nh, hd),
            (y @ lp["wk"]).reshape(b, t, nkv, hd),
            (y @ lp["wv"]).reshape(b, t, nkv, hd))


def _head(config: NemotronHConfig, params, x):
    y = rms_norm(x, params["final_norm"], config.norm_eps)
    return jnp.matmul(y, params["lm_head"], preferred_element_type=jnp.float32)


def _pages_per_layer(cache: HybridCache, config: NemotronHConfig) -> int:
    return cache.k.shape[1] // config.count("*")


# --------------------------------------------------------------------------- #
# Prefill
# --------------------------------------------------------------------------- #
def paged_prefill(params, cache: HybridCache, tokens, pages, lengths, slots,
                  config: NemotronHConfig, page_size: int):
    """BATCHED prefill: tokens [PB, S_bucket] right-padded; pages
    [PB, S_bucket // page_size]; lengths [PB] true lengths; slots [PB] the
    slot each row was admitted to (a pad row: the trash row). Writes the
    prompts' K/V pages and OVERWRITES the slots' recurrent state with what
    each row's last real token leaves. Returns (last-token logits [PB, V],
    cache)."""
    from ray_tpu.ops.attention import attention

    pb, s = tokens.shape
    x = params["embed_tokens"][tokens].astype(config.dtype)
    per_layer = _pages_per_layer(cache, config)
    ck, cv, cs, cc = cache
    a_idx = m_idx = 0
    for kind, lp in zip(config.pattern, params["layers"]):
        y = rms_norm(x, lp["norm"], config.norm_eps)
        if kind == "M":
            z, xbc, dt_raw = _mamba_parts(config, lp, y)
            xbc, kept = ssm.causal_conv_prefill(
                xbc, lp["conv_w"], lp["conv_b"], lengths)
            xh, b, c = _mamba_split(config, jax.nn.silu(xbc))
            out, state = ssm.mamba2_prefill(
                xh, _step_size(lp, dt_raw), -jnp.exp(lp["a_log"]), b, c, lp["d"],
                jnp.zeros((pb,) + cs.shape[2:], jnp.float32), lengths,
                chunk=config.chunk_size)
            cs = put_prompt_state(cs, m_idx, slots, state)
            cc = put_prompt_state(cc, m_idx, slots, kept)
            m_idx += 1
            x = x + _mamba_out(config, lp, out.reshape(pb, s, -1), z)
        elif kind == "E":
            x = x + _moe(config, lp, y.reshape(pb * s, -1),
                         "ragged").reshape(pb, s, -1)
        else:
            q, k, v = _qkv(config, lp, y)
            o = attention(q, k, v, causal=True, impl=config.attention_impl)
            x = x + o.reshape(pb, s, -1) @ lp["wo"]
            layer_pages = pages + a_idx * per_layer
            ck = _scatter_prompt_rows_full(ck, k, layer_pages)
            cv = _scatter_prompt_rows_full(cv, v, layer_pages)
            a_idx += 1
    last = jnp.take_along_axis(x, (lengths - 1)[:, None, None], axis=1)[:, 0]
    return _head(config, params, last), HybridCache(ck, cv, cs, cc)


# --------------------------------------------------------------------------- #
# Decode
# --------------------------------------------------------------------------- #
def paged_decode_one(params, cache: HybridCache, tokens, positions, active,
                     table, config: NemotronHConfig, page_size: int,
                     use_kernel: bool):
    """One decode tick over every slot. tokens / positions / active: [B];
    table: [B, max_pages]. Returns (logits [B, V], cache, int32 [4] of the
    expert layers' counts over active rows, summed over layers). An inactive
    slot's K/V write lands in its layer's trash page (its table row is
    zeros) and its recurrent state does not move."""
    nb = tokens.shape[0]
    scale = config.head_dim ** -0.5
    max_ctx = table.shape[1] * page_size
    x = params["embed_tokens"][tokens].astype(config.dtype)          # [B, h]
    safe_pos = jnp.minimum(positions, max_ctx - 1)
    pages = jnp.take_along_axis(
        table, (safe_pos // page_size)[:, None], axis=1)[:, 0]
    rows = safe_pos % page_size
    lengths = _live_lengths(safe_pos, active)
    per_layer = _pages_per_layer(cache, config)
    ck, cv, cs, cc = cache
    counts = jnp.zeros((4,), jnp.int32)
    a_idx = m_idx = 0
    for kind, lp in zip(config.pattern, params["layers"]):
        y = rms_norm(x, lp["norm"], config.norm_eps)
        if kind == "M":
            z, xbc, dt_raw = _mamba_parts(config, lp, y)
            xbc, kept = ssm.causal_conv_step(
                xbc, slot_state(cc, m_idx, nb), lp["conv_w"], lp["conv_b"])
            kept = jnp.where(active[:, None, None], kept,
                             slot_state(cc, m_idx, nb))
            xh, b, c = _mamba_split(config, jax.nn.silu(xbc))
            dt = jnp.where(active[:, None], _step_size(lp, dt_raw), 0.0)
            out, state = ssm.mamba2_step(
                xh, dt, -jnp.exp(lp["a_log"]), b, c, lp["d"],
                slot_state(cs, m_idx, nb))
            cs = put_slot_state(cs, m_idx, state)
            cc = put_slot_state(cc, m_idx, kept)
            m_idx += 1
            x = x + _mamba_out(config, lp, out.reshape(nb, -1), z)
        elif kind == "E":
            out, layer_counts = _moe(config, lp, y, "dense", counted=active)
            counts = counts + layer_counts
            x = x + out
        else:
            q, k, v = _qkv(config, lp, y[:, None])
            base = a_idx * per_layer
            ck = _scatter_token_rows(ck, k[:, 0], pages + base, rows)
            cv = _scatter_token_rows(cv, v[:, 0], pages + base, rows)
            o = _paged_attention(q, ck, cv, table + base, lengths, scale,
                                 use_kernel)
            x = x + o.reshape(nb, -1) @ lp["wo"]
            a_idx += 1
    return _head(config, params, x), HybridCache(ck, cv, cs, cc), counts


def paged_decode_steps(params, cache: HybridCache, tokens, positions, active,
                       table, key, config: NemotronHConfig, num_steps: int,
                       page_size: int, use_kernel: bool,
                       temperature: float = 0.0):
    """``num_steps`` decode ticks on the device, as
    ``models/paged_decode.py`` ``paged_decode_steps``; the fifth result is
    the expert layers' counts (``ops/moe.py``) summed over ticks and layers."""
    return counted_decode_steps(
        lambda cache, toks, pos: paged_decode_one(
            params, cache, toks, pos, active, table, config, page_size,
            use_kernel),
        cache, tokens, positions, active, key, num_steps, temperature, 4)


def paged_kernel_fits(config: NemotronHConfig) -> bool:
    """The Pallas paged-attention kernel tiles head_dim onto 128 lanes."""
    return config.head_dim % 128 == 0


def make_paged_decode_fn(config: NemotronHConfig, num_steps: int, page_size: int,
                         temperature: float = 0.0, *, use_kernel: bool):
    fn = functools.partial(paged_decode_steps, config=config,
                           num_steps=num_steps, page_size=page_size,
                           use_kernel=use_kernel, temperature=temperature)
    fn.__name__ = "nemotron_h_decode_steps"  # jit_nemotron_h_decode_steps
    return jax.jit(fn, donate_argnums=(1,))


def make_paged_prefill_fn(config: NemotronHConfig, page_size: int):
    fn = functools.partial(paged_prefill, config=config, page_size=page_size)
    fn.__name__ = "nemotron_h_prefill"  # jit_nemotron_h_prefill in a profile
    return jax.jit(fn, donate_argnums=(1,))
