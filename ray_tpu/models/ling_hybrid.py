"""Ling-3.0 class hybrid decoder for the serving engine (``model_type``
bailing_hybrid, inclusionAI/Ling-3.0-flash): groups of ``layer_group_size``
layers, every layer but a group's last a Kimi-Delta-Attention (KDA) mixer,
the last a multi-head LATENT attention (MLA) mixer; a dense SwiGLU in the
first ``first_k_dense_replace`` layers, after them routed SwiGLU experts
behind a GROUP-LIMITED sigmoid router with a correction bias, plus one shared
expert.

Every layer is ``h += mixer(rms(h)); h += FFN(rms(h))``.

- KDA (a head of ``num_attention_heads``, ``d = head_dim``): ``[q~ | k~ |
  v~ | f | g] = y W_in`` (five widths of heads x d); q~, k~, v~ through a
  depthwise causal convolution over the newest ``short_conv_kernel_size``
  rows (``ops/ssm.py``'s) and SiLU; ``q = l2norm(q~) / sqrt(d)``, ``k =
  l2norm(k~)``, ``v = v~``; the decay a CHANNEL, ``a = kda_lower_bound x
  sigmoid(exp(A_log) (f + dt_bias))``, in (``kda_lower_bound``, 0); ``beta =
  sigmoid(y w_beta)`` a head; the state ``S`` [d, d] a head in float32 moved
  by ``ops/kda.py``; ``out = [rms_head(o) * sigmoid(g)] W_o``.
- MLA (``models/kimi_k2.py``'s block at this model's sizes, WITHOUT a query
  rank): ``q = y W_q`` a head (``qk_nope_head_dim`` + ``qk_rope_head_dim``,
  the last rotated); ``[c | k_r] = y W_kva``, ``c`` normalised, ``k_r``
  rotated, ONE head for all: the cached row (``models/cache_rows.py``); scores
  scaled by ``(d_n + d_r)^-0.5``; head ``i``'s output times ``sigmoid(y
  w_gate,i)`` (``gated_attention_proj_granularity_type`` head_wise) before
  ``W_o``. Prefill UNABSORBED through the flash forward (k 192, v 128),
  decode ABSORBED through ``paged_attention_latent``.
- FFN: ``ops/moe.py``, ``scoring="sigmoid_bias"``, ``form="swiglu"``,
  ``groups=(n_group, topk_group)``; the shared expert added with weight 1.

What a request keeps between steps is of two kinds, in ONE donated cache
(``LingCache``):

- ``k``: the latent pool of the MLA layers alone, ``[1, L_mla * total_pages,
  page_size, latent_width]`` (one row of 640 a token for ONE layer in six);
- by SLOT (``models/cache_rows.py``), for each KDA layer: ``kda`` ``[L_kda,
  slots + 1, heads, d, d]`` float32, the delta-rule state, and ``conv``
  ``[L_kda, slots + 1, K - 1, 3 heads d]``, the last ``K - 1`` inputs of the
  three convolutions. Prefill overwrites both for the slots it admits; a
  decode tick moves the state in place (``ops/kda.py`` ``kda_step`` aliases
  the whole array) and an inactive slot's by nothing (``a = 0``, ``beta =
  0``).

A prefill takes each prompt ``PREFILL_ROWS`` rows at a time. A KDA layer is
ONE loop over a prompt's pieces, mixer and FFN both, with the state and the
convolution's last rows carried from piece to piece and the pieces past the
prompt's length skipped; an MLA layer walks as ``models/kimi_k2.py``'s does
(the latents, a group of heads' attention over the rows so far, ``W_o`` with
the FFN). So ONE program of the longest bucket costs each prompt what it
holds, to within a piece, and the engine compiles one prefill program.

The layer loop is unrolled (six layers in the benchmark's cut; the kinds
differ). ``num_experts`` is the experts HELD here, ``held_experts`` which of
the router's ``n_router_outputs`` they are; ``vocab_size`` the rows held.

Training of this family is not written: the backward of the chunked delta
rule is what is missing.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import cache_rows
from ray_tpu.models.paged_decode import (
    _live_lengths, _scatter_prompt_rows_full, _scatter_token_rows, _walk,
    counted_decode_steps)
from ray_tpu.ops import kda, ssm
from ray_tpu.ops.moe import routed_experts, swiglu_mlp
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.rope import apply_rope, rope_frequencies


@dataclasses.dataclass(frozen=True, eq=False)
class LingHybridConfig:
    """The source's key names (``config.json`` of ``model_type``
    bailing_hybrid); the defaults are Ling-3.0-flash's language model whole,
    without its multi-token-prediction module."""
    vocab_size: int = 157184
    hidden_size: int = 2560
    intermediate_size: int = 6144
    moe_intermediate_size: int = 768
    moe_shared_expert_intermediate_size: int = 768
    num_hidden_layers: int = 42
    first_k_dense_replace: int = 2
    layer_group_size: int = 6
    num_attention_heads: int = 32
    head_dim: int = 128
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    short_conv_kernel_size: int = 4
    kda_lower_bound: float = -5.0
    num_experts: int = 512
    n_router_outputs: int = 512
    held_experts: Tuple[int, int] = (0, 512)
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-6
    rope_theta: float = 6e6
    max_seq_len: int = 4096
    dtype: Any = jnp.bfloat16
    attention_impl: str = "auto"
    kda_impl: str = "auto"

    def __post_init__(self):
        lo, hi = self.held_experts
        if not (0 <= lo < hi <= self.n_router_outputs
                and hi - lo == self.num_experts):
            raise ValueError(
                f"held_experts {self.held_experts} must be num_experts "
                f"({self.num_experts}) of the router's "
                f"{self.n_router_outputs} outputs")
        if self.n_router_outputs % self.n_group:
            raise ValueError("the router's outputs lie in n_group equal groups")
        if self.kda_lower_bound * kda.SUB < -80:
            raise ValueError(
                f"ops/kda.py factors a pair decay over {kda.SUB} rows: "
                f"kda_lower_bound {self.kda_lower_bound} would pass e^80")

    def is_mla(self, layer: int) -> bool:
        return (layer + 1) % self.layer_group_size == 0

    def count(self, mla: bool) -> int:
        return sum(self.is_mla(i) == mla for i in range(self.num_hidden_layers))

    @property
    def kda_width(self) -> int:
        return self.num_attention_heads * self.head_dim

    @property
    def latent_width(self) -> int:
        return cache_rows.latent_width(self.kv_lora_rank,
                                       self.qk_rope_head_dim)

    @property
    def softmax_scale(self) -> float:
        """``rope_scaling`` null: no YaRN factor on the scores."""
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5

    @classmethod
    def tiny(cls, **kw) -> "LingHybridConfig":
        """CPU tests: groups of three (KDA, KDA, MLA) twice, 4 heads of 16,
        a latent of 32 + 8, one dense layer, 4 of 8 experts held (two of the
        router's four groups, the best two kept), top-2."""
        kw.setdefault("max_seq_len", 512)
        return cls(**{**dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=32, moe_shared_expert_intermediate_size=32,
            num_hidden_layers=6, first_k_dense_replace=1, layer_group_size=3,
            num_attention_heads=4, head_dim=16, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            num_experts=4, n_router_outputs=8, held_experts=(0, 4),
            num_experts_per_tok=2, n_group=4, topk_group=2), **kw})


SLOT_STATE = True  # serve/llm.py: prefill is told each row's slot
# what the decode program counts on the device, its fifth result: ops/moe.py's
# four over the expert layers and ticks; the cached latent rows attended over
# live slots, ticks and the MLA layers; the delta-rule states moved (live
# slots x KDA layers x ticks)
DECODE_COUNTERS = ("moe_assignments", "moe_assignments_held",
                   "moe_experts_touched", "moe_expert_load_max",
                   "attn_rows_latent", "kda_state_updates")
# what the prefill program counts, its third result: the compacted expert
# product's calls and extra blocks; the prompt tokens of the call's real rows;
# the causal (query, key) pairs ONE MLA layer attends over for them; and the
# rows the chunk kernel took, prompt tokens x KDA layers (pad rows apart)
PREFILL_COUNTERS = ("moe_blocks", "moe_blocks_extra", "prefill_rows",
                    "prefill_attn_pairs", "kda_rows")
# rows of ONE prompt a prefill takes at a time: a piece reads the held
# experts' weights once (0.75 GB a layer at 128 experts of 3 x 2560 x 768:
# 0.9 ms), so it is not smaller; a KDA layer's five projections of a piece
# are [2048, 20480] bfloat16, 84 MB
PREFILL_ROWS = 2048
# heads of a prefill's unabsorbed attention expanded and attended at a time
PREFILL_HEADS = 16
# as models/nemotron_h.py argues: with fan-in-scale routed outputs a near-tie
# of the router moves a token's residual by a whole expert's worth
ROUTED_OUT_SCALE = 0.25


class LingCache(NamedTuple):
    k: jax.Array     # [1, L_mla * total_pages, page_size, latent_width]
    kda: jax.Array   # [L_kda, slots + 1, heads, d, d] float32
    conv: jax.Array  # [L_kda, slots + 1, K - 1, 3 heads d]


def init_cache(config: LingHybridConfig, num_slots: int, total_pages: int,
               page_size: int) -> LingCache:
    nh, d, lk = config.num_attention_heads, config.head_dim, config.count(False)
    return LingCache(
        k=jnp.zeros((1, config.count(True) * total_pages, page_size,
                     config.latent_width), config.dtype),
        kda=cache_rows.init_slot_state(lk, num_slots, (nh, d, d), jnp.float32),
        conv=cache_rows.init_slot_state(
            lk, num_slots, (config.short_conv_kernel_size - 1,
                            3 * config.kda_width), config.dtype))


def init_params(config: LingHybridConfig, key) -> Dict[str, Any]:
    """Seeded weights: normal / sqrt(fan_in) matrices, norms of one, the
    router and its correction bias in float32 (the bias small and nonzero, so
    that choice and weights differ), the routed experts' ``w_down`` at
    ``ROUTED_OUT_SCALE``; a KDA layer's ``A_log`` = ln U(0.5, 2) a head and
    ``dt_bias`` U(-8, 1) a channel: with ``f`` about unit normal a channel's
    ``a`` then sits anywhere from -4 (it forgets in a token) to -1e-5 (it
    keeps thousands), the time scales spread evenly in the logarithm as a
    state-space layer's step sizes are seeded, so that ``a`` spans
    (``kda_lower_bound``, 0) over the channels and the state carries what a
    long prompt holds (seeded around 0, every channel forgot within two
    tokens and a prefill that dropped the state between its pieces served
    the same tokens: PERF.md 6, PR 52). Traceable."""
    h, dt = config.hidden_size, config.dtype
    nh, d, w = config.num_attention_heads, config.head_dim, config.kda_width
    rkv = config.kv_lora_rank
    dn, dr, dv = (config.qk_nope_head_dim, config.qk_rope_head_dim,
                  config.v_head_dim)
    e, r = config.num_experts, config.n_router_outputs

    def normal(k, shape, fan_in, dtype=dt):
        return (jax.random.normal(k, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(dtype)

    def gated(ks, width, lead=(), out_scale=1.0):
        return {"w_gate": normal(ks[0], lead + (h, width), h),
                "w_up": normal(ks[1], lead + (h, width), h),
                "w_down": normal(ks[2], lead + (width, h),
                                 width / out_scale ** 2)}

    def layer(i: int):
        ks = jax.random.split(jax.random.fold_in(key, i), 20)
        lp = {"attn_norm": jnp.ones((h,), dt), "mlp_norm": jnp.ones((h,), dt)}
        if config.is_mla(i):
            lp.update({
                "wq": normal(ks[0], (h, nh * (dn + dr)), h),
                "wkv_a": normal(ks[1], (h, rkv + dr), h),
                "kv_norm": jnp.ones((rkv,), dt),
                "wkv_b": normal(ks[2], (rkv, nh * (dn + dv)), rkv),
                "w_gate": normal(ks[3], (h, nh), h),
                "wo": normal(ks[4], (nh * dv, h), nh * dv)})
        else:
            k_conv = config.short_conv_kernel_size
            lp.update({
                "w_in": normal(ks[0], (h, 5 * w), h),
                "w_beta": normal(ks[1], (h, nh), h),
                "conv_w": normal(ks[2], (k_conv, 3 * w), k_conv),
                "a_log": jnp.log(jax.random.uniform(
                    ks[3], (nh,), jnp.float32, 0.5, 2.0)),
                "dt_bias": jax.random.uniform(ks[4], (w,), jnp.float32,
                                              -8.0, 1.0),
                "o_norm": jnp.ones((d,), dt),
                "wo": normal(ks[5], (w, h), w)})
        if i < config.first_k_dense_replace:
            lp["mlp"] = gated(ks[6:9], config.intermediate_size)
        else:
            lp["router"] = {
                "w": normal(ks[9], (h, r), h, jnp.float32),
                "bias": 0.05 * jax.random.normal(ks[10], (r,), jnp.float32)}
            lp["experts"] = gated(ks[11:14], config.moe_intermediate_size,
                                  (e,), ROUTED_OUT_SCALE)
            lp["shared"] = gated(
                ks[14:17], config.moe_shared_expert_intermediate_size)
        return lp

    return {
        "embed_tokens": normal(jax.random.fold_in(key, 1000),
                               (config.vocab_size, h), h),
        "layers": [layer(i) for i in range(config.num_hidden_layers)],
        "final_norm": jnp.ones((h,), dt),
        "lm_head": normal(jax.random.fold_in(key, 1001),
                          (h, config.vocab_size), h),
    }


# --------------------------------------------------------------------------- #
# Layer parts
# --------------------------------------------------------------------------- #
def _rope_tables(config: LingHybridConfig, positions: int):
    return rope_frequencies(config.qk_rope_head_dim, positions,
                            float(config.rope_theta))


def _kda_parts(config: LingHybridConfig, lp, y):
    """y: [..., h] normed -> (q~ k~ v~ before their convolution [..., 3 w],
    a [..., H, d] float32, the output gate's input [..., w], beta [..., H])."""
    nh, d, w = config.num_attention_heads, config.head_dim, config.kda_width
    parts = y @ lp["w_in"]
    f = parts[..., 3 * w:4 * w].astype(jnp.float32) + lp["dt_bias"]
    f = f.reshape(*f.shape[:-1], nh, d) * jnp.exp(lp["a_log"])[:, None]
    a = config.kda_lower_bound * jax.nn.sigmoid(f)
    beta = jax.nn.sigmoid((y @ lp["w_beta"]).astype(jnp.float32))
    return parts[..., :3 * w], a, parts[..., 4 * w:], beta


def _kda_qkv(config: LingHybridConfig, qkv):
    """Convolved q~ k~ v~ [..., 3 w] -> q, k, v [..., H, d]: SiLU, q and k
    normalised a head, q scaled."""
    nh, d = config.num_attention_heads, config.head_dim
    x = jax.nn.silu(qkv.astype(jnp.float32)).reshape(*qkv.shape[:-1], 3, nh, d)

    def unit(t):
        return t * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)

    q, k, v = x[..., 0, :, :], x[..., 1, :, :], x[..., 2, :, :]
    return ((unit(q) * d ** -0.5).astype(qkv.dtype),
            unit(k).astype(qkv.dtype), v.astype(qkv.dtype))


def _kda_out(config: LingHybridConfig, lp, o, gate):
    """o: [..., H, d]; gate: [..., w] -> [rms_head(o) * sigmoid(gate)] W_o."""
    normed = rms_norm(o, lp["o_norm"], config.rms_norm_eps)
    gated = normed.reshape(gate.shape).astype(jnp.float32) \
        * jax.nn.sigmoid(gate.astype(jnp.float32))
    return gated.astype(config.dtype) @ lp["wo"]


def _ffn(config: LingHybridConfig, lp, y, impl: str, counted):
    """y: [T, h] normed -> (the layer's FFN [T, h], ops/moe.py's counters
    over the ``counted`` rows, or None for a dense layer)."""
    if "mlp" in lp:
        with jax.named_scope("dense_mlp"):
            return swiglu_mlp(y, **lp["mlp"]), None
    with jax.named_scope("experts"):
        out, counts = routed_experts(
            y, lp["router"], lp["experts"], held=config.held_experts,
            top_k=config.num_experts_per_tok,
            scale=config.routed_scaling_factor, impl=impl, counted=counted,
            scoring="sigmoid_bias", form="swiglu",
            groups=(config.n_group, config.topk_group))
        return out + swiglu_mlp(y, **lp["shared"]), counts


def _head(config: LingHybridConfig, params, x):
    with jax.named_scope("head"):
        y = rms_norm(x, params["final_norm"], config.rms_norm_eps)
        return jnp.matmul(y, params["lm_head"],
                          preferred_element_type=jnp.float32)


# --------------------------------------------------------------------------- #
# Prefill
# --------------------------------------------------------------------------- #
def _no_counts():
    return jnp.zeros((2,), jnp.int32)


def _finish(config: LingHybridConfig, lp, x, mixed):
    """x + the mixer's output, then the FFN over the piece: [T, h] ->
    ([T, h], the compacted product's counters int32 [2])."""
    x = x + mixed
    y = rms_norm(x, lp["mlp_norm"], config.rms_norm_eps)
    # every row counted: only the compacted product's blocks are kept
    out, counts = _ffn(config, lp, y, "ragged", jnp.ones((y.shape[0],), bool))
    return x + out, _no_counts() if counts is None else counts[-2:]


def _pieces(s: int) -> int:
    """The fewest equal pieces of at most ``PREFILL_ROWS`` rows, in whole
    chunks of the delta rule (a prompt whole where no such split exists)."""
    return next((n for n in range(-(-s // PREFILL_ROWS), s // kda.CHUNK + 1)
                 if s % n == 0 and (s // n) % kda.CHUNK == 0), 1)


def _prefill_kda_layer(config: LingHybridConfig, lp, x, lengths):
    """One KDA layer of a prefill over x [PB, S, h], mixer and FFN, as ONE
    loop over the prompts' pieces: the delta-rule state and the
    convolution's last rows go from piece to piece, a piece past every
    prompt's length is skipped. Returns (x, the state [PB, H, d, d] and the
    convolution rows [PB, K - 1, 3 w] after each prompt's last token, the
    expert counters int32 [2])."""
    pb, s, h = x.shape
    nh, d, w = config.num_attention_heads, config.head_dim, config.kda_width
    taps = config.short_conv_kernel_size - 1
    n = _pieces(s)
    piece = s // n
    no_bias = jnp.zeros((3 * w,), jnp.float32)

    def one(carry, args):
        state, tail, counts = carry
        xp, start = args                                  # [PB, piece, h]
        left = jnp.clip(lengths - start, 0, piece)        # real rows here

        def run():
            with jax.named_scope("kda"):
                y = rms_norm(xp, lp["attn_norm"], config.rms_norm_eps)
                qkv, a, gate, beta = _kda_parts(config, lp, y)
                # the rows before the piece are the convolution's history
                conv, kept = ssm.causal_conv_prefill(
                    jnp.concatenate([tail, qkv], axis=1), lp["conv_w"],
                    no_bias, left + taps)
                q, k, v = _kda_qkv(config, conv[:, taps:])
                o, moved = kda.kda_prefill(q, k, v, a, beta, state, left,
                                           impl=config.kda_impl)
                mixed = _kda_out(config, lp, o, gate)
            out, c = _finish(config, lp, xp.reshape(-1, h),
                             mixed.reshape(-1, h))
            return out.reshape(xp.shape), moved, kept.astype(tail.dtype), c

        def skip():
            return jnp.zeros_like(xp), state, tail, _no_counts()

        # a prompt that ended before this piece keeps what it had: its rows
        # here are all padding to the kernel and to the convolution
        out, state, tail, c = jax.lax.cond(jnp.any(left > 0), run, skip)
        return (state, tail, counts + c), out

    cut = x.reshape(pb, n, piece, h).transpose(1, 0, 2, 3)
    carry = (jnp.zeros((pb, nh, d, d), jnp.float32),
             jnp.zeros((pb, taps, 3 * w), x.dtype), _no_counts())
    (state, tail, counts), out = jax.lax.scan(
        one, carry, (cut, jnp.arange(n, dtype=jnp.int32) * piece))
    return out.transpose(1, 0, 2, 3).reshape(pb, s, h), state, tail, counts


def _prefill_mla_layer(config: LingHybridConfig, lp, x, pool, layer_pages,
                       rope, positions, lengths):
    """One MLA layer of a prefill over x [PB, S, h] -> (x, pool, the expert
    counters int32 [2]), in three walks over the prompts' rows, as
    ``models/kimi_k2.py``'s: the latents; a group of ``PREFILL_HEADS`` heads
    at a time, their q, k and v expanded (unabsorbed) and attended over the
    rows so far; the gate, W_o and the layer's FFN."""
    from ray_tpu.ops.attention import attention

    pb, s, _ = x.shape
    nh, rkv = config.num_attention_heads, config.kv_lora_rank
    dn, dr, dv = (config.qk_nope_head_dim, config.qk_rope_head_dim,
                  config.v_head_dim)
    g = math.gcd(nh, PREFILL_HEADS)

    def latents(x, pos):
        y = rms_norm(x, lp["attn_norm"], config.rms_norm_eps)
        rows = cache_rows.latent_rows(
            (y @ lp["wkv_a"])[None], lp["kv_norm"], config.rms_norm_eps, rkv,
            config.latent_width, rope, pos[None])
        return (y, rows[0]), _no_counts()

    def heads(w):
        wq, wkv_b = w                     # [h, g (dn + dr)], [r_kv, g (dn + dv)]

        def expand(y, rows, pos):
            q = (y @ wq).reshape(-1, g, dn + dr)
            q_r = apply_rope(q[None, ..., dn:], *rope, pos[None])[0]
            kv = (rows[:, 0, :rkv] @ wkv_b).reshape(-1, g, dn + dv)
            k_r = jnp.broadcast_to(rows[..., rkv:rkv + dr], q_r.shape)
            return (jnp.concatenate([q[..., :dn], q_r], axis=-1),
                    jnp.concatenate([kv[..., :dn], k_r], axis=-1),
                    kv[..., dn:]), _no_counts()

        (q, k, v), _ = _walk(expand, (y, rows, positions), lengths,
                             PREFILL_ROWS)
        return attention(q, k, v, causal=True, scale=config.softmax_scale,
                         impl=config.attention_impl, lengths=lengths)

    def by_group(w, width):
        return w.reshape(w.shape[0], nh // g, g * width).transpose(1, 0, 2)

    def finish(x, y, o):
        gate = jax.nn.sigmoid((y @ lp["w_gate"]).astype(jnp.float32))
        o = (o.reshape(-1, nh, dv) * gate[..., None]).astype(x.dtype)
        return _finish(config, lp, x, o.reshape(-1, nh * dv) @ lp["wo"])

    with jax.named_scope("mla"):
        (y, rows), _ = _walk(latents, (x, positions), lengths, PREFILL_ROWS)
        pool = _scatter_prompt_rows_full(pool, rows, layer_pages)
        o = jax.lax.map(heads, (by_group(lp["wq"], dn + dr),
                                by_group(lp["wkv_b"], dn + dv)))
        o = o.transpose(1, 2, 0, 3, 4).reshape(pb, s, nh * dv)
    x, counts = _walk(finish, (x, y, o), lengths, PREFILL_ROWS)
    return x, pool, counts


def paged_prefill(params, cache: LingCache, tokens, pages, lengths, slots,
                  config: LingHybridConfig, page_size: int):
    """BATCHED prefill: tokens [PB, S_bucket] right-padded; pages [PB,
    S_bucket // page_size]; lengths [PB] (a pad row: 1 and the trash page);
    slots [PB] the slot each row was admitted to (a pad row: the trash row).
    Every MLA layer writes the prompt's latent rows at ``l * P + pages``;
    every KDA layer OVERWRITES the slots' state and convolution rows with
    what each prompt's last real token leaves. What a call costs follows
    ``lengths``, not the bucket. Returns (last-token logits [PB, V], cache,
    int32 [5]: the ``PREFILL_COUNTERS`` of this call; pad rows count a token
    and a pair each, and a row a KDA layer)."""
    pb, s = tokens.shape
    x = params["embed_tokens"][tokens].astype(config.dtype)
    rope = _rope_tables(config, s)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (pb, s))
    pool, states, convs = cache
    per_layer = pool.shape[1] // config.count(True)
    counts = _no_counts()
    i_mla = i_kda = 0
    for i, lp in enumerate(params["layers"]):
        if config.is_mla(i):
            x, pool, c = _prefill_mla_layer(
                config, lp, x, pool, pages + i_mla * per_layer, rope,
                positions, lengths)
            i_mla += 1
        else:
            x, state, tail, c = _prefill_kda_layer(config, lp, x, lengths)
            states = cache_rows.put_prompt_state(states, i_kda, slots, state)
            convs = cache_rows.put_prompt_state(convs, i_kda, slots, tail)
            i_kda += 1
        counts = counts + c
    last = jnp.take_along_axis(x, (lengths - 1)[:, None, None], axis=1)[:, 0]
    rows = jnp.sum(lengths)
    counted = jnp.stack([rows, jnp.sum(lengths * (lengths + 1) // 2),
                         rows * config.count(False)])
    return (_head(config, params, last), LingCache(pool, states, convs),
            jnp.concatenate([counts, counted.astype(jnp.int32)]))


# --------------------------------------------------------------------------- #
# Decode
# --------------------------------------------------------------------------- #
def _decode_kda(config: LingHybridConfig, lp, y, states, convs, layer: int,
                active):
    """A KDA mixer's tick over every slot. y: [B, h] normed -> (out [B, h],
    states, convs). An inactive slot's state and rows do not move."""
    nb = y.shape[0]
    qkv, a, gate, beta = _kda_parts(config, lp, y)
    had = cache_rows.slot_state(convs, layer, nb)
    conv, kept = ssm.causal_conv_step(
        qkv, had, lp["conv_w"], jnp.zeros((qkv.shape[-1],), jnp.float32))
    convs = cache_rows.put_slot_state(
        convs, layer, jnp.where(active[:, None, None], kept, had))
    q, k, v = _kda_qkv(config, conv)
    o, states = kda.kda_step(
        q, k, v, jnp.where(active[:, None, None], a, 0.0),
        jnp.where(active[:, None], beta, 0.0), states, layer=layer,
        impl=config.kda_impl)
    return _kda_out(config, lp, o, gate), states, convs


def _decode_mla(config: LingHybridConfig, lp, y, pool, base, tick,
                use_kernel: bool):
    """An MLA mixer's tick over every slot, absorbed. y: [B, h] normed ->
    (out [B, h], pool)."""
    rope, safe_pos, pages, rows, lengths, table = tick
    nb = y.shape[0]
    nh, dn, dr = (config.num_attention_heads, config.qk_nope_head_dim,
                  config.qk_rope_head_dim)
    row = cache_rows.latent_rows(
        (y @ lp["wkv_a"])[:, None], lp["kv_norm"], config.rms_norm_eps,
        config.kv_lora_rank, config.latent_width, rope, safe_pos[:, None])
    pool = _scatter_token_rows(pool, row[:, 0], pages + base, rows)
    q = (y @ lp["wq"]).reshape(nb, nh, dn + dr)
    o = cache_rows.absorbed_attention(
        q, lp["wkv_b"], pool, table, base, lengths, rope, safe_pos,
        rank=config.kv_lora_rank, nope=dn, scale=config.softmax_scale,
        use_kernel=use_kernel)
    gate = jax.nn.sigmoid((y @ lp["w_gate"]).astype(jnp.float32))
    o = (o * gate[..., None]).astype(y.dtype)
    return o.reshape(nb, -1) @ lp["wo"], pool


def paged_decode_one(params, cache: LingCache, tokens, positions, active,
                     table, config: LingHybridConfig, page_size: int,
                     use_kernel: bool, rope=None):
    """One decode tick over every slot. tokens / positions / active: [B];
    table: [B, max_pages]. Returns (logits [B, V], cache, int32 [6]: the
    ``DECODE_COUNTERS`` of this tick). An inactive slot's latent row lands in
    the trash page, it attends over nothing and its state does not move."""
    max_ctx = table.shape[1] * page_size
    x = params["embed_tokens"][tokens].astype(config.dtype)          # [B, h]
    safe_pos = jnp.minimum(positions, max_ctx - 1)
    pages = jnp.take_along_axis(table, (safe_pos // page_size)[:, None],
                                axis=1)[:, 0]
    lengths = _live_lengths(safe_pos, active)
    tick = (rope or _rope_tables(config, max_ctx), safe_pos, pages,
            safe_pos % page_size, lengths, table)
    pool, states, convs = cache
    per_layer = pool.shape[1] // config.count(True)
    counts = jnp.zeros((4,), jnp.int32)
    i_mla = i_kda = 0
    for i, lp in enumerate(params["layers"]):
        y = rms_norm(x, lp["attn_norm"], config.rms_norm_eps)
        if config.is_mla(i):
            with jax.named_scope("mla"):
                out, pool = _decode_mla(config, lp, y, pool,
                                        i_mla * per_layer, tick, use_kernel)
            i_mla += 1
        else:
            with jax.named_scope("kda"):
                out, states, convs = _decode_kda(config, lp, y, states, convs,
                                                 i_kda, active)
            i_kda += 1
        x = x + out
        y = rms_norm(x, lp["mlp_norm"], config.rms_norm_eps)
        # every held expert over every row: a tick's rows reach few of them,
        # but a loaded deployment's would reach all, and reads all the same
        out, c = _ffn(config, lp, y, "dense", active)
        x = x + out
        if c is not None:
            counts = counts + c
    live = jnp.sum(active)
    moved = jnp.stack([config.count(True) * jnp.sum(lengths),
                       config.count(False) * live]).astype(jnp.int32)
    return (_head(config, params, x), LingCache(pool, states, convs),
            jnp.concatenate([counts, moved]))


def paged_decode_steps(params, cache: LingCache, tokens, positions, active,
                       table, key, config: LingHybridConfig, num_steps: int,
                       page_size: int, use_kernel: bool,
                       temperature: float = 0.0):
    """``num_steps`` decode ticks on the device, as
    ``models/paged_decode.py`` ``paged_decode_steps``; the fifth result is
    ``DECODE_COUNTERS`` summed over ticks and layers."""
    rope = _rope_tables(config, table.shape[1] * page_size)
    return counted_decode_steps(
        lambda cache, toks, pos: paged_decode_one(
            params, cache, toks, pos, active, table, config, page_size,
            use_kernel, rope),
        cache, tokens, positions, active, key, num_steps, temperature,
        len(DECODE_COUNTERS))


def paged_kernel_fits(config: LingHybridConfig) -> bool:
    """``paged_attention_latent`` slices the values off the fetched rows at
    a lane tile."""
    return config.kv_lora_rank % cache_rows.LANES == 0


def make_paged_decode_fn(config: LingHybridConfig, num_steps: int,
                         page_size: int, temperature: float = 0.0, *,
                         use_kernel: bool):
    fn = functools.partial(paged_decode_steps, config=config,
                           num_steps=num_steps, page_size=page_size,
                           use_kernel=use_kernel, temperature=temperature)
    fn.__name__ = "ling_decode"  # jit_ling_decode in a profile
    return jax.jit(fn, donate_argnums=(1,))


def make_paged_prefill_fn(config: LingHybridConfig, page_size: int):
    fn = functools.partial(paged_prefill, config=config, page_size=page_size)
    fn.__name__ = "ling_prefill"  # jit_ling_prefill in a profile
    return jax.jit(fn, donate_argnums=(1,))
