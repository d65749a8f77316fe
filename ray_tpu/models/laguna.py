"""Laguna class decoder for the serving engine (``model_type`` laguna,
poolside/Laguna-XS.2): pre-norm residual blocks whose attention differs BY
LAYER, and a mixture of experts behind a softmax router.

- ``layer_types[l]`` is ``full_attention`` or ``sliding_attention``; the
  layer has ``num_attention_heads_per_layer[l]`` query heads over the same
  ``num_key_value_heads`` (48 / 8 on full layers, 64 / 8 on sliding ones, so
  the query groups are 6 and 8 in one model). A sliding layer's query sees
  its ``sliding_window`` newest keys, itself among them.
- two rotary schemes, chosen by layer type (``rope_parameters``): sliding
  layers rotate the whole head with plain frequencies; full layers rotate
  the first ``partial_rotary_factor x head_dim`` dimensions with YaRN
  frequencies and its attention factor (``ops/rope.py``).
- the attention output is gated per head: ``sigmoid(u W_g)`` times each
  head's output, before ``W_o``.
- ``mlp_layer_types[l]`` is ``dense`` (a SwiGLU MLP of ``intermediate_size``)
  or ``sparse``: ``moe_routed_scaling_factor`` x the top
  ``num_experts_per_tok`` of a float32 softmax over ``n_router_outputs``,
  normalised over the chosen, each expert a SwiGLU of
  ``moe_intermediate_size``, plus one shared expert added ungated
  (``ops/moe.py``: ``scoring="softmax"``, ``form="swiglu"``).

What a request keeps between steps is of two kinds, in one donated cache
(``LagunaCache``):

- PAGES from the engine's allocator for the full layers, in
  ``models/paged_decode.py``'s pool layout ``[n_kv, L_full * P, ps, D]``;
- per SLOT, for every sliding layer, a RING of ``ring_pages`` pages
  (``(window - 1) // ps + 2``: 9 at a window of 512 and pages of 64) that
  the allocator never sees: ``[n_kv, L_win * (slots + 1) * ring, ps, D]``.
  Position ``p`` lives in ring page ``(p // ps) % ring``, row ``p % ps``.
  Ring ``slots`` is the trash ring (pad rows of a prefill, inactive slots of
  a decode tick). Prefill writes the last ``ring`` pages of a prompt and
  nothing else of it; decode hands the kernel the ring in logical order from
  the oldest live page (``ops/paged_attention.py`` ``starts``). A retired
  slot's ring needs no clearing: what a new request has not written is
  behind its start or beyond its length.

The layers are unrolled (the kinds differ). ``num_experts`` is the experts
HELD here, ``held_experts`` which of the router's ``n_router_outputs`` they
are; ``vocab_size`` the rows of the vocabulary held here.

Training of this family is not written.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Mapping, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models.paged_decode import (
    _live_lengths, _paged_attention, _scatter_prompt_rows_full,
    _scatter_token_rows, counted_decode_steps, ring_pages_of,
    ring_prompt_pages, ring_rows, ring_tick)
from ray_tpu.ops.moe import routed_experts, swiglu_mlp
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.rope import apply_rope, rope_frequencies

FULL, SLIDING = "full_attention", "sliding_attention"
PERIOD = (FULL, SLIDING, SLIDING, SLIDING)
ROPE_XS2 = {
    FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
           "original_max_position_embeddings": 4096, "beta_slow": 1,
           "beta_fast": 64, "attention_factor": 1.4158883083359672,
           "partial_rotary_factor": 0.5},
    SLIDING: {"rope_type": "default", "rope_theta": 10000,
              "partial_rotary_factor": 1},
}


@dataclasses.dataclass(frozen=True, eq=False)
class LagunaConfig:
    """The source's key names (``config.json`` of ``model_type`` laguna);
    the defaults are Laguna-XS.2 whole. ``num_experts`` is the experts HELD
    here, ``held_experts`` which of the router's ``n_router_outputs``."""
    vocab_size: int = 100352
    hidden_size: int = 2048
    intermediate_size: int = 8192
    layer_types: Tuple[str, ...] = PERIOD * 10
    mlp_layer_types: Tuple[str, ...] = ("dense",) + ("sparse",) * 39
    num_attention_heads_per_layer: Tuple[int, ...] = (48, 64, 64, 64) * 10
    num_key_value_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 512
    rope_parameters: Mapping[str, Mapping[str, Any]] = dataclasses.field(
        default_factory=lambda: ROPE_XS2)
    num_experts: int = 256
    n_router_outputs: int = 256
    held_experts: Tuple[int, int] = (0, 256)
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    moe_routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-6
    max_seq_len: int = 4096
    dtype: Any = jnp.bfloat16
    attention_impl: str = "auto"

    def __post_init__(self):
        lo, hi = self.held_experts
        n = len(self.layer_types)
        if not (len(self.mlp_layer_types) == n
                == len(self.num_attention_heads_per_layer)):
            raise ValueError("layer_types, mlp_layer_types and "
                             "num_attention_heads_per_layer differ in length")
        if not set(self.layer_types) <= {FULL, SLIDING}:
            raise ValueError(f"layer types are {FULL} and {SLIDING}")
        if not set(self.mlp_layer_types) <= {"dense", "sparse"}:
            raise ValueError("mlp layer types are dense and sparse")
        if any(h % self.num_key_value_heads
               for h in self.num_attention_heads_per_layer):
            raise ValueError("query heads must divide over the KV heads")
        if not (0 <= lo < hi <= self.n_router_outputs
                and hi - lo == self.num_experts):
            raise ValueError(
                f"held_experts {self.held_experts} must be num_experts "
                f"({self.num_experts}) of the router's "
                f"{self.n_router_outputs} outputs")

    def count(self, layer_type: str) -> int:
        return self.layer_types.count(layer_type)

    @classmethod
    def tiny(cls, **kw) -> "LagunaConfig":
        """CPU tests: both layer types (query groups of 3 and 4), a window of
        32, YaRN on half a head, the leading dense layer, 4 of 8 experts held,
        top-2."""
        kw.setdefault("max_seq_len", 512)
        rope = {FULL: {**ROPE_XS2[FULL], "factor": 4,
                       "original_max_position_embeddings": 64,
                       "attention_factor": 1.1386294361119891},
                SLIDING: ROPE_XS2[SLIDING]}
        return cls(**{**dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            layer_types=(FULL, SLIDING, SLIDING, SLIDING, FULL),
            mlp_layer_types=("dense",) + ("sparse",) * 4,
            num_attention_heads_per_layer=(6, 8, 8, 8, 6),
            num_key_value_heads=2, head_dim=32, sliding_window=32,
            rope_parameters=rope, num_experts=4, n_router_outputs=8,
            held_experts=(0, 4), num_experts_per_tok=2,
            moe_intermediate_size=32, shared_expert_intermediate_size=32),
            **kw})


SLOT_STATE = True  # serve/llm.py: prefill is told each row's slot (its rings)
RING_FIELDS = ("k_win", "v_win")  # the cache's fields that are window rings
# what the decode program counts on the device, in the order of its fifth
# result: ops/moe.py's six (the last two: calls of the compacted expert
# product and the blocks they ran beyond their first; a tick of 24 slots is
# under one block and runs uncompacted, so they read 0 there), then the K/V
# rows attended over live slots, ticks and layers of each kind (``length`` a
# full layer, ``min(length, window)`` a sliding one)
DECODE_COUNTERS = ("moe_assignments", "moe_assignments_held",
                   "moe_experts_touched", "moe_expert_load_max",
                   "moe_blocks", "moe_blocks_extra",
                   "attn_rows_full", "attn_rows_window")
# what the prefill program counts, its third result: the same two over the
# call's expert layers and chunks (pad rows are routed and multiplied too)
PREFILL_COUNTERS = ("moe_blocks", "moe_blocks_extra")
# tokens of a prefill the routed experts take at a time: the sorted copy of
# the rows, and the float32 results before they are summed, are 8 x the
# tokens tall (a float32 [196608, 2048] at a 24,576-token prompt is 1.6 GB)
MOE_PREFILL_TOKENS = 4096


class LagunaCache(NamedTuple):
    k: jax.Array      # [n_kv, L_full * total_pages, page_size, D]
    v: jax.Array
    k_win: jax.Array  # [n_kv, L_win * (slots + 1) * ring, page_size, D]
    v_win: jax.Array


def ring_pages(config: LagunaConfig, page_size: int) -> int:
    return ring_pages_of(config.sliding_window, page_size)


def init_cache(config: LagunaConfig, num_slots: int, total_pages: int,
               page_size: int) -> LagunaCache:
    nkv, d = config.num_key_value_heads, config.head_dim
    pool = (nkv, config.count(FULL) * total_pages, page_size, d)
    rings = (nkv, config.count(SLIDING) * (num_slots + 1)
             * ring_pages(config, page_size), page_size, d)
    return LagunaCache(
        k=jnp.zeros(pool, config.dtype), v=jnp.zeros(pool, config.dtype),
        k_win=jnp.zeros(rings, config.dtype),
        v_win=jnp.zeros(rings, config.dtype))


def init_params(config: LagunaConfig, key) -> Dict[str, Any]:
    """Seeded weights: normal / sqrt(fan_in) matrices, norms of one, the
    router in float32. Traceable: call it under ``jit``."""
    h, dt, hd = config.hidden_size, config.dtype, config.head_dim
    nkv = config.num_key_value_heads
    f, fs = config.moe_intermediate_size, config.shared_expert_intermediate_size
    e, r = config.num_experts, config.n_router_outputs

    def normal(k, shape, fan_in, dtype=dt):
        return (jax.random.normal(k, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(dtype)

    def gated(ks, width, lead=()):
        return {"w_gate": normal(ks[0], lead + (h, width), h),
                "w_up": normal(ks[1], lead + (h, width), h),
                "w_down": normal(ks[2], lead + (width, h), width)}

    def layer(k, nq, mlp_type):
        ks = jax.random.split(k, 15)
        lp = {
            "attn_norm": jnp.ones((h,), dt),
            "wq": normal(ks[0], (h, nq * hd), h),
            "wk": normal(ks[1], (h, nkv * hd), h),
            "wv": normal(ks[2], (h, nkv * hd), h),
            "wg": normal(ks[3], (h, nq), h),
            "wo": normal(ks[4], (nq * hd, h), nq * hd),
            "mlp_norm": jnp.ones((h,), dt),
        }
        if mlp_type == "dense":
            lp["mlp"] = gated(ks[5:8], config.intermediate_size)
        else:
            lp["router"] = {"w": normal(ks[8], (h, r), h, jnp.float32)}
            lp["experts"] = gated(ks[9:12], f, (e,))
            lp["shared"] = gated(ks[12:15], fs)
        return lp

    return {
        "embed_tokens": normal(jax.random.fold_in(key, 1000),
                               (config.vocab_size, h), h),
        "layers": [layer(jax.random.fold_in(key, i), nq, mlp_type)
                   for i, (nq, mlp_type) in enumerate(zip(
                       config.num_attention_heads_per_layer,
                       config.mlp_layer_types))],
        "final_norm": jnp.ones((h,), dt),
        "lm_head": normal(jax.random.fold_in(key, 1001),
                          (h, config.vocab_size), h),
    }


# --------------------------------------------------------------------------- #
# Layer parts
# --------------------------------------------------------------------------- #
def _rope_tables(config: LagunaConfig, positions: int):
    """{layer type: (cos, sin)} over ``positions`` rows, each scheme as its
    ``rope_parameters`` block says."""
    tables = {}
    for kind, rp in config.rope_parameters.items():
        if kind not in (FULL, SLIDING):
            continue
        rotary = int(config.head_dim * rp.get("partial_rotary_factor", 1))
        yarn = rp if rp.get("rope_type", "default") == "yarn" else None
        tables[kind] = rope_frequencies(
            config.head_dim, positions, float(rp["rope_theta"]),
            rotary_dim=rotary, yarn=yarn)
    return tables


def _qkvg(config: LagunaConfig, lp, y, nq: int, rope, positions=None):
    """y: [B, T, h] normed -> rotated q [B, T, nq, D] and k, v [B, T, nkv, D],
    and the per-head output gate [B, T, nq] in float32."""
    b, t, _ = y.shape
    nkv, hd = config.num_key_value_heads, config.head_dim
    q = apply_rope((y @ lp["wq"]).reshape(b, t, nq, hd), *rope, positions)
    k = apply_rope((y @ lp["wk"]).reshape(b, t, nkv, hd), *rope, positions)
    v = (y @ lp["wv"]).reshape(b, t, nkv, hd)
    gate = jax.nn.sigmoid(jnp.matmul(y, lp["wg"],
                                     preferred_element_type=jnp.float32))
    return q, k, v, gate


def _attn_out(lp, o, gate):
    """o: [..., nq, D] attended, gate: [..., nq] -> [..., h]."""
    gated = (o.astype(jnp.float32) * gate[..., None]).astype(o.dtype)
    return gated.reshape(*gated.shape[:-2], -1) @ lp["wo"]


def _mlp(config: LagunaConfig, lp, y, counted=None):
    """y: [T, h] normed -> (the layer's MLP output [T, h], the expert
    counters or None: ops/moe.py's int32 [6] over the ``counted`` rows of a
    decode tick, the last two of them (``PREFILL_COUNTERS``) in prefill). A
    prefill's thousands of tokens go through
    the routed experts ``MOE_PREFILL_TOKENS`` at a time. The routed products
    are grouped ("ragged") in prefill and in decode alike: a decode tick's
    24 rows reach about half of the 32 held experts a layer, and a step took
    9.47 ms where every held expert over every row ("dense") took 10.70
    (PERF.md 6, PR 35)."""
    if "mlp" in lp:
        return swiglu_mlp(y, **lp["mlp"]), None

    def routed(rows, counted=None):
        return routed_experts(
            rows, lp["router"], lp["experts"], held=config.held_experts,
            top_k=config.num_experts_per_tok,
            scale=config.moe_routed_scaling_factor, impl="ragged",
            counted=counted, scoring="softmax", form="swiglu")

    shared = swiglu_mlp(y, **lp["shared"])
    if counted is not None:
        out, counts = routed(y, counted)
        return out + shared, counts

    def chunk(rows):
        # every row counted: only the blocks are kept, and they count calls
        out, counts = routed(rows, jnp.ones((rows.shape[0],), bool))
        return out, counts[-len(PREFILL_COUNTERS):]

    t = y.shape[0]
    if t > MOE_PREFILL_TOKENS and t % MOE_PREFILL_TOKENS == 0:
        out, counts = jax.lax.map(
            chunk, y.reshape(-1, MOE_PREFILL_TOKENS, y.shape[1]))
        return out.reshape(y.shape) + shared, jnp.sum(counts, axis=0)
    out, counts = chunk(y)
    return out + shared, counts


def _head(config: LagunaConfig, params, x):
    y = rms_norm(x, params["final_norm"], config.rms_norm_eps)
    return jnp.matmul(y, params["lm_head"], preferred_element_type=jnp.float32)


# --------------------------------------------------------------------------- #
# Prefill
# --------------------------------------------------------------------------- #
def paged_prefill(params, cache: LagunaCache, tokens, pages, lengths, slots,
                  config: LagunaConfig, page_size: int):
    """BATCHED prefill: tokens [PB, S_bucket] right-padded; pages
    [PB, S_bucket // page_size]; lengths [PB] true lengths; slots [PB] the
    slot each row was admitted to (a pad row: the trash ring). A full layer
    writes the prompt's pages; a sliding layer writes the prompt's last
    ``ring`` pages into the slot's ring, logical page ``p`` at ring page
    ``p % ring``, where decode finds it. Returns (last-token logits [PB, V],
    cache, int32 [2]: the ``PREFILL_COUNTERS`` of this call)."""
    from ray_tpu.ops.attention import attention

    pb, s = tokens.shape
    ring = ring_pages(config, page_size)
    n_pages = s // page_size
    x = params["embed_tokens"][tokens].astype(config.dtype)
    rope = _rope_tables(config, s)
    ck, cv, ckw, cvw = cache
    per_layer = ck.shape[1] // config.count(FULL)
    rings_per_layer = ckw.shape[1] // config.count(SLIDING)
    src, dst = ring_prompt_pages(lengths, slots, ring, n_pages,
                                 rings_per_layer, page_size)

    moe_counts = jnp.zeros((len(PREFILL_COUNTERS),), jnp.int32)
    f_idx = w_idx = 0
    for kind, nq, lp in zip(config.layer_types,
                            config.num_attention_heads_per_layer,
                            params["layers"]):
        y = rms_norm(x, lp["attn_norm"], config.rms_norm_eps)
        q, k, v, gate = _qkvg(config, lp, y, nq, rope[kind])
        if kind == FULL:
            o = attention(q, k, v, causal=True, impl=config.attention_impl)
            layer_pages = pages + f_idx * per_layer
            ck = _scatter_prompt_rows_full(ck, k, layer_pages)
            cv = _scatter_prompt_rows_full(cv, v, layer_pages)
            f_idx += 1
        else:
            o = attention(q, k, v, causal=True, impl=config.attention_impl,
                          window=config.sliding_window)
            layer_rings = dst + w_idx * rings_per_layer
            ckw = _scatter_prompt_rows_full(
                ckw, ring_rows(k, src, page_size), layer_rings)
            cvw = _scatter_prompt_rows_full(
                cvw, ring_rows(v, src, page_size), layer_rings)
            w_idx += 1
        x = x + _attn_out(lp, o, gate)
        y = rms_norm(x, lp["mlp_norm"], config.rms_norm_eps)
        out, layer_counts = _mlp(config, lp, y.reshape(pb * s, -1))
        if layer_counts is not None:
            moe_counts = moe_counts + layer_counts
        x = x + out.reshape(pb, s, -1)
    last = jnp.take_along_axis(x, (lengths - 1)[:, None, None], axis=1)[:, 0]
    return (_head(config, params, last), LagunaCache(ck, cv, ckw, cvw),
            moe_counts)


# --------------------------------------------------------------------------- #
# Decode
# --------------------------------------------------------------------------- #
def paged_decode_one(params, cache: LagunaCache, tokens, positions, active,
                     table, config: LagunaConfig, page_size: int,
                     use_kernel: bool, rope=None):
    """One decode tick over every slot. tokens / positions / active: [B];
    table: [B, max_pages]. Returns (logits [B, V], cache, int32 [8]: the
    ``DECODE_COUNTERS`` of this tick). An inactive slot's K/V writes land in
    the trash page and the trash ring, and it attends over nothing.
    ``rope``: ``_rope_tables`` over the table's rows, made once a chunk by
    ``paged_decode_steps`` (two tables of 25,600 rows cost a tick 0.18 ms)."""
    nb = tokens.shape[0]
    ring = ring_pages(config, page_size)
    scale = config.head_dim ** -0.5
    max_ctx = table.shape[1] * page_size
    x = params["embed_tokens"][tokens].astype(config.dtype)          # [B, h]
    safe_pos = jnp.minimum(positions, max_ctx - 1)
    page_idx = safe_pos // page_size
    pages = jnp.take_along_axis(table, page_idx[:, None], axis=1)[:, 0]
    rows = safe_pos % page_size
    lengths = _live_lengths(safe_pos, active)
    start, ring_table, win_lengths, win_starts, win_pages = ring_tick(
        lengths, active, page_idx, config.sliding_window, ring, page_size)
    rope = rope or _rope_tables(config, max_ctx)
    ck, cv, ckw, cvw = cache
    per_layer = ck.shape[1] // config.count(FULL)
    rings_per_layer = ckw.shape[1] // config.count(SLIDING)
    moe_counts = jnp.zeros((6,), jnp.int32)
    f_idx = w_idx = 0
    for kind, nq, lp in zip(config.layer_types,
                            config.num_attention_heads_per_layer,
                            params["layers"]):
        y = rms_norm(x, lp["attn_norm"], config.rms_norm_eps)
        q, k, v, gate = _qkvg(config, lp, y[:, None], nq, rope[kind],
                              safe_pos[:, None])
        if kind == FULL:
            base = f_idx * per_layer
            ck = _scatter_token_rows(ck, k[:, 0], pages + base, rows)
            cv = _scatter_token_rows(cv, v[:, 0], pages + base, rows)
            o = _paged_attention(q, ck, cv, table + base, lengths, scale,
                                 use_kernel)
            f_idx += 1
        else:
            base = w_idx * rings_per_layer
            ckw = _scatter_token_rows(ckw, k[:, 0], win_pages + base, rows)
            cvw = _scatter_token_rows(cvw, v[:, 0], win_pages + base, rows)
            o = _paged_attention(q, ckw, cvw, ring_table + base, win_lengths,
                                 scale, use_kernel, starts=win_starts)
            w_idx += 1
        x = x + _attn_out(lp, o[:, 0], gate[:, 0])
        y = rms_norm(x, lp["mlp_norm"], config.rms_norm_eps)
        out, layer_counts = _mlp(
            config, lp, y, counted=active if "mlp" not in lp else None)
        if layer_counts is not None:
            moe_counts = moe_counts + layer_counts
        x = x + out
    attended = jnp.stack([
        config.count(FULL) * jnp.sum(lengths),
        config.count(SLIDING) * jnp.sum(lengths - start)]).astype(jnp.int32)
    return (_head(config, params, x), LagunaCache(ck, cv, ckw, cvw),
            jnp.concatenate([moe_counts, attended]))


def paged_decode_steps(params, cache: LagunaCache, tokens, positions, active,
                       table, key, config: LagunaConfig, num_steps: int,
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


def paged_kernel_fits(config: LagunaConfig) -> bool:
    """The Pallas paged-attention kernel tiles head_dim onto 128 lanes."""
    return config.head_dim % 128 == 0


def make_paged_decode_fn(config: LagunaConfig, num_steps: int, page_size: int,
                         temperature: float = 0.0, *, use_kernel: bool):
    fn = functools.partial(paged_decode_steps, config=config,
                           num_steps=num_steps, page_size=page_size,
                           use_kernel=use_kernel, temperature=temperature)
    fn.__name__ = "laguna_decode_steps"  # jit_laguna_decode_steps in a profile
    return jax.jit(fn, donate_argnums=(1,))


def make_paged_prefill_fn(config: LagunaConfig, page_size: int):
    fn = functools.partial(paged_prefill, config=config, page_size=page_size)
    fn.__name__ = "laguna_prefill"  # jit_laguna_prefill in a profile
    return jax.jit(fn, donate_argnums=(1,))
