"""Kimi-K2 class decoder for the serving engine (``model_type`` kimi_k2,
moonshotai/Kimi-K2.6; the DeepSeek-V3 block): multi-head LATENT attention
over a cache of one compressed row a token a layer, a leading dense layer,
then identical expert layers behind a sigmoid router with a correction bias.

Every layer is ``h += Attn(rms(h)); h += FFN(rms(h))``.

- ``Attn(y)``: ``c_q = rms(y W_qa)``; ``[q_n | q_r] = c_q W_qb`` a head
  (``qk_nope_head_dim`` + ``qk_rope_head_dim``); ``[c | k_r] = y W_kva``
  (``kv_lora_rank`` + ``qk_rope_head_dim``); ``c = rms(c)``; ``q_r`` and
  ``k_r`` rotated, ``k_r`` ONE head for all the query heads; ``[k_n | v] =
  c W_kvb`` a head; head ``i``: ``softmax(s (q_n,i . k_n,i + q_r,i . k_r))
  v_i``; ``W_o``. YaRN's factor is on the SCORES: ``s = (d_n + d_r)^-0.5
  m^2`` with ``m = 0.1 mscale_all_dim ln(factor) + 1``, and cos and sin carry
  ``mscale / mscale_all_dim`` (1 as published: unscaled tables).
- ``FFN``: a SwiGLU of ``intermediate_size`` in the first
  ``first_k_dense_replace`` layers; after them ``routed_scaling_factor`` x
  the top ``num_experts_per_tok`` of ``sigmoid(y W_r) + b`` over
  ``n_router_outputs``, weighted by the scores WITHOUT ``b`` over their sum,
  each expert a SwiGLU of ``moe_intermediate_size``, plus one shared expert
  (``ops/moe.py``: ``scoring="sigmoid_bias"``, ``form="swiglu"``).

WHAT IS CACHED is ``[rms(c) | rope(k_r)]``, ``kv_lora_rank +
qk_rope_head_dim`` values a token a layer (576 as published, where the
expanded K and V of 64 heads are 20,480), in ONE pool
``[1, L * total_pages, page_size, latent_width]`` (``KimiK2Cache.k``; there is
no V pool: the values are the first ``kv_lora_rank`` columns of the key row).
``latent_width`` is that count in whole 128-lane tiles (640): the device
stores a 576-wide array in rows of 640 whatever the program says, and its
kernels cannot slice a row that is not whole tiles, so the program says 640,
writes zeros into the last 64 and counts them (1,280 B a token a layer).

TWO attention paths over that one cache, which compute the same function:

- prefill, UNABSORBED: the chunk's own latents expanded by ``W_kvb`` to 64
  heads of q/k ``d_n + d_r`` and v ``v_head_dim``, through the flash forward
  (``ops/attention.py`` at a v width of its own, ``flash_mla_fwd``), a group of
  ``PREFILL_HEADS`` heads at a time; absorbed it would cost (576 + 512) /
  (192 + 128) = 3.4 times the attention's operations.
- decode, ABSORBED: ``q'_i = q_n,i W_uk,i`` carries the query into the latent
  space, the kernel (``ops/paged_attention.py`` ``paged_attention_latent``:
  one KV head, a query group of 64) reads each cached row ONCE for scores and
  values, and ``a_i = o_i W_uv,i`` brings the result back. ``W_uk`` and
  ``W_uv`` are views of ``W_kvb``: no weight is stored twice, no K or V is
  ever expanded in the decode program.

The expert layers are ONE scanned body over stacked parameters
(``params["layers"]``), the dense layers unrolled in front of it
(``params["dense_layers"]``). A prefill walks each prompt's rows in pieces
(``_walk``: the dense MLP's ``[g | u]`` alone is 1.8 GB at 24,576 rows if
formed whole) and SKIPS the pieces past the prompt's length, as the flash
forward skips its q blocks past it (``lengths``): one program of the longest
bucket costs a prompt of 8,363 tokens what 10,240 rows cost, and the engine
compiles one prefill program, not one a bucket.

``n_routed_experts`` is the experts HELD here, ``held_experts`` which of the
router's ``n_router_outputs`` they are; ``vocab_size`` the rows held.

Training of this family is not written.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Mapping, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import cache_rows
from ray_tpu.models.paged_decode import (
    _live_lengths, _scatter_prompt_rows_full, _scatter_token_rows, _walk,
    counted_decode_steps)
from ray_tpu.ops.moe import routed_experts, swiglu_mlp
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.rope import apply_rope, rope_frequencies

ROPE_K26 = {"type": "yarn", "factor": 64, "beta_fast": 32, "beta_slow": 1,
            "mscale": 1, "mscale_all_dim": 1,
            "original_max_position_embeddings": 4096}


@dataclasses.dataclass(frozen=True, eq=False)
class KimiK2Config:
    """The source's key names (``config.json`` of ``model_type`` kimi_k2);
    the defaults are Kimi-K2.6's language model whole."""
    vocab_size: int = 163840
    hidden_size: int = 7168
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 1
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 384
    n_router_outputs: int = 384
    held_experts: Tuple[int, int] = (0, 384)
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.827
    rms_norm_eps: float = 1e-5
    rope_theta: float = 50000.0
    rope_scaling: Mapping[str, Any] = dataclasses.field(
        default_factory=lambda: ROPE_K26)
    max_seq_len: int = 4096
    dtype: Any = jnp.bfloat16
    attention_impl: str = "auto"

    def __post_init__(self):
        lo, hi = self.held_experts
        if not (0 <= lo < hi <= self.n_router_outputs
                and hi - lo == self.n_routed_experts):
            raise ValueError(
                f"held_experts {self.held_experts} must be n_routed_experts "
                f"({self.n_routed_experts}) of the router's "
                f"{self.n_router_outputs} outputs")
        if not 0 < self.first_k_dense_replace < self.num_hidden_layers:
            raise ValueError("dense layers first, then at least one expert layer")

    @property
    def latent_width(self) -> int:
        """A cached row: ``kv_lora_rank + qk_rope_head_dim`` values in whole
        lane tiles."""
        return cache_rows.latent_width(self.kv_lora_rank,
                                       self.qk_rope_head_dim)

    @property
    def softmax_scale(self) -> float:
        """``(d_n + d_r)^-0.5 m^2``: this family's YaRN scales the scores."""
        rs = self.rope_scaling
        m = 1.0
        if rs and rs["factor"] > 1:
            m = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 * m * m

    @classmethod
    def tiny(cls, **kw) -> "KimiK2Config":
        """CPU tests: 4 heads of 16 + 8 / 16 over a latent of 32 + 8, YaRN at
        a factor of 4 (m = 1.139), one dense layer and three expert layers, 4
        of 8 experts held, top-2."""
        kw.setdefault("max_seq_len", 512)
        return cls(**{**dict(
            vocab_size=256, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=32, num_hidden_layers=4,
            num_attention_heads=4, q_lora_rank=24, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            n_routed_experts=4, n_router_outputs=8, held_experts=(0, 4),
            num_experts_per_tok=2,
            rope_scaling={**ROPE_K26, "factor": 4, "beta_fast": 4,
                          "original_max_position_embeddings": 64}), **kw})


SLOT_STATE = False  # pages only: nothing is kept a slot
# what the decode program counts on the device, its fifth result: ops/moe.py's
# four over the expert layers and ticks, then the cached latent rows attended
# over live slots, ticks and ALL layers
DECODE_COUNTERS = ("moe_assignments", "moe_assignments_held",
                   "moe_experts_touched", "moe_expert_load_max",
                   "attn_rows_latent")
# what the prefill program counts, its third result: the compacted expert
# product's calls and extra blocks; the prompt tokens of the call's real rows;
# and the causal (query, key) pairs ONE layer attends over for them, sum of
# n (n + 1) / 2 (every layer attends the same pairs; a layer's count keeps a
# 4-row call of 24,576 inside int32)
PREFILL_COUNTERS = ("moe_blocks", "moe_blocks_extra", "prefill_rows",
                    "prefill_attn_pairs")
# rows of ONE prompt a prefill takes at a time (``_walk``), in every stage
# that works a row at a time (the latents, a head group's expansion, W_o with
# the layer's FFN): pieces wholly past the prompt's length are skipped, so
# ONE program of the longest bucket costs what each prompt needs, to within
# a piece. A piece reads the held experts' weights once (1.06 GB a layer at
# 12 experts of 3 x 7168 x 2048: 1.3 ms), so it is not smaller; the dense
# MLP's float32 [rows, 18432] pair is 302 MB at this many
PREFILL_ROWS = 2048
# heads of a prefill's unabsorbed attention expanded and attended at a time: q
# and k of 64 heads over 24,576 rows are 604 MB each, before the kernel's
# layout copies
PREFILL_HEADS = 16
# as models/nemotron_h.py argues: with fan-in-scale routed outputs a near-tie
# of the router moves a token's residual by a whole expert's worth
ROUTED_OUT_SCALE = 0.25


class KimiK2Cache(NamedTuple):
    k: jax.Array  # [1, L * total_pages, page_size, latent_width]: [c | k_r | 0]


def init_cache(config: KimiK2Config, num_slots: int, total_pages: int,
               page_size: int) -> KimiK2Cache:
    return KimiK2Cache(k=jnp.zeros(
        (1, config.num_hidden_layers * total_pages, page_size,
         config.latent_width), config.dtype))


def init_params(config: KimiK2Config, key) -> Dict[str, Any]:
    """Seeded weights: normal / sqrt(fan_in) matrices, norms of one, the
    router and its correction bias in float32 (the bias small and nonzero, so
    that choice and weights differ), the routed experts' ``w_down`` at
    ``ROUTED_OUT_SCALE``. The expert layers are made one at a time and stacked
    (a layer's float32 draws are 0.7 GB at 12 experts). Traceable."""
    h, dt = config.hidden_size, config.dtype
    nh, rq, rkv = (config.num_attention_heads, config.q_lora_rank,
                   config.kv_lora_rank)
    dn, dr, dv = (config.qk_nope_head_dim, config.qk_rope_head_dim,
                  config.v_head_dim)
    f, e, r = (config.moe_intermediate_size, config.n_routed_experts,
               config.n_router_outputs)

    def normal(k, shape, fan_in, dtype=dt):
        return (jax.random.normal(k, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(dtype)

    def gated(ks, width, lead=(), out_scale=1.0):
        return {"w_gate": normal(ks[0], lead + (h, width), h),
                "w_up": normal(ks[1], lead + (h, width), h),
                "w_down": normal(ks[2], lead + (width, h),
                                 width / out_scale ** 2)}

    def layer(k, dense: bool):
        ks = jax.random.split(k, 16)
        lp = {
            "attn_norm": jnp.ones((h,), dt),
            "wq_a": normal(ks[0], (h, rq), h),
            "q_norm": jnp.ones((rq,), dt),
            "wq_b": normal(ks[1], (rq, nh * (dn + dr)), rq),
            "wkv_a": normal(ks[2], (h, rkv + dr), h),
            "kv_norm": jnp.ones((rkv,), dt),
            "wkv_b": normal(ks[3], (rkv, nh * (dn + dv)), rkv),
            "wo": normal(ks[4], (nh * dv, h), nh * dv),
            "mlp_norm": jnp.ones((h,), dt),
        }
        if dense:
            lp["mlp"] = gated(ks[5:8], config.intermediate_size)
        else:
            lp["router"] = {
                "w": normal(ks[8], (h, r), h, jnp.float32),
                "bias": 0.05 * jax.random.normal(ks[9], (r,), jnp.float32)}
            lp["experts"] = gated(ks[10:13], f, (e,), ROUTED_OUT_SCALE)
            lp["shared"] = gated(ks[13:16], f)
        return lp

    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(
        jnp.arange(config.num_hidden_layers))
    n_dense = config.first_k_dense_replace
    return {
        "embed_tokens": normal(jax.random.fold_in(key, 1000),
                               (config.vocab_size, h), h),
        "dense_layers": [layer(keys[i], True) for i in range(n_dense)],
        "layers": jax.lax.map(lambda k: layer(k, False), keys[n_dense:]),
        "final_norm": jnp.ones((h,), dt),
        "lm_head": normal(jax.random.fold_in(key, 1001),
                          (h, config.vocab_size), h),
    }


# --------------------------------------------------------------------------- #
# Layer parts
# --------------------------------------------------------------------------- #
def _rope_tables(config: KimiK2Config, positions: int):
    """cos, sin [positions, d_r / 2]: YaRN's frequencies, and on the tables
    only ``mscale / mscale_all_dim`` (the factor itself is in
    ``softmax_scale``)."""
    rs = config.rope_scaling
    yarn = None
    if rs:
        yarn = {**rs, "attention_factor": rs["mscale"] / rs["mscale_all_dim"]}
    return rope_frequencies(config.qk_rope_head_dim, positions,
                            float(config.rope_theta), yarn=yarn)


def _latents(config: KimiK2Config, lp, y, rope, positions=None):
    """y: [B, T, h] normed -> (c_q [B, T, r_q] normed, the rows to cache
    [B, T, 1, latent_width] = [rms(c) | rope(k_r) | 0]). The rotated key is
    ONE head."""
    c_q = rms_norm(y @ lp["wq_a"], lp["q_norm"], config.rms_norm_eps)
    return c_q, cache_rows.latent_rows(
        y @ lp["wkv_a"], lp["kv_norm"], config.rms_norm_eps,
        config.kv_lora_rank, config.latent_width, rope, positions)


def _experts(config: KimiK2Config, lp, rows, impl: str, counted):
    """rows: [T, h] normed -> (shared + routed experts [T, h], ops/moe.py's
    counters over the ``counted`` rows)."""
    out, counts = routed_experts(
        rows, lp["router"], lp["experts"], held=config.held_experts,
        top_k=config.num_experts_per_tok, scale=config.routed_scaling_factor,
        impl=impl, counted=counted, scoring="sigmoid_bias", form="swiglu")
    return out + swiglu_mlp(rows, **lp["shared"]), counts


def _head(config: KimiK2Config, params, x):
    with jax.named_scope("kimi_head"):
        y = rms_norm(x, params["final_norm"], config.rms_norm_eps)
        return jnp.matmul(y, params["lm_head"],
                          preferred_element_type=jnp.float32)


# --------------------------------------------------------------------------- #
# Prefill: unabsorbed
# --------------------------------------------------------------------------- #
def _no_counts():
    return jnp.zeros((2,), jnp.int32)


def _prefill_layer(config: KimiK2Config, lp, x, pool, layer_pages, rope,
                   positions, lengths):
    """One layer of a prefill over x [PB, S, h] -> (x, pool, the expert
    counters int32 [2]), in three walks over the prompts' rows: the latents;
    a group of ``PREFILL_HEADS`` heads at a time, their q, k and v expanded
    from the chunk's own latents (unabsorbed) and attended; W_o with the
    layer's FFN."""
    from ray_tpu.ops.attention import attention

    pb, s, _ = x.shape
    nh, rkv = config.num_attention_heads, config.kv_lora_rank
    dn, dr, dv = (config.qk_nope_head_dim, config.qk_rope_head_dim,
                  config.v_head_dim)
    g = math.gcd(nh, PREFILL_HEADS)

    def latents(x, pos):
        y = rms_norm(x, lp["attn_norm"], config.rms_norm_eps)
        c_q, rows = _latents(config, lp, y[None], rope, pos[None])
        return (c_q[0], rows[0]), _no_counts()

    def heads(w):
        wq_b, wkv_b = w                 # [r_q, g (dn + dr)], [r_kv, g (dn + dv)]

        def expand(c_q, rows, pos):
            q = (c_q @ wq_b).reshape(-1, g, dn + dr)
            q_r = apply_rope(q[None, ..., dn:], *rope, pos[None])[0]
            kv = (rows[:, 0, :rkv] @ wkv_b).reshape(-1, g, dn + dv)
            k_r = jnp.broadcast_to(rows[..., rkv:rkv + dr], q_r.shape)
            return (jnp.concatenate([q[..., :dn], q_r], axis=-1),
                    jnp.concatenate([kv[..., :dn], k_r], axis=-1),
                    kv[..., dn:]), _no_counts()

        (q, k, v), _ = _walk(expand, (c_q, rows, positions), lengths,
                             PREFILL_ROWS)
        return attention(q, k, v, causal=True, scale=config.softmax_scale,
                         impl=config.attention_impl, lengths=lengths)

    def by_group(w, width):
        return w.reshape(w.shape[0], nh // g, g * width).transpose(1, 0, 2)

    def finish(x, o):
        x = x + o @ lp["wo"]
        y = rms_norm(x, lp["mlp_norm"], config.rms_norm_eps)
        if "mlp" in lp:
            with jax.named_scope("kimi_dense_mlp"):
                return x + swiglu_mlp(y, **lp["mlp"]), _no_counts()
        with jax.named_scope("kimi_experts"):
            # every row counted: only the compacted product's blocks are kept
            out, counts = _experts(config, lp, y, "ragged",
                                   jnp.ones((y.shape[0],), bool))
        return x + out, counts[-2:]

    with jax.named_scope("kimi_attention"):
        (c_q, rows), _ = _walk(latents, (x, positions), lengths, PREFILL_ROWS)
        pool = _scatter_prompt_rows_full(pool, rows, layer_pages)
        o = jax.lax.map(heads, (by_group(lp["wq_b"], dn + dr),
                                by_group(lp["wkv_b"], dn + dv)))
        o = o.transpose(1, 2, 0, 3, 4).reshape(pb, s, nh * dv)
    x, counts = _walk(finish, (x, o), lengths, PREFILL_ROWS)
    return x, pool, counts


def paged_prefill(params, cache: KimiK2Cache, tokens, pages, lengths,
                  config: KimiK2Config, page_size: int):
    """BATCHED prefill: tokens [PB, S_bucket] right-padded; pages
    [PB, S_bucket // page_size]; lengths [PB] (a pad row: 1 and the trash
    page). Every layer writes the prompt's latent rows at ``l * P + pages``,
    the rows decode reads (zeros past the prompt's last piece). What a call
    costs follows ``lengths``, not the bucket: row pieces and attention
    blocks past a prompt's length are skipped, so one bucket of the longest
    prompt serves every length. Returns (last-token logits [PB, V], cache, int32
    [4]: the ``PREFILL_COUNTERS`` of this call; pad rows count a token and a
    pair each)."""
    pb, s = tokens.shape
    x = params["embed_tokens"][tokens].astype(config.dtype)
    rope = _rope_tables(config, s)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (pb, s))
    pool = cache.k
    per_layer = pool.shape[1] // config.num_hidden_layers
    counts = _no_counts()
    for i, lp in enumerate(params["dense_layers"]):
        x, pool, _ = _prefill_layer(config, lp, x, pool, pages + i * per_layer,
                                    rope, positions, lengths)

    def body(carry, lp):
        x, pool, layer, counts = carry
        x, pool, layer_counts = _prefill_layer(
            config, lp, x, pool, pages + layer * per_layer, rope, positions,
            lengths)
        return (x, pool, layer + 1, counts + layer_counts), None

    (x, pool, _, counts), _ = jax.lax.scan(
        body, (x, pool, jnp.int32(config.first_k_dense_replace), counts),
        params["layers"])
    last = jnp.take_along_axis(x, (lengths - 1)[:, None, None], axis=1)[:, 0]
    attended = jnp.stack([jnp.sum(lengths),
                          jnp.sum(lengths * (lengths + 1) // 2)])
    return (_head(config, params, last), KimiK2Cache(pool),
            jnp.concatenate([counts, attended.astype(jnp.int32)]))


# --------------------------------------------------------------------------- #
# Decode: absorbed
# --------------------------------------------------------------------------- #
def _decode_layer(config: KimiK2Config, lp, x, pool, base, tick,
                  use_kernel: bool):
    """One layer of a decode tick over every slot. x: [B, h] -> (x, pool, the
    expert counters int32 [4])."""
    rope, safe_pos, pages, rows, lengths, table, active = tick
    nb = x.shape[0]
    nh, rkv = config.num_attention_heads, config.kv_lora_rank
    dn, dr, dv = (config.qk_nope_head_dim, config.qk_rope_head_dim,
                  config.v_head_dim)
    with jax.named_scope("kimi_attention"):
        y = rms_norm(x, lp["attn_norm"], config.rms_norm_eps)
        c_q, row = _latents(config, lp, y[:, None], rope, safe_pos[:, None])
        pool = _scatter_token_rows(pool, row[:, 0], pages + base, rows)
        q = (c_q[:, 0] @ lp["wq_b"]).reshape(nb, nh, dn + dr)
        a = cache_rows.absorbed_attention(
            q, lp["wkv_b"], pool, table, base, lengths, rope, safe_pos,
            rank=rkv, nope=dn, scale=config.softmax_scale,
            use_kernel=use_kernel)
        x = x + a.reshape(nb, nh * dv) @ lp["wo"]
    y = rms_norm(x, lp["mlp_norm"], config.rms_norm_eps)
    if "mlp" in lp:
        with jax.named_scope("kimi_dense_mlp"):
            return (x + swiglu_mlp(y, **lp["mlp"]), pool,
                    jnp.zeros((4,), jnp.int32))
    with jax.named_scope("kimi_experts"):
        # every held expert over every row: a tick's rows reach few of them,
        # but a loaded deployment's would reach all, and reads all the same
        out, counts = _experts(config, lp, y, "dense", active)
    return x + out, pool, counts


def paged_decode_one(params, cache: KimiK2Cache, tokens, positions, active,
                     table, config: KimiK2Config, page_size: int,
                     use_kernel: bool, rope=None):
    """One decode tick over every slot. tokens / positions / active: [B];
    table: [B, max_pages]. Returns (logits [B, V], cache, int32 [5]: the
    ``DECODE_COUNTERS`` of this tick). An inactive slot's row lands in the
    trash page and it attends over nothing."""
    max_ctx = table.shape[1] * page_size
    x = params["embed_tokens"][tokens].astype(config.dtype)          # [B, h]
    safe_pos = jnp.minimum(positions, max_ctx - 1)
    pages = jnp.take_along_axis(table, (safe_pos // page_size)[:, None],
                                axis=1)[:, 0]
    lengths = _live_lengths(safe_pos, active)
    tick = (rope or _rope_tables(config, max_ctx), safe_pos, pages,
            safe_pos % page_size, lengths, table, active)
    pool = cache.k
    per_layer = pool.shape[1] // config.num_hidden_layers
    for i, lp in enumerate(params["dense_layers"]):
        x, pool, _ = _decode_layer(config, lp, x, pool, i * per_layer, tick,
                                   use_kernel)

    def body(carry, lp):
        x, pool, layer, counts = carry
        x, pool, layer_counts = _decode_layer(
            config, lp, x, pool, layer * per_layer, tick, use_kernel)
        return (x, pool, layer + 1, counts + layer_counts), None

    (x, pool, _, counts), _ = jax.lax.scan(
        body, (x, pool, jnp.int32(config.first_k_dense_replace),
               jnp.zeros((4,), jnp.int32)), params["layers"])
    attended = (config.num_hidden_layers * jnp.sum(lengths)).astype(jnp.int32)
    return (_head(config, params, x), KimiK2Cache(pool),
            jnp.concatenate([counts, attended[None]]))


def paged_decode_steps(params, cache: KimiK2Cache, tokens, positions, active,
                       table, key, config: KimiK2Config, num_steps: int,
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


def paged_kernel_fits(config: KimiK2Config) -> bool:
    """``paged_attention_latent`` slices the values off the fetched rows at
    a lane tile."""
    return config.kv_lora_rank % cache_rows.LANES == 0


def make_paged_decode_fn(config: KimiK2Config, num_steps: int, page_size: int,
                         temperature: float = 0.0, *, use_kernel: bool):
    fn = functools.partial(paged_decode_steps, config=config,
                           num_steps=num_steps, page_size=page_size,
                           use_kernel=use_kernel, temperature=temperature)
    fn.__name__ = "kimi_k2_decode"  # jit_kimi_k2_decode in a profile
    return jax.jit(fn, donate_argnums=(1,))


def make_paged_prefill_fn(config: KimiK2Config, page_size: int):
    fn = functools.partial(paged_prefill, config=config, page_size=page_size)
    fn.__name__ = "kimi_k2_prefill"  # jit_kimi_k2_prefill in a profile
    return jax.jit(fn, donate_argnums=(1,))
