"""KV-cached inference path for the Llama family: prefill + batched decode.

TPU-first design (the reference orchestrates external engines — vLLM/torch —
for serving; here decode is a first-class compiled path):

- the KV cache is SLOTTED: a fixed [L, B_slots, S_max, H_kv, D] HBM tensor;
  a request owns one slot for its lifetime. Contiguous per-slot layout means
  no paging tables are needed (paged attention solves CUDA allocator
  fragmentation; a static XLA buffer has none).
- prefill is one compiled program per PROMPT BUCKET (prompt padded up to the
  bucket length) that runs the normal causal forward and writes the slot's
  K/V rows; decode is ONE compiled program for the whole batch that appends
  one token per active slot and attends over the cache with a per-slot
  length mask.
- multi-token decode: ``decode_steps`` lax.scans T greedy/temperature steps
  entirely on device, feeding each sampled token into the next step — one
  host round trip per T tokens, so per-program dispatch and the host sync
  amortize over the chunk.
- cache buffers are DONATED through jit so XLA updates them in place.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models.llama import LlamaConfig
from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.rope import apply_rope, rope_frequencies


class KVCache(NamedTuple):
    k: jax.Array  # [L, B, S_max, H_kv, D]
    v: jax.Array  # [L, B, S_max, H_kv, D]


def init_kv_cache(config: LlamaConfig, num_slots: int, max_seq: int,
                  dtype=jnp.bfloat16) -> KVCache:
    shape = (config.num_layers, num_slots, max_seq, config.num_kv_heads,
             config.head_dim_)
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))


def _project_qkv(config: LlamaConfig, lp: Dict[str, Any], x):
    """x: [B, T, H] -> q [B,T,nh,hd], k/v [B,T,nkv,hd] (pre-rope)."""
    b, t, _ = x.shape
    nh, nkv, hd = config.num_heads, config.num_kv_heads, config.head_dim_
    y = rms_norm(x, lp["attn_norm"], config.rms_eps)
    q = (y @ lp["wq"]).reshape(b, t, nh, hd)
    k = (y @ lp["wk"]).reshape(b, t, nkv, hd)
    v = (y @ lp["wv"]).reshape(b, t, nkv, hd)
    return y, q, k, v


def _mlp(config: LlamaConfig, lp: Dict[str, Any], x):
    y = rms_norm(x, lp["mlp_norm"], config.rms_eps)
    gate = jax.nn.silu(y @ lp["w_gate"])
    up = y @ lp["w_up"]
    return (gate * up) @ lp["w_down"]


def _decode_attention(q, k_cache, v_cache, positions, scale):
    """q: [B, 1, nh, hd]; caches: [B, S, nkv, hd]; positions: [B] (index of
    the CURRENT token, already written into the cache). Attends over
    cache[: pos] inclusive with a length mask.

    GQA via a GROUPED einsum (q reshaped [B, nkv, rep, hd]) — never
    jnp.repeat the cache: decode is HBM-bandwidth-bound and a repeat
    multiplies cache traffic by the group size. Dots run in the cache dtype
    (bf16) with f32 accumulation."""
    b, _, nh, hd = q.shape
    s = k_cache.shape[1]
    nkv = k_cache.shape[2]
    rep = nh // nkv
    qg = q.reshape(b, nkv, rep, hd)
    logits = jnp.einsum(
        "bnrd,bsnd->bnrs", qg, k_cache,
        preferred_element_type=jnp.float32,
    ) * scale  # [B, nkv, rep, S] f32
    mask = jnp.arange(s)[None, :] <= positions[:, None]  # [B, S]
    logits = jnp.where(mask[:, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum(
        "bnrs,bsnd->bnrd", probs.astype(v_cache.dtype), v_cache,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, 1, nh, hd).astype(q.dtype)


def _write_cache_rows(cache_layer, rows, positions):
    """cache_layer: [B, S, nkv, hd]; rows: [B, 1, nkv, hd]; positions: [B].
    Writes rows at per-slot positions (vmapped dynamic_update_slice)."""
    def write_one(c, r, p):
        return jax.lax.dynamic_update_slice(c, r.astype(c.dtype), (p, 0, 0))

    return jax.vmap(write_one)(cache_layer, rows, positions)


def _write_cache_rows_full(cache_full, rows, positions, layer):
    """cache_full: [L, B, S, nkv, hd]; rows: [B, 1, nkv, hd]; positions: [B];
    layer: scalar. Writes ONLY the new token rows (per-slot position) into
    the full cache — tiny in-place writes instead of copying layer slices."""
    def write_one(c, r, p):  # c: [L, S, nkv, hd] (one slot, all layers)
        return jax.lax.dynamic_update_slice(
            c, r[None].astype(c.dtype), (layer, p, 0, 0)
        )

    return jax.vmap(write_one, in_axes=(1, 0, 0), out_axes=1)(
        cache_full, rows, positions
    )


def _embed(params, tokens, dtype):
    return params["embed_tokens"][tokens].astype(dtype)


def _lm_head(params, x, config: LlamaConfig):
    x = rms_norm(x, params["final_norm"], config.rms_eps)
    head = params.get("lm_head")
    if head is None:
        head = params["embed_tokens"].T.astype(config.dtype)
    return (x @ head).astype(jnp.float32)


# --------------------------------------------------------------------------- #
# Prefill
# --------------------------------------------------------------------------- #
def prefill(params, cache: KVCache, tokens, slot, length,
            config: LlamaConfig) -> Tuple[jax.Array, KVCache]:
    """tokens: [1, S_bucket] (padded); slot: scalar int; length: scalar int
    (true prompt length). Runs the causal forward, writes K/V rows
    [0, S_bucket) of the slot, returns logits at position length-1 ([V]).

    The FULL cache rides the layer scan as CARRY (not xs/ys): scanning the
    cache as ys would stack a fresh copy of the whole multi-GB buffer per
    layer; as donated carry, XLA keeps the dynamic_update_slices in place
    (the maxtext decode pattern)."""
    from ray_tpu.ops.attention import attention

    _, s = tokens.shape
    cos, sin = rope_frequencies(config.head_dim_, s, config.rope_theta)
    x = _embed(params, tokens, config.dtype)

    def body(carry, lp):
        x, ck_full, cv_full, layer = carry
        _, q, k, v = _project_qkv(config, lp, x)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        o = attention(q, k, v, causal=True, impl=config.attention_impl)
        b, t, nh, hd = q.shape
        x = x + o.reshape(b, t, nh * hd) @ lp["wo"]
        x = x + _mlp(config, lp, x)
        ck_full = jax.lax.dynamic_update_slice(
            ck_full, k[None].astype(ck_full.dtype), (layer, slot, 0, 0, 0)
        )
        cv_full = jax.lax.dynamic_update_slice(
            cv_full, v[None].astype(cv_full.dtype), (layer, slot, 0, 0, 0)
        )
        return (x, ck_full, cv_full, layer + 1), None

    (x, new_k, new_v, _), _ = jax.lax.scan(
        body, (x, cache.k, cache.v, jnp.int32(0)), params["layers"]
    )
    logits = _lm_head(params, x, config)  # [1, S, V]
    last = logits[0, length - 1]
    return last, KVCache(k=new_k, v=new_v)


# --------------------------------------------------------------------------- #
# Decode
# --------------------------------------------------------------------------- #
def decode_one(params, cache: KVCache, tokens, positions,
               config: LlamaConfig) -> Tuple[jax.Array, KVCache]:
    """One decode tick for every slot. tokens: [B] (current input token per
    slot); positions: [B] (cache index to write this token's K/V). Returns
    (logits [B, V], new cache)."""
    scale = config.head_dim_ ** -0.5
    cos, sin = rope_frequencies(config.head_dim_, int(cache.k.shape[2]),
                                config.rope_theta)
    x = _embed(params, tokens[:, None], config.dtype)  # [B, 1, H]

    def body(carry, lp):
        x, ck_full, cv_full, layer = carry
        _, q, k, v = _project_qkv(config, lp, x)
        q = apply_rope(q, cos, sin, positions=positions[:, None])
        k = apply_rope(k, cos, sin, positions=positions[:, None])
        ck_full = _write_cache_rows_full(ck_full, k, positions, layer)
        cv_full = _write_cache_rows_full(cv_full, v, positions, layer)
        ck = jax.lax.dynamic_index_in_dim(ck_full, layer, 0, keepdims=False)
        cv = jax.lax.dynamic_index_in_dim(cv_full, layer, 0, keepdims=False)
        o = _decode_attention(q, ck, cv, positions, scale)
        b, t, nh, hd = q.shape
        x = x + o.reshape(b, t, nh * hd) @ lp["wo"]
        x = x + _mlp(config, lp, x)
        return (x, ck_full, cv_full, layer + 1), None

    (x, new_k, new_v, _), _ = jax.lax.scan(
        body, (x, cache.k, cache.v, jnp.int32(0)), params["layers"]
    )
    logits = _lm_head(params, x, config)[:, 0]  # [B, V]
    return logits, KVCache(k=new_k, v=new_v)


def sample_token(logits, key, temperature: float):
    """logits: [B, V]. temperature <= 0 -> greedy."""
    if temperature <= 0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(key, logits / temperature, axis=-1).astype(jnp.int32)


def decode_steps(params, cache: KVCache, tokens, positions, active, key,
                 config: LlamaConfig, num_steps: int,
                 temperature: float = 0.0) -> Tuple[jax.Array, jax.Array, jax.Array, KVCache]:
    """T decode ticks fully on device. tokens/positions/active: [B]; returns
    (sampled [B, T], last_tokens [B], new_positions [B], cache). Inactive
    slots still flow through the math but their cache writes land on their
    own frozen position repeatedly (position not advanced), keeping them
    harmless."""

    def tick(carry, k_):
        toks, pos, cache = carry
        logits, cache = decode_one(params, cache, toks, pos, config)
        nxt = sample_token(logits, k_, temperature)
        nxt = jnp.where(active, nxt, toks)
        new_pos = jnp.where(active, pos + 1, pos)
        return (nxt, new_pos, cache), nxt

    keys = jax.random.split(key, num_steps)
    (last, pos, cache), sampled = jax.lax.scan(
        tick, (tokens, positions, cache), keys
    )
    return sampled.T, last, pos, cache  # sampled: [B, T]


def make_decode_fn(config: LlamaConfig, num_steps: int, temperature: float = 0.0):
    """Jitted multi-step decode with cache donation (in-place HBM updates)."""
    fn = functools.partial(decode_steps, config=config, num_steps=num_steps,
                           temperature=temperature)
    return jax.jit(fn, donate_argnums=(1,))


def make_prefill_fn(config: LlamaConfig):
    """Jitted prefill (one compile per prompt-bucket length) with cache
    donation."""
    fn = functools.partial(prefill, config=config)
    return jax.jit(fn, donate_argnums=(1,))
