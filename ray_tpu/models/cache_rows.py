"""What more than one family does with the two kinds of cached rows that are
not K/V pages, written once.

LATENT ROWS (``models/kimi_k2.py``, ``models/ling_hybrid.py``): multi-head
latent attention caches ONE row a token a layer, ``[rms(c) | rope(k_r) | 0]``
(``kv_lora_rank`` normalised values, ``qk_rope_head_dim`` rotated ones that
every head shares, zeros up to whole 128-lane tiles), in a pool ``[1, L *
total_pages, page_size, width]``. ``latent_rows`` makes them; the pool is
written with ``models/paged_decode.py``'s scatters (one KV head);
``absorbed_attention`` is the decode path over them: ``W_kvb``'s key half
carries a head's query into the latent space, ``paged_attention_latent`` reads
each cached row once for scores and values, ``W_kvb``'s value half brings the
result back. No K or V is expanded and no weight is stored twice.

STATE BY SLOT (``models/nemotron_h.py``, ``models/ling_hybrid.py``): what a
recurrent layer keeps of a request whatever its length, ``[L, slots + 1,
...]``: a layer's rows of every slot and a TRASH ROW, row ``slots``, where a
padded prefill row writes as a padded row's pages are the trash page.
Prefill OVERWRITES the rows of the slots it admits (``put_prompt_state``), so
a retired slot needs no clearing; a decode tick reads and writes the rows of
the live slots ``[0, B)`` (``slot_state`` / ``put_slot_state``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.ops.norms import rms_norm
from ray_tpu.ops.paged_attention import paged_attention_latent
from ray_tpu.ops.rope import apply_rope

LANES = 128


# --------------------------------------------------------------------------- #
# Latent rows
# --------------------------------------------------------------------------- #
def latent_width(kv_lora_rank: int, qk_rope_head_dim: int) -> int:
    """A cached row: ``kv_lora_rank + qk_rope_head_dim`` values in whole lane
    tiles (the device stores a 576-wide array in rows of 640 whatever the
    program says, and its kernels cannot slice a row that is not whole
    tiles)."""
    return -(-(kv_lora_rank + qk_rope_head_dim) // LANES) * LANES


def latent_rows(ckv, kv_norm, eps: float, rank: int, width: int, rope,
                positions=None):
    """ckv: [B, T, rank + d_r] = ``y W_kva`` -> the rows to cache [B, T, 1,
    width] = [rms(c) | rope(k_r) | 0]. The rotated key is ONE head."""
    c = rms_norm(ckv[..., :rank], kv_norm, eps)
    k_r = apply_rope(ckv[..., None, rank:], *rope, positions)
    pad = jnp.zeros((*c.shape[:-1], 1, width - ckv.shape[-1]), c.dtype)
    return jnp.concatenate([c[..., None, :], k_r, pad], axis=-1)


def latent_attention_reference(q, pool, table, lengths, v_width: int):
    """Gather-based ``paged_attention_latent`` (CPU tests, widths the kernel
    does not tile). q: [B, G, W] scaled; pool: [1, P, ps, W]."""
    b, _, w = q.shape
    kg = pool[0][table].reshape(b, -1, w)                 # [B, S, W]
    logits = jnp.einsum("bgw,bsw->bgs", q, kg,
                        preferred_element_type=jnp.float32)
    seen = jnp.arange(kg.shape[1])[None, :] < lengths[:, None]
    probs = jax.nn.softmax(jnp.where(seen[:, None, :], logits, -1e30), axis=-1)
    out = jnp.einsum("bgs,bsv->bgv", probs.astype(kg.dtype),
                     kg[..., :v_width], preferred_element_type=jnp.float32)
    return jnp.where((lengths > 0)[:, None, None], out, 0.0).astype(q.dtype)


def absorbed_attention(q, wkv_b, pool, table, base, lengths, rope, positions,
                       *, rank: int, nope: int, scale: float,
                       use_kernel: bool):
    """One decode tick's attention of every slot over its cached latent
    rows. q: [B, nh, d_n + d_r] unrotated; wkv_b: [rank, nh * (d_n + d_v)];
    pool: the latent pool, this tick's rows already written; table + base:
    the slots' pages in the layer's block; lengths / positions: [B]. Returns
    [B, nh, d_v]. ``W_uk`` and ``W_uv`` are views of ``W_kvb``."""
    nb, nh, _ = q.shape
    q_r = apply_rope(q[:, None, :, nope:], *rope, positions[:, None])[:, 0]
    # W_kvb a head: [r_kv, nh, d_n | d_v]
    w_kvb = wkv_b.reshape(rank, nh, -1)
    q_c = jnp.einsum("bhn,chn->bhc", q[..., :nope], w_kvb[..., :nope])
    pad = jnp.zeros((nb, nh, pool.shape[-1] - rank - q_r.shape[-1]), q.dtype)
    q_lat = jnp.concatenate([q_c, q_r, pad], axis=-1)
    q_lat = (q_lat * scale).astype(q.dtype)
    if use_kernel:
        o = paged_attention_latent(q_lat, pool, lengths, table + base,
                                   v_width=rank)
    else:
        o = latent_attention_reference(q_lat, pool, table + base, lengths,
                                       rank)
    return jnp.einsum("bhc,chv->bhv", o, w_kvb[..., nope:])


# --------------------------------------------------------------------------- #
# State by slot
# --------------------------------------------------------------------------- #
def init_slot_state(layers: int, num_slots: int, shape, dtype):
    """[layers, num_slots + 1, *shape] zeros: every slot's rows and the trash
    row."""
    return jnp.zeros((layers, num_slots + 1, *shape), dtype)


def slot_state(state, layer: int, nb: int):
    """The live slots' rows of one layer: [nb, ...]."""
    return state[layer, :nb]


def put_slot_state(state, layer: int, rows):
    """A decode tick's rows back over slots ``[0, nb)`` of one layer."""
    return state.at[layer, :rows.shape[0]].set(rows.astype(state.dtype))


def put_prompt_state(state, layer: int, slots, rows):
    """What each prompt's last real token leaves, OVER the rows of the slots
    the prompts were admitted to (a pad row: the trash row)."""
    return state.at[layer, slots].set(rows.astype(state.dtype))
