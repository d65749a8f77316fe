"""Rotary position embeddings (RoPE), half-rotation layout: over the whole
head or over its first ``rotary_dim`` dimensions (the rest pass through),
with plain or YaRN-scaled frequencies."""

from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax.numpy as jnp


def yarn_inv_freq(rotary_dim: int, theta: float, yarn: Dict[str, Any]):
    """YaRN's frequencies [rotary_dim // 2] (Peng et al. 2023, as the
    published ``rope_type: yarn`` configs are read): dimension ``i`` keeps its
    plain frequency ``f_i = theta^(-2i / rotary_dim)`` where it turns more than
    ``beta_fast`` times over the original context, takes ``f_i / factor``
    where it turns less than ``beta_slow`` times, and a linear blend between.
    ``yarn``: ``factor``, ``original_max_position_embeddings``, ``beta_fast``,
    ``beta_slow``."""
    half = rotary_dim // 2
    f = 1.0 / (theta ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32)
                         / rotary_dim))
    original = yarn["original_max_position_embeddings"]

    def correction_dim(turns):
        return rotary_dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(yarn["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(yarn["beta_slow"])), rotary_dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low) / (high - low),
                    0.0, 1.0)
    keep = 1.0 - ramp
    return f / yarn["factor"] * (1.0 - keep) + f * keep


def rope_frequencies(head_dim: int, max_seq: int, theta: float = 10000.0,
                     rotary_dim: Optional[int] = None,
                     yarn: Optional[Dict[str, Any]] = None):
    """Precompute cos/sin tables: [max_seq, rotary_dim//2] each (fp32);
    ``rotary_dim`` defaults to the whole head. With ``yarn`` the frequencies
    are ``yarn_inv_freq``'s and both tables are multiplied by its
    ``attention_factor``: applied to q and k alike, the scores are scaled by
    its square, as the published scheme scales them."""
    rotary_dim = rotary_dim or head_dim
    if yarn is None:
        inv_freq = 1.0 / (theta ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32) / rotary_dim))
    else:
        inv_freq = yarn_inv_freq(rotary_dim, theta, yarn)
    t = jnp.arange(max_seq, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)
    if yarn is None:
        return jnp.cos(freqs), jnp.sin(freqs)
    factor = yarn["attention_factor"]
    return jnp.cos(freqs) * factor, jnp.sin(freqs) * factor


def apply_rope(x, cos, sin, positions=None):
    """x: [..., seq, n_heads, head_dim]; cos/sin: [max_seq, rotary_dim//2];
    positions: optional [..., seq] int32 (for decode with offsets). The
    first ``rotary_dim`` dimensions are rotated (in halves), the rest pass."""
    seq = x.shape[-3]
    if positions is None:
        c = cos[:seq]
        s = sin[:seq]
        # [seq, hd/2] -> [seq, 1, hd/2] to broadcast over heads
        c = c[:, None, :]
        s = s[:, None, :]
    else:
        c = cos[positions][..., None, :]
        s = sin[positions][..., None, :]
    rotary_dim = 2 * cos.shape[-1]
    whole = rotary_dim == x.shape[-1]
    turned = x if whole else x[..., :rotary_dim]
    x1, x2 = jnp.split(turned.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    out = out.astype(x.dtype)
    return out if whole else jnp.concatenate([out, x[..., rotary_dim:]], axis=-1)
