"""The plain reference of the Llama family: the decoder of the configuration's
source in straightforward ``jax.numpy`` and float32, no kernels, no cache, no
batching tricks. Written from the published description of the Mistral / Llama
block (pre-norm RMSNorm, rotary embeddings in the half-rotation layout the
program also uses, grouped-query causal attention, SwiGLU MLP, untied head),
not from ``models/llama.py``. It takes the weights the BENCHMARK made from the
seed (``families/llama.py``) and upcasts them; nothing the program computed
enters.

Departures from a textbook forward, none of which changes the mathematics:
layers are scanned (weights upcast one layer at a time, so 2 B parameters
never sit in float32 at once), attention is computed in query blocks (so an
S x S score matrix never exists), and both are wrapped in ``jax.checkpoint``
for the gradient.

``quant`` computes the same decoder with every matmul input rounded to a
lower precision (``harness/reference.py``): the CONTROL, the step below the
configuration's bfloat16 that would tempt a later PR (``fp8``), or the
configuration's own precision (``bf16``) for tests.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from benchmarks.harness.reference import (
    gap_fn_of, greedy_fn_of, mean_cross_entropy, mm as _mm,
    round_to as _round_to)


def _rms(x, w, eps):
    import jax
    import jax.numpy as jnp

    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rope(x, theta):
    """x: [S, heads, D]; rotate pairs (i, i + D/2) by pos * theta^(-2i/D)."""
    import jax.numpy as jnp

    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(q, k, v, quant, block: int):
    """q: [S, nh, D]; k, v: [S, nkv, D]; causal; query blocks of ``block``."""
    import jax
    import jax.numpy as jnp

    s, nh, d = q.shape
    nkv = k.shape[1]
    rep = nh // nkv
    block = min(block, s)
    while s % block:  # the largest block that divides the sequence
        block //= 2
    qb = q.reshape(s // block, block, nkv, rep, d)
    kq, vq = _round_to(k, quant), _round_to(v, quant)
    cols = jnp.arange(s)

    @jax.checkpoint
    def one(args):
        qblk, start = args
        scores = jnp.einsum("qnrd,snd->nrqs", _round_to(qblk, quant), kq,
                            precision="highest") * (d ** -0.5)
        rows = start + jnp.arange(block)
        mask = cols[None, :] <= rows[:, None]
        scores = jnp.where(mask[None, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("nrqs,snd->qnrd", _round_to(probs, quant), vq,
                          precision="highest")

    out = jax.lax.map(one, (qb, jnp.arange(0, s, block)))
    return out.reshape(s, nh * d)


def reference_hidden(params: Dict[str, Any], tokens, cfg: Dict[str, Any],
                     quant: Optional[str] = None, block: int = 512):
    """tokens: [S] int32 -> final-norm hidden [S, H] float32."""
    import jax
    import jax.numpy as jnp

    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)  # noqa: E731
    x = params["embed_tokens"][tokens].astype(jnp.float32)
    s = x.shape[0]

    @jax.checkpoint
    def layer(x, lp):
        lp = f32(lp)
        y = _rms(x, lp["attn_norm"], eps)
        q = _rope(_mm(y, lp["wq"], quant).reshape(s, nh, hd), theta)
        k = _rope(_mm(y, lp["wk"], quant).reshape(s, nkv, hd), theta)
        v = _mm(y, lp["wv"], quant).reshape(s, nkv, hd)
        x = x + _mm(_attention(q, k, v, quant, block), lp["wo"], quant)
        y = _rms(x, lp["mlp_norm"], eps)
        gate = jax.nn.silu(_mm(y, lp["w_gate"], quant))
        return x + _mm(gate * _mm(y, lp["w_up"], quant), lp["w_down"], quant)

    x, _ = jax.lax.scan(lambda c, lp: (layer(c, lp), None), x, params["layers"])
    return _rms(x, params["final_norm"].astype(jnp.float32), eps)


def _head(params):
    import jax.numpy as jnp

    head = params.get("lm_head")
    if head is None:
        head = params["embed_tokens"].T
    return head.astype(jnp.float32)


def reference_logits(params, tokens, cfg, quant=None, block: int = 512):
    """tokens: [S] -> logits [S, V] float32."""
    return _mm(reference_hidden(params, tokens, cfg, quant, block),
               _head(params), quant)


def reference_loss(params, tokens, targets, cfg, quant=None, block: int = 512):
    """Mean next-token cross-entropy over rows; tokens/targets: [R, S]."""
    return mean_cross_entropy(
        lambda t: reference_logits(params, t, cfg, quant, block), tokens, targets)


def make_gap_fn(cfg, quant=None):
    """``harness/reference.py`` ``gap_fn_of`` over this decoder."""
    return gap_fn_of(lambda p, t: reference_logits(p, t, cfg, quant))


def make_greedy_fn(cfg, quant=None):
    """``harness/reference.py`` ``greedy_fn_of`` over this decoder."""
    return greedy_fn_of(lambda p, t: reference_logits(p, t, cfg, quant))
