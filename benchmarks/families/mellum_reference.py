"""The plain reference of the Mellum family on the TRAINING path: the decoder
of the configuration's source (``model_type`` mellum,
JetBrains/Mellum2-12B-A2.5B-Instruct) and its loss in straightforward
``jax.numpy`` and float32 (``highest``), one sequence at a time, no kernels,
no sort, no grouped product, no cache. Written from the published
configuration's equations, not from ``ray_tpu``, of which it imports nothing.
It takes the weights the BENCHMARK made from the seed and upcasts them; its
gradient is ``jax.grad`` of it.

Every layer ``l`` is ``h = x + Attn_l(rms(x)); y = h + MoE(rms'(h))``; then a
final RMSNorm, logits ``y W_head`` over the vocabulary held, and the mean
cross-entropy of the next token.

- ``Attn_l``: ``num_attention_heads`` query heads over
  ``num_key_value_heads`` KV heads of ``head_dim``; q and k rotated over the
  WHOLE head (half-rotation layout) by the scheme of ``layer_types[l]``;
  ``softmax(q k / sqrt(head_dim))`` over ``j <= i`` and, on
  ``sliding_attention`` layers, ``i - j < sliding_window``; ``W_o``. No bias.
- rotary by ``rope_parameters[layer type]``: ``f_i = theta^(-2i / d)``;
  ``rope_type: yarn``: ``inv_freq_i = f_i / factor x (1 - m_i) + f_i x m_i``
  with ``m_i = 1 - clip((i - low) / (high - low), 0, 1)`` and ``low``,
  ``high`` the floor and ceiling of ``d ln(original / (2 pi beta)) / (2 ln
  theta)`` at ``beta_fast`` and ``beta_slow``; cos and sin times
  ``attention_factor``, on q and k alike.
- ``MoE``: ``p = softmax(u W_r)`` over all the router's outputs in float32;
  the ``num_experts_per_tok`` largest; their ``p`` over their sum
  (``norm_topk_prob``); ``sum_k w_k E_k(u)``, ``E(u) = (silu(u W_g) * u W_u)
  W_d``. Every held expert runs over every token, weighted by the router's
  ``w`` or by zero. The gradient reaches ``W_r`` through ``w``.

Departures, each listed in the configuration file under ``assumed``,
``reduced`` or ``share``: softmax scoring; no q/k norm; no auxiliary
load-balance term; the share: ``held_experts = [lo, hi]`` of the
``n_router_outputs`` experts are held, the router scores and normalises over
ALL of them, the sum is over the held ones that were chosen and what the
others would add is dropped; the vocabulary is the ``vocab_size`` rows held.

Kept small for the compiler and the memory, none of which changes the
mathematics: the layers are one scanned body (a sliding layer is a full one
with a window and another rotary table, both chosen by the layer's index),
each layer, each block of queries and each expert under ``jax.checkpoint``.

``quant`` rounds the inputs of every product with learned weights, and of the
attention products, to a lower precision (``harness/reference.py``): the
CONTROL (``fp8``), or ``bf16`` for tests. The router's scores stay float32.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional

from benchmarks.harness.reference import (
    gap_fn_of, greedy_fn_of, mean_cross_entropy, mm as _mm,
    round_to as _round_to)

SLIDING = "sliding_attention"


def _rms(x, w, eps):
    import jax
    import jax.numpy as jnp

    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def rope_table(rp: Dict[str, Any], head_dim: int, seq: int):
    """cos, sin [seq, head_dim / 2] of one ``rope_parameters`` block, the
    attention factor folded in."""
    import jax.numpy as jnp

    theta = float(rp["rope_theta"])
    i = jnp.arange(head_dim // 2, dtype=jnp.float32)
    f = theta ** (-2.0 * i / head_dim)
    factor = 1.0
    if rp.get("rope_type", "default") == "yarn":
        original = rp["original_max_position_embeddings"]

        def dim_of(beta):
            return head_dim * math.log(original / (beta * 2 * math.pi)) \
                / (2 * math.log(theta))

        low = max(math.floor(dim_of(rp["beta_fast"])), 0)
        high = min(math.ceil(dim_of(rp["beta_slow"])), head_dim - 1)
        m = 1.0 - jnp.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
        f = f / rp["factor"] * (1.0 - m) + f * m
        factor = rp["attention_factor"]
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * f[None, :]
    return jnp.cos(angles) * factor, jnp.sin(angles) * factor


def _rotate(x, cos, sin):
    """x: [S, heads, D]; pairs (i, i + D/2) turn."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _attention(lp, u, cfg, rope, window, quant, block: int):
    """u: [S, h] normed; ``window``: a traced scalar, the keys a query sees
    (the sequence's length on a full layer); query blocks of ``block``."""
    import jax
    import jax.numpy as jnp

    nq, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    s, rep = u.shape[0], nq // nkv
    cos, sin = rope
    q = _rotate(_mm(u, lp["wq"], quant).reshape(s, nq, d), cos, sin)
    k = _round_to(_rotate(_mm(u, lp["wk"], quant).reshape(s, nkv, d), cos, sin),
                  quant)
    v = _round_to(_mm(u, lp["wv"], quant).reshape(s, nkv, d), quant)
    block = min(block, s)
    while s % block:
        block //= 2
    cols = jnp.arange(s)

    @jax.checkpoint
    def one(args):
        qblk, start = args
        scores = jnp.einsum("qnrd,snd->nrqs", _round_to(qblk, quant), k,
                            precision="highest") * (d ** -0.5)
        rows = start + jnp.arange(block)
        seen = (cols[None, :] <= rows[:, None]) \
            & (rows[:, None] - cols[None, :] < window)
        probs = jax.nn.softmax(jnp.where(seen[None, None], scores, -1e30),
                               axis=-1)
        return jnp.einsum("nrqs,snd->qnrd", _round_to(probs, quant), v,
                          precision="highest")

    out = jax.lax.map(one, (q.reshape(s // block, block, nkv, rep, d),
                            jnp.arange(0, s, block)))
    return _mm(out.reshape(s, nq * d), lp["wo"], quant)


def routing(router, u, cfg):
    """u: [S, h] -> (chosen experts [S, k], their weights [S, k])."""
    import jax
    import jax.numpy as jnp

    p = jax.nn.softmax(jnp.matmul(u, router.astype(jnp.float32),
                                  precision="highest"), axis=-1)
    weights, chosen = jax.lax.top_k(p, cfg["num_experts_per_tok"])
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return chosen, weights


def routed_sum(lp, u, cfg, quant, held=None):
    """The weighted sum of the experts ``held = [lo, hi]`` (default: the
    configuration's) over every token, one expert at a time, each weighted by
    the router's ``w`` for the token or by zero; ``lp["w_gate"]`` ... hold
    exactly those experts."""
    import jax
    import jax.numpy as jnp

    lo, hi = held or cfg["held_experts"]
    chosen, weights = routing(lp["router"], u, cfg)
    ids = jnp.arange(lo, hi)
    # [S, E_held]: the weight of each held expert for each token, 0 if unchosen
    per_expert = jnp.sum(jnp.where(chosen[:, :, None] == ids[None, None, :],
                                   weights[:, :, None], 0.0), axis=1)

    @jax.checkpoint
    def one(acc, args):
        w_gate, w_up, w_down, weight = args
        f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
        out = _mm(jax.nn.silu(_mm(u, f32(w_gate), quant))
                  * _mm(u, f32(w_up), quant), f32(w_down), quant)
        return acc + weight[:, None] * out, None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(u), (
        lp["w_gate"], lp["w_up"], lp["w_down"], per_expert.T))
    return routed


def reference_hidden(params: Dict[str, Any], tokens, cfg: Dict[str, Any],
                     quant: Optional[str] = None, block: int = 256):
    """tokens: [S] int32 -> final-norm hidden [S, h] float32."""
    import jax
    import jax.numpy as jnp

    eps, d = float(cfg["rms_norm_eps"]), cfg["head_dim"]
    s = tokens.shape[0]
    kinds = sorted(set(cfg["layer_types"]))
    tables = [rope_table(cfg["rope_parameters"][kind], d, s) for kind in kinds]
    cos = jnp.stack([t[0] for t in tables])
    sin = jnp.stack([t[1] for t in tables])
    kind_of = jnp.asarray([kinds.index(k) for k in cfg["layer_types"]], jnp.int32)
    window_of = jnp.asarray(
        [cfg["sliding_window"] if k == SLIDING else s
         for k in cfg["layer_types"]], jnp.int32)
    if set(cfg["mlp_layer_types"]) != {"sparse"}:
        raise ValueError("every published layer of this family is sparse")
    x = params["embed_tokens"][tokens].astype(jnp.float32)

    @jax.checkpoint
    def layer(x, args):
        lp, kind, window = args
        attn = {n: lp[n].astype(jnp.float32) for n in ("wq", "wk", "wv", "wo")}
        u = _rms(x, lp["attn_norm"].astype(jnp.float32), eps)
        x = x + _attention(attn, u, cfg, (cos[kind], sin[kind]), window, quant,
                           block)
        u = _rms(x, lp["mlp_norm"].astype(jnp.float32), eps)
        return x + routed_sum(lp, u, cfg, quant), None

    x, _ = jax.lax.scan(layer, x, (params["layers"], kind_of, window_of))
    return _rms(x, params["final_norm"].astype(jnp.float32), eps)


def reference_logits(params, tokens, cfg, quant=None, block: int = 256):
    """tokens: [S] -> logits [S, V] float32 over the vocabulary held."""
    import jax.numpy as jnp

    return _mm(reference_hidden(params, tokens, cfg, quant, block),
               params["lm_head"].astype(jnp.float32), quant)


def not_for_the_compile_cache():
    """A host callback that does nothing. A program that holds one is not
    written to jax's persistent compilation cache (``jax/_src/compiler.py``
    ``_cache_write``: "because it uses host callbacks"). The train runner
    jits the reference itself, so the family cannot switch the cache off
    around the call as the serving references do
    (``laguna_reference._out_of_the_compile_cache``); the reference and the
    control run once a run, after the window, and what they would write
    pushes an accepted cell's programs out of the chip machine's capped cache
    (PERF.md 6, PR 35)."""
    import jax

    jax.debug.callback(lambda: None)


def reference_loss(params, tokens, targets, cfg, quant=None, block: int = 256):
    """Mean next-token cross-entropy over rows; tokens/targets: [R, S]."""
    not_for_the_compile_cache()
    return mean_cross_entropy(
        lambda t: reference_logits(params, t, cfg, quant, block), tokens, targets)


def make_gap_fn(cfg, quant=None):
    """``harness/reference.py`` ``gap_fn_of`` over this decoder."""
    return gap_fn_of(lambda p, t: reference_logits(p, t, cfg, quant))


def make_greedy_fn(cfg, quant=None):
    """``harness/reference.py`` ``greedy_fn_of`` over this decoder."""
    return greedy_fn_of(lambda p, t: reference_logits(p, t, cfg, quant))
