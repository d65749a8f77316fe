"""The plain reference of the Ling-3.0 hybrid family: the decoder of the
configuration's source (``model_type`` bailing_hybrid,
inclusionAI/Ling-3.0-flash) in straightforward ``jax.numpy`` and float32
(``highest``), one sequence at a time, the delta rule a token at a time, the
latent attention UNABSORBED, no kernels, no cache, no batching. Written from
the published configuration's equations, not from ``ray_tpu``, of which it
imports nothing. It takes the weights the BENCHMARK made from the seed and
upcasts them a matrix at a time; nothing the program computed enters.

Layer ``i`` of ``num_hidden_layers`` is ``h = x + Mixer(rms(x)); y = h +
FFN(rms(h))``; the mixer is latent attention where ``(i + 1) %
layer_group_size == 0`` and Kimi Delta Attention otherwise.

- KDA, a head ``n`` of ``num_attention_heads``, ``d = head_dim``: ``[q~ | k~
  | v~ | f | g] = u W_in``; each channel of q~, k~, v~ through a causal
  depthwise convolution over its newest ``short_conv_kernel_size`` rows
  (tap ``j`` on the row ``K - 1 - j`` back; no bias) and SiLU; ``q =
  q~ / sqrt(|q~|^2 + 1e-6) / sqrt(d)``, ``k = k~ / sqrt(|k~|^2 + 1e-6)``, ``v
  = v~``; ``a = kda_lower_bound sigmoid(exp(A_log_n) (f + dt_bias))`` a
  channel; ``beta = sigmoid(u w_beta)`` a head; over the tokens in order,
  ``S <- Diag(exp(a)) S``, ``S <- S + beta k (v - S^T k)^T``, ``o = S^T q``
  (``S`` [d, d] from zeros); ``out = [rms_d(o) * sigmoid(g)] W_o``.
- MLA: ``q = u W_q`` a head (``qk_nope_head_dim`` + ``qk_rope_head_dim``, the
  last rotated); ``[c | k_r] = u W_kva``, ``c = rms(c)``, ``k_r`` rotated
  ONCE and shared; ``[k_n | v] = c W_kvb`` a head; ``softmax((d_n + d_r)^-0.5
  (q_n . k_n + q_r . k_r))`` over ``j <= i``, times ``v``; head ``n`` times
  ``sigmoid(u w_gate,n)``; ``W_o``. Rotary at ``rope_theta``, no scaling.
- FFN, the first ``first_k_dense_replace`` layers: ``(silu(u W_g) * u W_u)
  W_d``. After them: ``s = sigmoid(u W_r)`` over all the router's outputs;
  ``c = s + b``; the outputs in ``n_group`` groups, a group's score the sum
  of its two largest ``c``; among the outputs of the ``topk_group`` best
  groups the ``num_experts_per_tok`` of largest ``c``; weights ``s`` of the
  chosen over their sum, times ``routed_scaling_factor``; plus one shared
  expert, weight 1.

Departures from the published description, each of which the configuration
file lists under ``assumed``, ``reduced`` or ``share``: the half-rotation
rotary layout; the gate forms above; the share: ``held_experts = [lo, hi]`` of
the ``n_router_outputs`` experts are held, the router scores, limits and
normalises over ALL of them, the sum is over the held ones that were chosen
and what the others would add is dropped; the vocabulary is the rows held.

``quant`` rounds the inputs of every product with learned weights, and of the
attention and delta-rule products, to a lower precision
(``harness/reference.py``): the CONTROL (``fp8``), or ``bf16`` for tests. The
router's scores and the delta rule's state stay float32. A KDA layer runs a
group of heads at a time (the five projections of 33,024 rows are 2.7 GB in
float32 if formed whole), experts are upcast and multiplied one at a time,
attention in query blocks, the dense MLP and the head in row blocks.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional

from benchmarks.families.kimi_k2_reference import (
    _block_for, _dense_ffn, rope_table)
from benchmarks.families.laguna_reference import (
    _by_rows, _f32, _out_of_the_compile_cache, _rms, _rotate, _swiglu)
from benchmarks.harness.reference import mm as _mm, round_to as _round_to

HEAD_GROUP = 8


def is_mla(cfg: Dict[str, Any], layer: int) -> bool:
    return (layer + 1) % cfg["layer_group_size"] == 0


def delta_rule(q, k, v, a, beta):
    """The recurrence, a token a step. q, k, v, a: [S, H, d]; beta: [S, H].
    Returns o [S, H, d]."""
    import jax
    import jax.numpy as jnp

    def step(state, part):
        qt, kt, vt, at, bt = part                       # state: [H, d_k, d_v]
        state = jnp.exp(at)[:, :, None] * state
        seen = jnp.einsum("hk,hkv->hv", kt, state, precision="highest")
        state = state + kt[:, :, None] * (bt[:, None] * (vt - seen))[:, None, :]
        return state, jnp.einsum("hk,hkv->hv", qt, state, precision="highest")

    heads, d = q.shape[1:]
    _, o = jax.lax.scan(step, jnp.zeros((heads, d, d), jnp.float32),
                        (q, k, v, a, beta))
    return o


def _kda(lp, u, cfg, quant):
    """u: [S, h] normed -> the KDA mixer's output [S, h], ``HEAD_GROUP`` heads
    at a time."""
    import jax
    import jax.numpy as jnp

    nh, d, taps = (cfg["num_attention_heads"], cfg["head_dim"],
                   cfg["short_conv_kernel_size"])
    s, h = u.shape
    g = math.gcd(nh, HEAD_GROUP)
    eps, low = float(cfg["rms_norm_eps"]), float(cfg["kda_lower_bound"])

    def by_group(w, parts):
        """[..., parts * nh * d] -> [nh / g, ..., parts, g * d]."""
        w = w.reshape(*w.shape[:-1], parts, nh // g, g * d)
        return jnp.moveaxis(w, -2, 0)

    def group(acc, w):
        w_in, w_beta, conv_w, a_log, dt_bias, wo = w
        parts = _mm(u, w_in.reshape(h, 5 * g * d), quant).reshape(s, 5, g * d)
        padded = jnp.pad(parts[:, :3], ((taps - 1, 0), (0, 0), (0, 0)))
        qkv = sum(padded[j:j + s] * conv_w[j] for j in range(taps))
        qkv = jax.nn.silu(qkv).reshape(s, 3, g, d)

        def unit(t):
            return t / jnp.sqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)

        q, k, v = unit(qkv[:, 0]) / math.sqrt(d), unit(qkv[:, 1]), qkv[:, 2]
        f = (parts[:, 3] + dt_bias).reshape(s, g, d)
        a = low * jax.nn.sigmoid(jnp.exp(a_log)[:, None] * f)
        beta = jax.nn.sigmoid(_mm(u, w_beta, quant))
        o = delta_rule(_round_to(q, quant), _round_to(k, quant),
                       _round_to(v, quant), a, beta)
        gated = _rms(o, lp["o_norm"], eps) \
            * jax.nn.sigmoid(parts[:, 4].reshape(s, g, d))
        return acc + _mm(gated.reshape(s, g * d), wo, quant), None

    out, _ = jax.lax.scan(group, jnp.zeros_like(u), (
        by_group(lp["w_in"], 5), lp["w_beta"].reshape(h, nh // g, g).transpose(
            1, 0, 2),
        by_group(lp["conv_w"], 3), lp["a_log"].reshape(nh // g, g),
        lp["dt_bias"].reshape(nh // g, g * d),
        lp["wo"].reshape(nh // g, g * d, h)))
    return out


def _mla(lp, u, cfg, quant, block: int):
    """u: [S, h] normed; the unabsorbed form, full masks, in query blocks,
    ``HEAD_GROUP`` heads at a time."""
    import jax
    import jax.numpy as jnp

    nh, rkv = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    eps, s = float(cfg["rms_norm_eps"]), u.shape[0]
    scale = (dn + dr) ** -0.5
    cos, sin = rope_table(cfg, s)
    ckv = _mm(u, lp["wkv_a"], quant)
    c = _rms(ckv[:, :rkv], lp["kv_norm"], eps)
    k_r = _rotate(ckv[:, None, rkv:], cos, sin)            # [S, 1, d_r]: once
    gate = jax.nn.sigmoid(_mm(u, lp["w_gate"], quant))     # [S, nh]
    block = min(block, s)
    while s % block:
        block //= 2
    cols = jnp.arange(s)
    g = math.gcd(nh, HEAD_GROUP)

    def heads(acc, w):
        wq, wkv_b, wo, gate_g = w
        q = _mm(u, wq, quant).reshape(s, g, dn + dr)
        q = jnp.concatenate([q[..., :dn], _rotate(q[..., dn:], cos, sin)],
                            axis=-1)
        kv = _mm(c, wkv_b, quant).reshape(s, g, dn + dv)
        k = _round_to(jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_r, (s, g, dr))], axis=-1), quant)
        v = _round_to(kv[..., dn:], quant)

        def one(args):
            qblk, start = args
            scores = jnp.einsum("qhd,shd->hqs", _round_to(qblk, quant), k,
                                precision="highest") * scale
            seen = cols[None, :] <= (start + jnp.arange(block))[:, None]
            probs = jax.nn.softmax(jnp.where(seen[None], scores, -1e30),
                                   axis=-1)
            return jnp.einsum("hqs,shd->qhd", _round_to(probs, quant), v,
                              precision="highest")

        out = jax.lax.map(one, (q.reshape(s // block, block, g, dn + dr),
                                jnp.arange(0, s, block)))
        out = out.reshape(s, g, dv) * gate_g[:, :, None]
        return acc + _mm(out.reshape(s, g * dv), wo, quant), None

    def columns(w, width):
        return w.reshape(w.shape[0], nh // g, g * width).transpose(1, 0, 2)

    out, _ = jax.lax.scan(heads, jnp.zeros_like(u), (
        columns(lp["wq"], dn + dr), columns(lp["wkv_b"], dn + dv),
        lp["wo"].reshape(nh // g, g * dv, -1),
        gate.reshape(s, nh // g, g).transpose(1, 0, 2)))
    return out


def routing(lp, u, cfg):
    """u: [S, h] -> (chosen experts [S, k], their weights [S, k]): the
    group-limited rule, literally."""
    import jax
    import jax.numpy as jnp

    router = lp["router"]
    n_group, topk_group = cfg["n_group"], cfg["topk_group"]
    scores = jax.nn.sigmoid(jnp.matmul(u, router["w"].astype(jnp.float32),
                                       precision="highest"))
    choice = scores + router["bias"].astype(jnp.float32)
    s, r = choice.shape
    groups = choice.reshape(s, n_group, r // n_group)
    group_score = jnp.sum(jnp.sort(groups, axis=-1)[..., -2:], axis=-1)
    # a group is kept if fewer than topk_group groups score more than it
    ahead = jnp.sum(group_score[:, None, :] > group_score[:, :, None], axis=-1)
    kept = ahead < topk_group                                  # [S, n_group]
    limited = jnp.where(kept[:, :, None], groups, -jnp.inf).reshape(s, r)
    _, chosen = jax.lax.top_k(limited, cfg["num_experts_per_tok"])
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True) \
        * float(cfg["routed_scaling_factor"])
    return chosen, weights


def routed_sum(lp, u, cfg, quant, held=None):
    """The weighted sum of the experts ``held = [lo, hi]`` (default: the
    configuration's) over the tokens routed to them; ``lp["experts"]`` holds
    exactly those, in the served dtype, and each is taken off it, upcast and
    multiplied alone."""
    import jax
    import jax.numpy as jnp

    lo, hi = held or cfg["held_experts"]
    chosen, weights = routing(lp, u, cfg)
    ids = jnp.arange(lo, hi)
    per_expert = jnp.sum(jnp.where(chosen[:, :, None] == ids[None, None, :],
                                   weights[:, :, None], 0.0), axis=1)

    def one(acc, args):
        e, weight = args
        w = jax.tree.map(lambda a: a[e], lp["experts"])
        return acc + weight[:, None] * _swiglu(u, _f32(w), quant), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(u),
                             (jnp.arange(hi - lo), per_expert.T))
    return routed


MIXER_KDA = ("w_in", "w_beta", "conv_w", "a_log", "dt_bias", "o_norm", "wo")
MIXER_MLA = ("wq", "wkv_a", "kv_norm", "wkv_b", "w_gate", "wo")


def _layer(lp, x, cfg, quant, block):
    """One layer over x [S, h] float32; what ``lp`` holds says its kind."""
    import jax.numpy as jnp

    eps = float(cfg["rms_norm_eps"])
    u = _rms(x, lp["attn_norm"].astype(jnp.float32), eps)
    if "wkv_a" in lp:
        x = x + _mla(_f32({k: lp[k] for k in MIXER_MLA}), u, cfg, quant, block)
    else:
        x = x + _kda(_f32({k: lp[k] for k in MIXER_KDA}), u, cfg, quant)
    u = _rms(x, lp["mlp_norm"].astype(jnp.float32), eps)
    if "mlp" in lp:
        return x + _dense_ffn(lp["mlp"], u, quant)
    shared = _f32(lp["shared"])
    return x + routed_sum(lp, u, cfg, quant) \
        + _by_rows(lambda rows: _swiglu(rows, shared, quant), u)


def reference_hidden(params: Dict[str, Any], tokens, cfg: Dict[str, Any],
                     quant: Optional[str] = None, block: Optional[int] = None):
    """tokens: [S] int32 -> final-norm hidden [S, h] float32, as ONE traced
    function (tests, small sizes)."""
    import jax.numpy as jnp

    block = _block_for(tokens.shape[0], block)
    x = params["embed_tokens"][tokens].astype(jnp.float32)
    for lp in params["layers"]:
        x = _layer(lp, x, cfg, quant, block)
    return _rms(x, params["final_norm"].astype(jnp.float32),
                float(cfg["rms_norm_eps"]))


def reference_logits(params, tokens, cfg, quant=None, block=None):
    """tokens: [S] -> logits [S, V] float32 over the vocabulary held."""
    import jax.numpy as jnp

    head = params["lm_head"].astype(jnp.float32)
    return _by_rows(lambda rows: _mm(rows, head, quant),
                    reference_hidden(params, tokens, cfg, quant, block))


def hidden_fn(cfg: Dict[str, Any], quant: Optional[str] = None,
              block: Optional[int] = None):
    """(params, tokens [S]) -> final-norm hidden [S, h] float32 at the
    benchmark's sizes: a Python loop over the layers, ONE jitted layer
    function that compiles a program a layer KIND (what a layer's dict holds:
    three kinds in six layers), so that what is live is one layer's float32
    copies and a sequence of 33,024 fits beside the engine."""
    import jax
    import jax.numpy as jnp

    layer = jax.jit(lambda lp, x: _layer(
        lp, x, cfg, quant, _block_for(x.shape[0], block)))
    final = jax.jit(lambda x, w: _rms(x, w.astype(jnp.float32),
                                      float(cfg["rms_norm_eps"])))

    def hidden(params, tokens):
        x = params["embed_tokens"][jnp.asarray(tokens)].astype(jnp.float32)
        for lp in params["layers"]:
            x = layer(lp, x)
        return final(x, params["final_norm"])

    return hidden


def make_gap_fn(cfg, quant=None):
    """(params, tokens[length], chosen[length]) -> per position the
    reference's largest logit minus its logit of ``chosen``
    (``harness/reference.py`` ``gap_fn_of``), the head a row block at a time:
    the logits of 33,024 rows are 5 GB and never exist."""
    import jax
    import jax.numpy as jnp

    hidden = hidden_fn(cfg, quant)

    def gaps(h, head, chosen):
        head = head.astype(jnp.float32)

        def rows(both):
            logits = _mm(both[:, :-1], head, quant)
            picked = jnp.take_along_axis(
                logits, both[:, -1:].astype(jnp.int32), axis=-1)[:, 0]
            return jnp.max(logits, axis=-1) - picked

        # the chosen ids ride beside their rows (exact in float32: < 2^24)
        return _by_rows(rows, jnp.concatenate(
            [h, chosen[:, None].astype(jnp.float32)], axis=-1))

    gaps = jax.jit(gaps)
    return _out_of_the_compile_cache(
        lambda params, tokens, chosen: gaps(
            hidden(params, tokens), params["lm_head"], jnp.asarray(chosen)))


def make_greedy_fn(cfg, quant=None):
    """(params, tokens[length], pos) -> argmax token after tokens[:pos]. Full
    recompute per token: no cache, by design."""
    import jax
    import jax.numpy as jnp

    hidden = hidden_fn(cfg, quant)
    pick = jax.jit(lambda h, head, pos: jnp.argmax(_mm(
        jax.lax.dynamic_slice_in_dim(h, pos - 1, 1), head.astype(jnp.float32),
        quant)[0]).astype(jnp.int32))
    return _out_of_the_compile_cache(
        lambda params, tokens, pos: pick(
            hidden(params, tokens), params["lm_head"], pos))
