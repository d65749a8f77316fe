"""The plain reference of the Kimi-K2 family: the decoder of the
configuration's source (``model_type`` kimi_k2, moonshotai/Kimi-K2.6; the
DeepSeek-V3 block) in straightforward ``jax.numpy`` and float32
(``highest``), one sequence at a time, UNABSORBED, no kernels, no cache, no
batching. Written from the published configuration's equations, not from
``ray_tpu``, of which it imports nothing. It takes the weights the BENCHMARK
made from the seed and upcasts them a matrix at a time; nothing the program
computed enters.

Every layer is ``h = x + Attn(rms(x)); y = h + FFN(rms(h))``:

- ``Attn(u)``: ``c_q = rms(u W_qa)``; ``[q_n | q_r] = c_q W_qb`` a head
  (``qk_nope_head_dim`` + ``qk_rope_head_dim``); ``[c | k_r] = u W_kva``
  (``kv_lora_rank`` + ``qk_rope_head_dim``); ``c = rms(c)``; ``q_r`` rotated a
  head, ``k_r`` rotated ONCE and shared by every head; ``[k_n | v] = c W_kvb``
  a head; ``softmax(s (q_n . k_n + q_r . k_r))`` over the full mask
  ``j <= i``, times ``v``; ``W_o``.
- the scale: ``s = (d_n + d_r)^-0.5 m^2``, ``m = 0.1 mscale_all_dim
  ln(factor) + 1`` (1 without ``rope_scaling``). Rotary, half-rotation
  layout, YaRN frequencies: ``f_i = theta^(-2i / d_r)``; ``inv_freq_i = f_i /
  factor x (1 - k_i) + f_i x k_i`` with ``k_i = 1 - clip((i - low) / (high -
  low), 0, 1)``, ``low`` and ``high`` the floor and ceiling of ``d_r
  ln(original / (2 pi beta)) / (2 ln theta)`` at ``beta_fast`` and
  ``beta_slow``; cos and sin times ``mscale / mscale_all_dim`` (1 as
  published): the factor is on the SCORES, not on the tables.
- ``FFN``, the first ``first_k_dense_replace`` layers: ``(silu(u W_g) * u
  W_u) W_d``. After them: ``scores = sigmoid(u W_r)`` over all the router's
  outputs; the ``num_experts_per_tok`` of largest ``scores + b``
  (``n_group = topk_group = 1``: no group limit); weights ``scores`` of the
  chosen WITHOUT ``b``, over their sum (``norm_topk_prob``), times
  ``routed_scaling_factor``; plus one shared expert, weight 1.

Departures from the published description, each of which the configuration
file lists under ``assumed``, ``reduced`` or ``share``: the half-rotation
layout (the checkpoint interleaves pairs: a fixed permutation of the rotated
columns of ``W_qb`` and ``W_kva``); the share: ``held_experts = [lo, hi]`` of
the ``n_router_outputs`` experts are held, the router scores and normalises
over ALL of them, the sum is over the held ones that were chosen and what the
others would add is dropped; the vocabulary is the ``vocab_size`` rows held.

The parameter tree is the one the benchmark's seeded weights come in:
``dense_layers`` a list, ``layers`` the expert layers stacked on a leading
axis. ``quant`` rounds the inputs of every product with learned weights, and
of the attention products, to a lower precision (``harness/reference.py``):
the CONTROL (``fp8``), or ``bf16`` for tests. The router's scores stay
float32. Experts are upcast and multiplied one at a time, attention in query
blocks, the dense MLP and the head in row blocks, so that a sequence of 25,088
fits beside the engine.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional

from benchmarks.families.laguna_reference import (
    _by_rows, _f32, _out_of_the_compile_cache, _rms, _rotate, _swiglu)
from benchmarks.harness.reference import mm as _mm, round_to as _round_to


def mscale(cfg: Dict[str, Any]) -> float:
    """``m``: what YaRN puts on the scores, squared."""
    rs = cfg.get("rope_scaling")
    if not rs or rs["factor"] <= 1:
        return 1.0
    return 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0


def rope_table(cfg: Dict[str, Any], seq: int):
    """cos, sin [seq, d_r / 2]."""
    import jax.numpy as jnp

    d_r, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    i = jnp.arange(d_r // 2, dtype=jnp.float32)
    f = theta ** (-2.0 * i / d_r)
    on_tables = 1.0
    rs = cfg.get("rope_scaling")
    if rs:
        original = rs["original_max_position_embeddings"]

        def dim_of(beta):
            return d_r * math.log(original / (beta * 2 * math.pi)) \
                / (2 * math.log(theta))

        low = max(math.floor(dim_of(rs["beta_fast"])), 0)
        high = min(math.ceil(dim_of(rs["beta_slow"])), d_r - 1)
        keep = 1.0 - jnp.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
        f = f / rs["factor"] * (1.0 - keep) + f * keep
        on_tables = rs["mscale"] / rs["mscale_all_dim"]
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * f[None, :]
    return jnp.cos(angles) * on_tables, jnp.sin(angles) * on_tables


HEAD_GROUP = 8


def _attention(lp, u, cfg, quant, block: int):
    """u: [S, h] normed; the unabsorbed form, full masks, in query blocks,
    ``HEAD_GROUP`` heads at a time (q and k of 64 heads over 25,088 rows are
    1.2 GB each in float32)."""
    import jax
    import jax.numpy as jnp

    nh, rkv = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    eps, s = float(cfg["rms_norm_eps"]), u.shape[0]
    scale = (dn + dr) ** -0.5 * mscale(cfg) ** 2
    cos, sin = rope_table(cfg, s)
    c_q = _rms(_mm(u, lp["wq_a"], quant), lp["q_norm"], eps)
    ckv = _mm(u, lp["wkv_a"], quant)
    c = _rms(ckv[:, :rkv], lp["kv_norm"], eps)
    k_r = _rotate(ckv[:, None, rkv:], cos, sin)            # [S, 1, d_r]: once
    block = min(block, s)
    while s % block:
        block //= 2
    cols = jnp.arange(s)
    g = math.gcd(nh, HEAD_GROUP)

    def heads(acc, w):
        wq_b, wkv_b, wo = w      # the group's columns of W_qb, W_kvb; rows of W_o
        q = _mm(c_q, wq_b, quant).reshape(s, g, dn + dr)
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
        return acc + _mm(out.reshape(s, g * dv), wo, quant), None

    def columns(w, width):
        return w.reshape(w.shape[0], nh // g, g * width).transpose(1, 0, 2)

    # concat(a_i) W_o as the sum over groups of heads of a_group W_o[group]
    out, _ = jax.lax.scan(heads, jnp.zeros_like(u), (
        columns(lp["wq_b"], dn + dr), columns(lp["wkv_b"], dn + dv),
        lp["wo"].reshape(nh // g, g * dv, -1)))
    return out


def routing(lp, u, cfg):
    """u: [S, h] -> (chosen experts [S, k], their weights [S, k])."""
    import jax
    import jax.numpy as jnp

    router = lp["router"]
    scores = jax.nn.sigmoid(jnp.matmul(u, router["w"].astype(jnp.float32),
                                       precision="highest"))
    _, chosen = jax.lax.top_k(scores + router["bias"].astype(jnp.float32),
                              cfg["num_experts_per_tok"])
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True) \
        * float(cfg["routed_scaling_factor"])
    return chosen, weights


def routed_sum(lp, u, cfg, quant, held=None, layer=None):
    """The weighted sum of the experts ``held = [lo, hi]`` (default: the
    configuration's) over the tokens routed to them; ``lp["experts"]`` holds
    exactly those, in the served dtype, and each is taken off it, upcast and
    multiplied alone. With ``layer`` the experts are still on the stack of
    layers, [L, E, ...], and expert ``e`` is ``[layer, e]`` of it: no copy of
    a layer's 1.06 GB of experts is made."""
    import jax
    import jax.numpy as jnp

    lo, hi = held or cfg["held_experts"]
    chosen, weights = routing(lp, u, cfg)
    ids = jnp.arange(lo, hi)
    per_expert = jnp.sum(jnp.where(chosen[:, :, None] == ids[None, None, :],
                                   weights[:, :, None], 0.0), axis=1)

    def one(acc, args):
        e, weight = args
        w = jax.tree.map(lambda a: a[e] if layer is None else a[layer, e],
                         lp["experts"])
        return acc + weight[:, None] * _swiglu(u, _f32(w), quant), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(u),
                             (jnp.arange(hi - lo), per_expert.T))
    return routed


DENSE_PIECES = 4


def _dense_ffn(w, u, quant):
    """(silu(u W_g) * u W_u) W_d as the sum over ``DENSE_PIECES`` pieces of
    the width of (silu(u W_g[:, p]) * u W_u[:, p]) W_d[p]: a piece's three
    matrices are upcast at a time (whole they are 1.6 GB in float32 at
    18,432), the rows in blocks."""
    import jax
    import jax.numpy as jnp

    h, f = w["w_gate"].shape
    n = math.gcd(f, DENSE_PIECES)

    def piece(p, acc):
        def cut(m, axis):
            return jax.lax.dynamic_slice_in_dim(m, p * (f // n), f // n, axis)

        ws = _f32({"w_gate": cut(w["w_gate"], 1), "w_up": cut(w["w_up"], 1),
                   "w_down": cut(w["w_down"], 0)})
        return acc + _by_rows(lambda rows: _swiglu(rows, ws, quant), u)

    return jax.lax.fori_loop(0, n, piece, jnp.zeros_like(u))


ATTENTION = ("wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo")


def _layer(lp, x, cfg, quant, block, layer=None):
    """One layer over x [S, h] float32; ``lp`` a dense layer (``mlp``) or an
    expert layer (``router``, ``experts``, ``shared``)."""
    import jax.numpy as jnp

    eps = float(cfg["rms_norm_eps"])
    u = _rms(x, lp["attn_norm"].astype(jnp.float32), eps)
    x = x + _attention(_f32({k: lp[k] for k in ATTENTION}), u, cfg, quant, block)
    u = _rms(x, lp["mlp_norm"].astype(jnp.float32), eps)
    if "mlp" in lp:
        return x + _dense_ffn(lp["mlp"], u, quant)
    shared = _f32(lp["shared"])
    return x + routed_sum(lp, u, cfg, quant, layer=layer) \
        + _by_rows(lambda rows: _swiglu(rows, shared, quant), u)


def _block_for(seq: int, block: Optional[int]) -> int:
    """Queries a block: its scores are [heads, block, S] float32."""
    return block or (256 if seq <= 8192 else 64)


def reference_hidden(params: Dict[str, Any], tokens, cfg: Dict[str, Any],
                     quant: Optional[str] = None, block: Optional[int] = None):
    """tokens: [S] int32 -> final-norm hidden [S, h] float32, as ONE traced
    function (tests, small sizes)."""
    import jax
    import jax.numpy as jnp

    block = _block_for(tokens.shape[0], block)
    x = params["embed_tokens"][tokens].astype(jnp.float32)
    for lp in params["dense_layers"]:
        x = _layer(lp, x, cfg, quant, block)
    stacked = params["layers"]
    for i in range(jax.tree.leaves(stacked)[0].shape[0]):
        x = _layer(jax.tree.map(lambda a: a[i], stacked), x, cfg, quant, block)
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
    benchmark's sizes: a Python loop over the layers, a compiled program a
    layer KIND (the dense layer's; the expert layers' one, handed the stack
    of layers and an index), so that what is live is one layer's float32
    copies and a sequence of 25,088 fits beside the engine's 11.5 GB."""
    import jax
    import jax.numpy as jnp

    def programs(seq):
        blk = _block_for(seq, block)
        dense = jax.jit(lambda lp, x: _layer(lp, x, cfg, quant, blk))

        def expert(stacked, i, x):
            lp = {k: jax.tree.map(lambda a: a[i], v)
                  for k, v in stacked.items() if k != "experts"}
            lp["experts"] = stacked["experts"]
            return _layer(lp, x, cfg, quant, blk, layer=i)

        return dense, jax.jit(expert)

    final = jax.jit(lambda x, w: _rms(x, w.astype(jnp.float32),
                                      float(cfg["rms_norm_eps"])))
    made: Dict[int, Any] = {}

    def hidden(params, tokens):
        tokens = jnp.asarray(tokens)
        if tokens.shape[0] not in made:
            made[tokens.shape[0]] = programs(tokens.shape[0])
        dense, expert = made[tokens.shape[0]]
        x = params["embed_tokens"][tokens].astype(jnp.float32)
        for lp in params["dense_layers"]:
            x = dense(lp, x)
        stacked = params["layers"]
        for i in range(jax.tree.leaves(stacked)[0].shape[0]):
            x = expert(stacked, jnp.int32(i), x)
        return final(x, params["final_norm"])

    return hidden


def make_gap_fn(cfg, quant=None):
    """(params, tokens[length], chosen[length]) -> per position the
    reference's largest logit minus its logit of ``chosen``
    (``harness/reference.py`` ``gap_fn_of``), the head a row block at a time:
    the logits of 25,088 rows are 2 GB and never exist."""
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
