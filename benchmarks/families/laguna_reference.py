"""The plain reference of the Laguna family: the decoder of the
configuration's source (``model_type`` laguna, poolside/Laguna-XS.2) in
straightforward ``jax.numpy`` and float32 (``highest``), one sequence at a
time, no kernels, no cache, no rings, no batching. Written from the published
configuration's equations, not from ``ray_tpu``, of which it imports nothing.
It takes the weights the BENCHMARK made from the seed and upcasts them;
nothing the program computed enters.

Every layer ``l`` is ``h = x + Attn_l(rms(x)); y = h + MLP_l(rms(h))``:

- ``Attn_l``: ``n_q = num_attention_heads_per_layer[l]`` query heads over
  ``num_key_value_heads`` KV heads of ``head_dim``; q and k rotated by the
  scheme of ``layer_types[l]``; ``softmax(q k / sqrt(head_dim))`` over the
  FULL mask ``j <= i`` and, on ``sliding_attention`` layers, ``i - j <
  sliding_window``; each head's output times ``sigmoid(u W_g)_h``; ``W_o``.
- rotary, half-rotation layout, by ``rope_parameters[layer type]``: over the
  first ``partial_rotary_factor x head_dim`` dimensions, the rest pass
  through. ``rope_type: yarn``: ``f_i = theta^(-2i / d_r)``;
  ``inv_freq_i = f_i / factor x (1 - m_i) + f_i x m_i`` with
  ``m_i = 1 - clip((i - low) / (high - low), 0, 1)`` and ``low``, ``high``
  the floor and ceiling of ``d_r ln(original / (2 pi beta)) / (2 ln theta)``
  at ``beta_fast`` and ``beta_slow``; cos and sin times ``attention_factor``.
- ``MLP_l`` dense: ``(silu(u W_gate) * u W_up) W_down``. Sparse:
  ``p = softmax(u W_r)`` over all the router's outputs; the top
  ``num_experts_per_tok`` by ``p``, weights ``p`` over the sum of the chosen,
  times ``moe_routed_scaling_factor``, on the experts' OUTPUTS; plus one
  shared expert of the same form, added ungated.

Departures from the published description, each of which the configuration
file lists under ``assumed``, ``reduced`` or ``share``:

- the gate is PER HEAD (``gating: true`` does not say; the sibling
  Laguna-S-2.1 says ``per-head``); the router's scoring is a SOFTMAX and the
  chosen weights are normalised (the config names neither; its MoE keys are
  the Qwen-MoE lineage's); ``silu``; no q/k norm.
- the share: ``held_experts = [lo, hi]`` of the ``n_router_outputs`` experts
  are held; the router scores and normalises over ALL of them, the sum is
  over the held ones that were chosen, and what the others would add is
  dropped. The vocabulary is the ``vocab_size`` rows held.

``quant`` rounds the inputs of every product with learned weights, and of the
attention products, to a lower precision (``harness/reference.py``): the
CONTROL (``fp8``), or ``bf16`` for tests. The router's scores stay float32.
Experts are upcast and multiplied one at a time, attention in query blocks,
the dense MLP and the head in row blocks, so that a sequence of 25,600 fits
beside the engine.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional

from benchmarks.harness.reference import (
    gap_fn_of, greedy_fn_of, mm as _mm, round_to as _round_to)

ROW_BLOCK = 2048


def _rms(x, w, eps):
    import jax
    import jax.numpy as jnp

    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _f32(tree):
    import jax
    import jax.numpy as jnp

    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _by_rows(fn, x, block: int = ROW_BLOCK):
    """``fn`` over the rows of ``x`` [S, ...], ``block`` at a time."""
    import jax

    s = x.shape[0]
    while s % block:
        block //= 2
    if s <= block:
        return fn(x)
    out = jax.lax.map(fn, x.reshape(s // block, block, *x.shape[1:]))
    return out.reshape(s, *out.shape[2:])


def rope_table(rp: Dict[str, Any], head_dim: int, seq: int):
    """cos, sin [seq, d_r / 2] of one ``rope_parameters`` block, the
    attention factor folded in."""
    import jax.numpy as jnp

    d_r = int(head_dim * rp.get("partial_rotary_factor", 1))
    theta = float(rp["rope_theta"])
    i = jnp.arange(d_r // 2, dtype=jnp.float32)
    f = theta ** (-2.0 * i / d_r)
    factor = 1.0
    if rp.get("rope_type", "default") == "yarn":
        original = rp["original_max_position_embeddings"]

        def dim_of(beta):
            return d_r * math.log(original / (beta * 2 * math.pi)) \
                / (2 * math.log(theta))

        low = max(math.floor(dim_of(rp["beta_fast"])), 0)
        high = min(math.ceil(dim_of(rp["beta_slow"])), d_r - 1)
        m = 1.0 - jnp.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
        f = f / rp["factor"] * (1.0 - m) + f * m
        factor = rp["attention_factor"]
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * f[None, :]
    return jnp.cos(angles) * factor, jnp.sin(angles) * factor


def _rotate(x, cos, sin):
    """x: [S, heads, D]; the first 2 x cos.shape[1] dimensions turn."""
    import jax.numpy as jnp

    half = cos.shape[1]
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, rest], axis=-1)


def _attention(lp, u, cfg, layer: int, quant, block: int):
    """u: [S, h] normed; full masks, in query blocks of ``block``."""
    import jax
    import jax.numpy as jnp

    kind = cfg["layer_types"][layer]
    nq, nkv, d = (cfg["num_attention_heads_per_layer"][layer],
                  cfg["num_key_value_heads"], cfg["head_dim"])
    s, rep = u.shape[0], nq // nkv
    cos, sin = rope_table(cfg["rope_parameters"][kind], d, s)
    q = _rotate(_mm(u, lp["wq"], quant).reshape(s, nq, d), cos, sin)
    q = q.reshape(s, nkv, rep, d)
    k = _round_to(_rotate(_mm(u, lp["wk"], quant).reshape(s, nkv, d), cos, sin),
                  quant)
    v = _round_to(_mm(u, lp["wv"], quant).reshape(s, nkv, d), quant)
    gate = jax.nn.sigmoid(_mm(u, lp["wg"], quant))                 # [S, nq]
    window = cfg["sliding_window"] if kind == "sliding_attention" else None
    block = min(block, s)
    while s % block:
        block //= 2
    cols = jnp.arange(s)

    def one(args):
        qblk, start = args
        scores = jnp.einsum("qnrd,snd->nrqs", _round_to(qblk, quant), k,
                            precision="highest") * (d ** -0.5)
        rows = start + jnp.arange(block)
        seen = cols[None, :] <= rows[:, None]
        if window is not None:
            seen = seen & (rows[:, None] - cols[None, :] < window)
        probs = jax.nn.softmax(jnp.where(seen[None, None], scores, -1e30),
                               axis=-1)
        return jnp.einsum("nrqs,snd->qnrd", _round_to(probs, quant), v,
                          precision="highest")

    out = jax.lax.map(one, (q.reshape(s // block, block, nkv, rep, d),
                            jnp.arange(0, s, block)))
    out = out.reshape(s, nq, d) * gate[:, :, None]
    return _mm(out.reshape(s, nq * d), lp["wo"], quant)


def _swiglu(u, w, quant):
    import jax

    return _mm(jax.nn.silu(_mm(u, w["w_gate"], quant)) * _mm(u, w["w_up"], quant),
               w["w_down"], quant)


def routing(lp, u, cfg):
    """u: [S, h] -> (chosen experts [S, k], their weights [S, k])."""
    import jax
    import jax.numpy as jnp

    p = jax.nn.softmax(jnp.matmul(u, lp["router"]["w"].astype(jnp.float32),
                                  precision="highest"), axis=-1)
    weights, chosen = jax.lax.top_k(p, cfg["num_experts_per_tok"])
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True) \
        * float(cfg["moe_routed_scaling_factor"])
    return chosen, weights


def routed_sum(lp, u, cfg, quant, held=None):
    """The weighted sum of the experts ``held = [lo, hi]`` (default: the
    configuration's) over the tokens routed to them; ``lp["experts"]`` holds
    exactly those, in the served dtype: one at a time."""
    import jax
    import jax.numpy as jnp

    lo, hi = held or cfg["held_experts"]
    chosen, weights = routing(lp, u, cfg)
    ids = jnp.arange(lo, hi)
    # [S, E_held]: the weight of each held expert for each token, 0 if unchosen
    per_expert = jnp.sum(jnp.where(chosen[:, :, None] == ids[None, None, :],
                                   weights[:, :, None], 0.0), axis=1)

    def one(acc, args):
        w, weight = args
        return acc + weight[:, None] * _swiglu(u, _f32(w), quant), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(u),
                             (lp["experts"], per_expert.T))
    return routed


def _mlp(lp, u, cfg, quant):
    if "mlp" in lp:
        w = _f32(lp["mlp"])
        return _by_rows(lambda rows: _swiglu(rows, w, quant), u)
    return routed_sum(lp, u, cfg, quant) + _swiglu(u, _f32(lp["shared"]), quant)


def reference_hidden(params: Dict[str, Any], tokens, cfg: Dict[str, Any],
                     quant: Optional[str] = None, block: Optional[int] = None):
    """tokens: [S] int32 -> final-norm hidden [S, h] float32."""
    import jax.numpy as jnp

    eps = float(cfg["rms_norm_eps"])
    if block is None:  # scores of a block are [heads, block, S] float32
        block = 256 if tokens.shape[0] <= 8192 else 64
    x = params["embed_tokens"][tokens].astype(jnp.float32)
    for layer, lp in enumerate(params["layers"]):
        attn = _f32({k: lp[k] for k in ("wq", "wk", "wv", "wg", "wo")})
        u = _rms(x, lp["attn_norm"].astype(jnp.float32), eps)
        x = x + _attention(attn, u, cfg, layer, quant, block)
        u = _rms(x, lp["mlp_norm"].astype(jnp.float32), eps)
        x = x + _mlp(lp, u, cfg, quant)
    return _rms(x, params["final_norm"].astype(jnp.float32), eps)


def reference_logits(params, tokens, cfg, quant=None, block=None):
    """tokens: [S] -> logits [S, V] float32 over the vocabulary held."""
    import jax.numpy as jnp

    head = params["lm_head"].astype(jnp.float32)
    return _by_rows(lambda rows: _mm(rows, head, quant),
                    reference_hidden(params, tokens, cfg, quant, block))


def _out_of_the_compile_cache(fn):
    """``fn`` (jitted), called with the persistent compilation cache off. The
    reference at the check's length of 25,600 compiles to 138 MB (26.6 MB as
    the cache compresses it; 26 s), more than the cell's five prefill
    programs together, and runs AFTER the window. The chip machine's cache
    holds 192 MiB (``JAX_COMPILATION_CACHE_MAX_SIZE``) and forgets what was
    used longest ago; with the reference in it, what the benchmark's cells
    write in one round no longer fit, and a run of this cell found its
    prefill programs gone: ``setup_s`` 153 s where the run before read 79
    (PERF.md 6, PR 35). So the reference is compiled anew in every run, which
    costs the run its 26 s after the window and no metric anything."""

    def call(*args):
        import jax
        from jax.experimental.compilation_cache import compilation_cache

        was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            return fn(*args)
        finally:
            jax.config.update("jax_enable_compilation_cache", was_on)
            compilation_cache.reset_cache()

    return call


def make_gap_fn(cfg, quant=None):
    return _out_of_the_compile_cache(
        gap_fn_of(lambda p, t: reference_logits(p, t, cfg, quant)))


def make_greedy_fn(cfg, quant=None):
    return _out_of_the_compile_cache(
        greedy_fn_of(lambda p, t: reference_logits(p, t, cfg, quant)))
