"""The plain reference of the phi4flash family: the decoder of the
configuration's source (``model_type`` phi4flash,
microsoft/Phi-4-mini-flash-reasoning; SambaY, arXiv:2507.06607, with
differential attention) in straightforward ``jax.numpy`` and float32
(``highest``), one sequence at a time: the scan a ``lax.scan`` over time,
attention as masked softmaxes, EVERY layer over EVERY row, no kernels, no
cache, no rings, no packed heads, no skipped rows. Written from the
equations, not from ``ray_tpu``, of which it imports nothing. It takes the
weights the BENCHMARK made from the seed and upcasts them.

With ``L = num_hidden_layers`` and ``half = L // 2``, every layer ``l`` is
``h += Mix_l(LN(h)); h += (u * silu(g)) W2`` with ``[g, u] = LN'(h) W1``;
``LN`` has weight and bias. ``Mix_l``:

- ``l`` even, ``l <= half``: Mamba-1. ``[x, z] = y W_in``; ``x = silu(conv(x)
  + b)`` (causal, depthwise, ``mamba_d_conv`` taps); ``[dt', B, C] = x W_x``;
  ``dt = softplus(dt' W_dt + b_dt)``; ``S_t = exp(dt_t (x) A) S_{t-1} + (dt_t
  x_t) (x) B_t`` with ``A = -exp(A_log)``; ``s_t = S_t C_t + D x_t``; out
  ``(s * silu(z)) W_out``. Layer ``half`` hands ``m = s`` on.
- ``l`` odd, ``l < half``: differential attention over keys ``t - window + 1
  .. t``; ``l = half + 1`` the same over all keys ``<= t``. ``[q, k, v] = y
  W_qkv + b``; query heads pair as ``(2j, 2j + 1)`` into ``q1_j, q2_j``, KV
  heads as ``(2m, 2m + 1)`` into ``k1_m, k2_m`` and ``v1_m, v2_m``; query
  pair ``j`` uses KV pair ``j // (query pairs / KV pairs)``. ``a_i =
  softmax(q_i k_i^T / sqrt(D)) [v1 | v2]``; ``lam = exp(lq1 . lk1) - exp(lq2
  . lk2) + lam0``, ``lam0 = 0.8 - 0.6 exp(-0.3 l)``; out ``RMSNorm(a_1 - lam
  a_2) (1 - lam0)`` over the pair's ``2 D``, pairs side by side, ``W_o + b``.
- ``l`` even, ``l >= half + 2``: ``(m * silu(y W_1)) W_2``, ``m`` of the same
  token.
- ``l`` odd, ``l >= half + 3``: the same differential attention with ``q = y
  W_q + b`` against layer ``half + 1``'s ``k`` and ``v``, all keys ``<= t``.

Then a final LayerNorm and ``h E^T`` (the embedding, tied).

Departures from the published description, each under ``assumed`` in the
configuration file: the head pairing, ``m`` before the gate, the biases,
``lam0`` by layer index, the Mamba sizes of the configuration class.

``quant`` rounds the inputs of every product with learned weights, and of the
attention products, to a lower precision (``harness/reference.py``): the
CONTROL (``fp8``), or ``bf16`` for tests. The scan itself stays float32.
A layer KIND is one traced function, called in a loop: the program holds four
layer bodies, not 32 unrolled layers at ``highest``. Attention runs in query
blocks, the MLP and the head in row blocks, so a sequence of 16,896 fits
beside the engine.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional

from benchmarks.families.laguna_reference import (
    _f32, _out_of_the_compile_cache)
from benchmarks.harness.reference import mm as _mm, round_to as _round_to

ROW_BLOCK = 2048


def kind_of(layer: int, cfg: Dict[str, Any]) -> str:
    half = cfg["num_hidden_layers"] // 2
    if layer <= half:
        return "window" if layer % 2 else "mamba"
    if layer == half + 1:
        return "full"
    return "cross" if layer % 2 else "gmu"


def sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    """The sizes the configuration class defaults give where the published
    config.json is silent."""
    h = cfg["hidden_size"]
    return {"head_dim": h // cfg["num_attention_heads"],
            "d_inner": cfg.get("mamba_expand", 2) * h,
            "d_state": cfg.get("mamba_d_state", 16),
            "d_conv": cfg.get("mamba_d_conv", 4),
            "dt_rank": cfg.get("mamba_dt_rank") or math.ceil(h / 16)}


def _ln(x, p, eps):
    import jax
    import jax.numpy as jnp

    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["w"] + p["b"]


def _by_rows(fn, *xs, block: int = ROW_BLOCK):
    """``fn(*parts)`` over the rows of ``xs`` (arrays [S, ...] with the same
    S), ``block`` rows at a time."""
    import jax

    s = xs[0].shape[0]
    while s % block:
        block //= 2
    if s <= block:
        return fn(*xs)
    out = jax.lax.map(lambda parts: fn(*parts), tuple(
        x.reshape(s // block, block, *x.shape[1:]) for x in xs))
    return out.reshape(s, *out.shape[2:])


def mamba(mp, y, cfg, quant):
    """y: [S, h] normed -> (the layer's output [S, h], s [S, d_inner])."""
    import jax
    import jax.numpy as jnp

    sz = sizes(cfg)
    din, n, r, taps = sz["d_inner"], sz["d_state"], sz["dt_rank"], sz["d_conv"]
    s_len = y.shape[0]
    xz = _by_rows(lambda rows: _mm(rows, mp["w_in"], quant), y)
    x, z = xz[:, :din], xz[:, din:]
    padded = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    x = sum(padded[j:j + s_len] * mp["conv_w"][j] for j in range(taps))
    x = jax.nn.silu(x + mp["conv_b"])
    parts = _mm(x, mp["w_x"], quant)
    dt = jax.nn.softplus(_mm(parts[:, :r], mp["w_dt"], quant) + mp["b_dt"])
    b, c = parts[:, r:r + n], parts[:, r + n:]
    a = -jnp.exp(mp["a_log"])                                     # [Din, N]

    def step(state, part):
        xt, dtt, bt, ct = part
        state = jnp.exp(dtt[:, None] * a) * state \
            + (dtt * xt)[:, None] * bt[None, :]
        return state, jnp.sum(state * ct[None, :], axis=1) + mp["d"] * xt

    _, s = jax.lax.scan(step, jnp.zeros((din, n), jnp.float32), (x, dt, b, c))
    out = _by_rows(lambda rows: _mm(rows, mp["w_out"], quant),
                   s * jax.nn.silu(z))
    return out, s


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def diff_attention(mp, q, k, v, lam0, window, quant, block: int):
    """q: [S, n_q, D]; k, v: [S, n_kv, D]; lam0: the layer's scalar -> the
    layer's output [S, h]."""
    import jax
    import jax.numpy as jnp

    s, nq, d = q.shape
    nkv = k.shape[1]
    rep = (nq // 2) // (nkv // 2)  # query pairs a KV pair
    lam = jnp.exp(jnp.sum(mp["lq1"] * mp["lk1"])) \
        - jnp.exp(jnp.sum(mp["lq2"] * mp["lk2"])) + lam0
    # [S, KV pairs, which of the pair, D]
    kp = _round_to(k, quant).reshape(s, nkv // 2, 2, d)
    vp = _round_to(v, quant).reshape(s, nkv // 2, 2, d)
    both_v = jnp.concatenate([vp[:, :, 0], vp[:, :, 1]], axis=-1)  # [v1 | v2]
    block = min(block, s)
    while s % block:
        block //= 2
    cols = jnp.arange(s)

    def one(args):
        qblk, start = args                      # [block, n_q, D]
        qp = _round_to(qblk, quant).reshape(block, nkv // 2, rep, 2, d)
        scores = jnp.einsum("qmrid,smid->mriqs", qp, kp,
                            precision="highest") * (d ** -0.5)
        rows = start + jnp.arange(block)
        seen = cols[None, :] <= rows[:, None]
        if window is not None:
            seen = seen & (rows[:, None] - cols[None, :] < window)
        probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
        a = jnp.einsum("mriqs,smd->qmrid", _round_to(probs, quant), both_v,
                       precision="highest")     # [block, m, rep, 2, 2 D]
        diff = a[:, :, :, 0] - lam * a[:, :, :, 1]
        var = jnp.mean(diff * diff, axis=-1, keepdims=True)
        normed = diff * jax.lax.rsqrt(var + 1e-5) * mp["subln"]
        return (normed * (1.0 - lam0)).reshape(block, nq * d)

    out = jax.lax.map(one, (q.reshape(s // block, block, nq, d),
                            jnp.arange(0, s, block)))
    return _mm(out.reshape(s, nq * d), mp["w_o"], quant) + mp["b_o"]


def _mlp(lp, y, quant):
    import jax
    import jax.numpy as jnp

    def rows(part):
        g, u = jnp.split(_mm(part, lp["w1"], quant), 2, axis=-1)
        return _mm(u * jax.nn.silu(g), lp["w2"], quant)

    return _by_rows(rows, y)


def layer_fn(kind: str, cfg: Dict[str, Any], quant, block: int):
    """One jitted function a layer KIND: (lp in the served dtype, x [S, h],
    m [S, d_inner], k, v [S, n_kv, D] of the ``full`` layer, lam0) ->
    (x, m, k, v). ``lam0`` is an argument, so that the attention layers of a
    kind are ONE program."""
    import jax

    eps = float(cfg["layer_norm_eps"])
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = sizes(cfg)["head_dim"]

    def body(lp, x, m, k, v, lam0):
        lp = _f32(lp)
        mp = lp["mixer"]
        y = _ln(x, lp["norm1"], eps)
        s = x.shape[0]
        if kind == "mamba":
            out, m = mamba(mp, y, cfg, quant)
        elif kind == "gmu":
            out = _by_rows(lambda rows: _mm(rows, mp["w2"], quant),
                           m * jax.nn.silu(_mm(y, mp["w1"], quant)))
        else:
            if kind == "cross":
                q = (_mm(y, mp["w_q"], quant) + mp["b_q"]).reshape(s, nq, d)
                kk, vv, window = k, v, None
            else:
                qkv = _mm(y, mp["w_qkv"], quant) + mp["b_qkv"]
                q = qkv[:, :nq * d].reshape(s, nq, d)
                kk = qkv[:, nq * d:(nq + nkv) * d].reshape(s, nkv, d)
                vv = qkv[:, (nq + nkv) * d:].reshape(s, nkv, d)
                window = cfg["sliding_window"] if kind == "window" else None
                if kind == "full":
                    k, v = kk, vv
            out = diff_attention(mp, q, kk, vv, lam0, window, quant, block)
        x = x + out
        x = x + _mlp(lp, _ln(x, lp["norm2"], eps), quant)
        return x, m, k, v

    return jax.jit(body)


def hidden_fn(cfg: Dict[str, Any], quant: Optional[str] = None,
              block: Optional[int] = None):
    """(params, tokens [S] int32) -> final-norm hidden [S, h] float32. A
    Python loop over the layers, a compiled program a layer kind."""
    import jax
    import jax.numpy as jnp

    sz = sizes(cfg)
    fns: Dict[str, Any] = {}
    eps = float(cfg["layer_norm_eps"])
    final = jax.jit(lambda x, p: _ln(x, _f32(p), eps))

    def hidden(params, tokens):
        s = tokens.shape[0]
        # scores of a block are [heads, block, S] float32
        blk = block or (256 if s <= 8192 else 64)
        x = params["embed_tokens"][jnp.asarray(tokens)].astype(jnp.float32)
        m = jnp.zeros((s, sz["d_inner"]), jnp.float32)
        k = v = jnp.zeros((s, cfg["num_key_value_heads"], sz["head_dim"]),
                          jnp.float32)
        for layer, lp in enumerate(params["layers"]):
            kind = kind_of(layer, cfg)
            if (kind, blk) not in fns:
                fns[kind, blk] = layer_fn(kind, cfg, quant, blk)
            x, m, k, v = fns[kind, blk](lp, x, m, k, v,
                                        jnp.float32(lambda_init(layer)))
        return final(x, params["final_norm"])

    return hidden


def _head_rows(fn, embed, quant, h, *xs):
    """``fn(logits, *parts)`` over blocks of 512 rows of ``h @ embed^T``: the
    logits of 16,896 rows over 200,064 words are 13.5 GB and never exist."""
    import jax.numpy as jnp

    head = embed.astype(jnp.float32).T
    return _by_rows(lambda rows, *parts: fn(_mm(rows, head, quant), *parts),
                    h, *xs, block=512)


def reference_logits(params, tokens, cfg, quant=None, block=None):
    """tokens: [S] -> logits [S, V] float32 (the tied embedding as head).
    For tests: at the published vocabulary use ``make_gap_fn``."""
    import jax

    h = hidden_fn(cfg, quant, block)(params, tokens)
    return jax.jit(lambda h, e: _head_rows(lambda lg: lg, e, quant, h))(
        h, params["embed_tokens"])


def make_gap_fn(cfg, quant=None):
    """(params, tokens[length], chosen[length]) -> per position the
    reference's largest logit minus its logit of ``chosen``
    (``harness/reference.py`` ``gap_fn_of``, the head a row block at a
    time)."""
    import jax
    import jax.numpy as jnp

    hidden = hidden_fn(cfg, quant)

    def gap(logits, chosen):
        picked = jnp.take_along_axis(logits, chosen[:, None], axis=-1)[:, 0]
        return jnp.max(logits, axis=-1) - picked

    gaps = jax.jit(lambda h, e, chosen: _head_rows(gap, e, quant, h, chosen))
    return _out_of_the_compile_cache(
        lambda params, tokens, chosen: gaps(
            hidden(params, tokens), params["embed_tokens"],
            jnp.asarray(chosen)))


def make_greedy_fn(cfg, quant=None):
    """(params, tokens[length], pos) -> argmax token after tokens[:pos]. Full
    recompute per token: no cache, by design."""
    import jax
    import jax.numpy as jnp

    hidden = hidden_fn(cfg, quant)
    pick = jax.jit(lambda h, e, pos: jnp.argmax(_mm(
        jax.lax.dynamic_slice_in_dim(h, pos - 1, 1), e.astype(jnp.float32).T,
        quant)[0]).astype(jnp.int32))
    return _out_of_the_compile_cache(
        lambda params, tokens, pos: pick(
            hidden(params, tokens), params["embed_tokens"], pos))
