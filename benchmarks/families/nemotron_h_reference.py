"""The plain reference of the Nemotron-H family: the decoder of the
configuration's source in straightforward ``jax.numpy`` and float32
(``highest``), one sequence at a time, no kernels, no cache, no chunks, no
batching. Written from the published description of the ``nemotron_h`` block,
not from ``ray_tpu``, of which it imports nothing. It takes the weights the
BENCHMARK made from the seed and upcasts them; nothing the program computed
enters.

Every layer is ``x <- x + mixer(rms_norm(x))``, the mixer chosen by
``hybrid_override_pattern``:

- ``M`` Mamba-2: ``[z | xBC | dt] = x' W_in``; ``xBC = silu(conv1d_4(xBC) + b)``
  (causal, depthwise); ``X`` [H, P], ``B``, ``C`` [G, N], head h in group
  h // (H / G); ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``;
  ``S_t = exp(dt_t A) S_{t-1} + dt_t X_t (x) B_t``; ``Y_t = S_t C_t + D X_t``;
  ``Y = group_rms_norm(Y silu(z)) W_out``. The recurrence is a ``lax.scan``
  over TOKENS.
- ``E`` experts: ``s = sigmoid(x' W_r)`` over all the router's outputs; the
  ``num_experts_per_tok`` chosen are the top of ``s + e_score_correction_bias``;
  their weights are ``s`` over the sum of the chosen, times
  ``routed_scaling_factor``; an expert is ``relu(x' W_up)^2 W_down``, not
  gated; plus one shared expert of the same form.
- ``*`` attention: causal, grouped-query, scale 1 / sqrt(head_dim).

Departures from the published description, each of which the configuration
file lists under ``assumed`` or ``reduced``:

- NO rotary embedding in the attention layers: the ``nemotron_h`` reference
  implementation applies none (position is carried by the Mamba layers),
  though the config holds ``rope_theta`` and ``partial_rotary_factor``.
- the share: ``held_experts = [lo, hi]`` of the ``n_router_outputs`` experts
  are held; the router scores and normalises over ALL of them, the sum is over
  the held ones that were chosen, and what the others would add is dropped.
  The vocabulary is the ``vocab_size`` rows held.
- ``e_score_correction_bias`` is seeded small and nonzero (a checkpoint's is
  learned), so that the choice and the weights differ.
- the state ``S`` is float32 (the source keeps it in the model's dtype by
  default; a recurrence summed over thousands of steps is held in float32).

``quant`` rounds the inputs of every product with learned weights, and of the
attention products, to a lower precision (``harness/reference.py``): the
CONTROL (``fp8``), or ``bf16`` for tests. The router's scores stay float32,
as the source computes them. Experts are upcast and multiplied one at a time
(a layer's 64 in float32 are 2.5 GB), attention in query blocks.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from benchmarks.harness.reference import (
    gap_fn_of, greedy_fn_of, mm as _mm, round_to as _round_to)


def _rms(x, w, eps):
    import jax
    import jax.numpy as jnp

    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _f32(tree):
    import jax
    import jax.numpy as jnp

    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _mamba(lp, y, cfg, quant):
    """y: [S, h] normed -> the mixer's output [S, h]."""
    import jax
    import jax.numpy as jnp

    heads, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    n, g, taps = cfg["ssm_state_size"], cfg["n_groups"], cfg["conv_kernel"]
    inner, s = heads * p, y.shape[0]
    parts = _mm(y, lp["w_in"], quant)
    z, xbc, dt = (parts[:, :inner], parts[:, inner:inner + inner + 2 * g * n],
                  parts[:, inner + inner + 2 * g * n:])
    # causal depthwise convolution: tap k multiplies the input (taps-1-k) back
    padded = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
    xbc = sum(padded[k:k + s] * lp["conv_w"][k] for k in range(taps)) + lp["conv_b"]
    xbc = jax.nn.silu(xbc)
    x = xbc[:, :inner].reshape(s, heads, p)
    b = jnp.repeat(xbc[:, inner:inner + g * n].reshape(s, g, n), heads // g, axis=1)
    c = jnp.repeat(xbc[:, inner + g * n:].reshape(s, g, n), heads // g, axis=1)
    dt = jax.nn.softplus(dt + lp["dt_bias"])                       # [S, H]
    a = -jnp.exp(lp["a_log"])

    def token(state, args):
        x_t, b_t, c_t, dt_t = args
        state = state * jnp.exp(dt_t * a)[:, None, None] \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        y_t = jnp.sum(state * c_t[:, None, :], axis=-1) + lp["d"][:, None] * x_t
        return state, y_t

    _, out = jax.lax.scan(token, jnp.zeros((heads, p, n), jnp.float32),
                          (x, b, c, dt))
    gated = (out.reshape(s, inner) * jax.nn.silu(z)).reshape(s, g, inner // g)
    var = jnp.mean(gated * gated, axis=-1, keepdims=True)
    normed = (gated * jax.lax.rsqrt(var + float(cfg["norm_eps"]))).reshape(s, inner)
    return _mm(normed * lp["gate_norm"], lp["w_out"], quant)


def _relu2(y, w_up, w_down, quant):
    import jax.numpy as jnp

    up = jnp.maximum(_mm(y, w_up, quant), 0.0)
    return _mm(up * up, w_down, quant)


def routing(lp, y, cfg):
    """y: [S, h] -> (chosen experts [S, k], their weights [S, k])."""
    import jax
    import jax.numpy as jnp

    scores = jax.nn.sigmoid(jnp.matmul(y, lp["router"]["w"].astype(jnp.float32),
                                       precision="highest"))
    _, chosen = jax.lax.top_k(scores + lp["router"]["bias"],
                              cfg["num_experts_per_tok"])
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True) \
        * float(cfg["routed_scaling_factor"])
    return chosen, weights


def _experts(lp, y, cfg, quant):
    """y: [S, h] normed -> held routed experts' weighted sum + shared expert.
    ``lp`` holds the routed experts in the served dtype: one at a time."""
    import jax
    import jax.numpy as jnp

    lo, hi = cfg["held_experts"]
    chosen, weights = routing(lp, y, cfg)
    ids = jnp.arange(lo, hi)
    # [S, E_held]: the weight of each held expert for each token, 0 if unchosen
    per_expert = jnp.sum(jnp.where(chosen[:, :, None] == ids[None, None, :],
                                   weights[:, :, None], 0.0), axis=1)

    def one(acc, args):
        w_up, w_down, w = args
        out = _relu2(y, w_up.astype(jnp.float32), w_down.astype(jnp.float32), quant)
        return acc + w[:, None] * out, None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(y), (
        lp["experts"]["w_up"], lp["experts"]["w_down"], per_expert.T))
    shared = _f32(lp["shared"])
    return routed + _relu2(y, shared["w_up"], shared["w_down"], quant)


def _attention(lp, y, cfg, quant, block: int):
    """y: [S, h]; causal GQA in query blocks of ``block``; no rotary."""
    import jax
    import jax.numpy as jnp

    nh, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    s, rep = y.shape[0], nh // nkv
    q = _mm(y, lp["wq"], quant).reshape(s, nkv, rep, d)
    k = _round_to(_mm(y, lp["wk"], quant).reshape(s, nkv, d), quant)
    v = _round_to(_mm(y, lp["wv"], quant).reshape(s, nkv, d), quant)
    block = min(block, s)
    while s % block:
        block //= 2
    cols = jnp.arange(s)

    def one(args):
        qblk, start = args
        scores = jnp.einsum("qnrd,snd->nrqs", _round_to(qblk, quant), k,
                            precision="highest") * (d ** -0.5)
        rows = start + jnp.arange(block)
        scores = jnp.where((cols[None, :] <= rows[:, None])[None, None],
                           scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("nrqs,snd->qnrd", _round_to(probs, quant), v,
                          precision="highest")

    out = jax.lax.map(one, (q.reshape(s // block, block, nkv, rep, d),
                            jnp.arange(0, s, block)))
    return _mm(out.reshape(s, nh * d), lp["wo"], quant)


def reference_hidden(params: Dict[str, Any], tokens, cfg: Dict[str, Any],
                     quant: Optional[str] = None, block: int = 256):
    """tokens: [S] int32 -> final-norm hidden [S, h] float32."""
    import jax.numpy as jnp

    eps = float(cfg["norm_eps"])
    x = params["embed_tokens"][tokens].astype(jnp.float32)
    for kind, lp in zip(cfg["hybrid_override_pattern"], params["layers"]):
        y = _rms(x, lp["norm"].astype(jnp.float32), eps)
        if kind == "M":
            x = x + _mamba(_f32(lp), y, cfg, quant)
        elif kind == "E":
            routed = {k: v for k, v in lp.items() if k != "experts"}
            x = x + _experts({**_f32(routed), "experts": lp["experts"]}, y,
                             cfg, quant)
        elif kind == "*":
            x = x + _attention(_f32(lp), y, cfg, quant, block)
        else:
            raise ValueError(f"unknown layer kind {kind!r}")
    return _rms(x, params["final_norm"].astype(jnp.float32), eps)


def reference_logits(params, tokens, cfg, quant=None, block: int = 256):
    """tokens: [S] -> logits [S, V] float32 over the vocabulary held."""
    import jax.numpy as jnp

    return _mm(reference_hidden(params, tokens, cfg, quant, block),
               params["lm_head"].astype(jnp.float32), quant)


def make_gap_fn(cfg, quant=None):
    return gap_fn_of(lambda p, t: reference_logits(p, t, cfg, quant))


def make_greedy_fn(cfg, quant=None):
    return greedy_fn_of(lambda p, t: reference_logits(p, t, cfg, quant))
