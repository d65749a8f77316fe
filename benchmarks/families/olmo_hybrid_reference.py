"""The plain reference of the Olmo-Hybrid family on the TRAINING path: the
decoder of the configuration's source (``model_type`` olmo_hybrid,
allenai/Olmo-Hybrid-7B) and its loss in straightforward ``jax.numpy`` and
float32 (``highest``), one sequence at a time, no kernels, no chunk algebra,
no cache. Written from the published configuration's equations, not from
``ray_tpu``, of which it imports nothing. It takes the weights the BENCHMARK
made from the seed and upcasts them; its gradient is ``jax.grad`` of it.

Every layer ``l`` is ``h = x + rms(Mixer_l(x)); y = h + rms'(MLP(h))`` (the
norm on the branch's output), ``MLP(u) = (silu(u W_gate) * u W_up) W_down``;
then a final RMSNorm, logits ``y W_head`` over the vocabulary held, and the
mean cross-entropy of the next token.

- ``linear_attention``: with ``c = conv(x W_qkv)`` (causal, depthwise,
  ``linear_conv_kernel_dim`` taps: that many shifted copies, each times its
  tap, added) and ``s = silu(c)``, split into q, k (``H`` heads of
  ``linear_key_head_dim``) and v (``H`` of ``linear_value_head_dim``):
  ``q = l2norm(q) / sqrt(d_k)``, ``k = l2norm(k)`` a head; ``g = -exp(A_log)
  softplus(x W_a + dt_bias)``, ``beta = sigmoid(x W_b)`` (x 2 where
  ``linear_allow_neg_eigval``), both float32; then A TOKEN A STEP, a head,
  ``S <- exp(g_t) S; S <- S + beta_t (v_t - S k_t) k_t^T; o_t = S q_t`` from
  ``S = 0`` ([d_v, d_k]); ``y = W_o (rms_head(o) * silu(x W_g))``.
- ``full_attention``: ``q = rms(x W_q)``, ``k = rms(x W_k)`` over the whole
  width (its statistic first, then the heads five at a time),
  ``num_attention_heads`` heads of ``hidden / heads`` each for q, k and v
  alike, ``softmax(q k / sqrt(d))`` over ``j <= i``, ``W_o``; nothing is
  rotated.

Departures, each listed in the configuration file under ``assumed`` or
``reduced``: no positional encoding on the full layers (``rope_theta`` null);
the norms' places (the Olmo family's published block); ``l2norm`` as ``x /
sqrt(sum x^2 + 1e-6)``; the vocabulary is the ``vocab_size`` rows held.

Kept small for the compiler and the memory, none of which changes the
mathematics: each layer under ``jax.checkpoint``, and inside it the pieces
that would hold a [32768, 11008] float32 array each (the MLP and the head in
row blocks, a linear layer's heads five at a time from their projections
through the recurrence, attention in blocks of queries) and the recurrence as
64 tokens inside, rematerialised, of a scan outside (a reverse pass keeps a
state a 64 tokens and 64 inside, not one a token).

``quant`` rounds the inputs of every product with learned weights, and q, k
and v of both mixers, to a lower precision (``harness/reference.py``): the
CONTROL (``fp8``), or ``bf16`` for tests. The decay's and ``beta``'s narrow
projection stays float32, as the program keeps it.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from benchmarks.harness.reference import (
    gap_fn_of, greedy_fn_of, mm as _mm, round_to as _round_to)

LINEAR = "linear_attention"
INNER = 64


def _rms(x, w, eps):
    import jax
    import jax.numpy as jnp

    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _f32(tree):
    import jax
    import jax.numpy as jnp

    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _in_row_blocks(fn, block: int, *arrays):
    """``fn(*blocks)`` over arrays [S, ...] in blocks of rows, each
    rematerialised."""
    import jax

    s = arrays[0].shape[0]
    block = min(block, s)
    while s % block:
        block //= 2
    out = jax.lax.map(
        jax.checkpoint(lambda blocks: fn(*blocks)),
        tuple(a.reshape(s // block, block, *a.shape[1:]) for a in arrays))
    return out.reshape(s, *out.shape[2:])


def short_conv(x, taps):
    """y_t = sum_j taps[j] x_{t - (n - 1) + j}, zeros before the start: ``n``
    shifted copies added. x: [S, C]; taps: [n, C]."""
    import jax.numpy as jnp

    n, s = taps.shape[0], x.shape[0]
    out = jnp.zeros_like(x)
    for j in range(n):
        back = n - 1 - j
        shifted = jnp.concatenate(
            [jnp.zeros((back, x.shape[1]), x.dtype), x[:s - back]], axis=0)
        out = out + shifted * taps[j]
    return out


def delta_rule(q, k, v, g, beta):
    """The gated delta rule a token a step. q, k: [S, H, d_k]; v: [S, H,
    d_v]; g, beta: [S, H] -> o [S, H, d_v]."""
    import jax
    import jax.numpy as jnp

    s, h, dk = q.shape
    dv = v.shape[-1]
    pad = -s % INNER
    if pad:  # no decay and nothing written there
        q, k, v, g, beta = (jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1))
                            for t in (q, k, v, g, beta))

    def token(state, part):
        qt, kt, vt, gt, bt = part
        state = jnp.exp(gt)[:, None, None] * state
        seen = jnp.einsum("hvk,hk->hv", state, kt, precision="highest")
        state = state + (bt[:, None] * (vt - seen))[:, :, None] * kt[:, None, :]
        return state, jnp.einsum("hvk,hk->hv", state, qt, precision="highest")

    @jax.checkpoint
    def inner(state, parts):
        return jax.lax.scan(token, state, parts)

    parts = tuple(t.reshape((s + pad) // INNER, INNER, *t.shape[1:])
                  for t in (q, k, v, g, beta))
    _, o = jax.lax.scan(inner, jnp.zeros((h, dv, dk), jnp.float32), parts)
    return o.reshape(s + pad, h, dv)[:s]


def _heads_at_a_time(h: int) -> int:
    """The largest divisor of ``h`` that is at most 5."""
    return max(n for n in range(1, 6) if h % n == 0)


def _linear_mixer(lp, x, cfg, quant, block):
    import jax
    import jax.numpy as jnp

    s, width = x.shape
    h, dk, dv = (cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
                 cfg["linear_value_head_dim"])
    eps = float(cfg["rms_norm_eps"])
    double = 2.0 if cfg.get("linear_allow_neg_eigval", False) else 1.0
    # a few heads at a time, each group rematerialised: the heads share
    # nothing but ``x``, and a reverse pass over all 30 at once keeps 1.4 GB
    # of states and as much again of q, k, v and their cotangents
    hg = _heads_at_a_time(h)
    groups = h // hg

    def columns(w, first, d):          # [..., first + H d] -> [G, ..., hg d]
        w = w[..., first:first + h * d]
        return jnp.moveaxis(w.reshape(*w.shape[:-1], groups, hg * d), -2, 0)

    def through_conv(w, taps, d, unit):
        c = jax.nn.silu(short_conv(_mm(x, w, quant), taps)).reshape(s, hg, d)
        if unit:
            c = c * jax.lax.rsqrt(jnp.sum(c * c, axis=-1, keepdims=True) + 1e-6)
        return _round_to(c, quant)

    @jax.checkpoint
    def one_group(part):
        q = through_conv(part["wq"], part["cq"], dk, True) * dk ** -0.5
        k = through_conv(part["wk"], part["ck"], dk, True)
        v = through_conv(part["wv"], part["cv"], dv, False)
        g = -jnp.exp(part["a_log"]) * jax.nn.softplus(
            jnp.matmul(x, part["wa"], precision="highest") + part["dt_bias"])
        beta = double * jax.nn.sigmoid(
            jnp.matmul(x, part["wb"], precision="highest"))
        return delta_rule(q, k, v, g, beta)

    o = jax.lax.map(one_group, dict(
        wq=columns(lp["w_qkv"], 0, dk), wk=columns(lp["w_qkv"], h * dk, dk),
        wv=columns(lp["w_qkv"], 2 * h * dk, dv),
        cq=columns(lp["conv"], 0, dk), ck=columns(lp["conv"], h * dk, dk),
        cv=columns(lp["conv"], 2 * h * dk, dv),
        wa=columns(lp["w_ab"], 0, 1), wb=columns(lp["w_ab"], h, 1),
        a_log=lp["a_log"].reshape(groups, hg),
        dt_bias=lp["dt_bias"].reshape(groups, hg)))
    o = jnp.moveaxis(o, 0, 1).reshape(s, h, dv)        # [G, S, hg, dv] ->

    def out(xr, orow):
        gate = jax.nn.silu(_mm(xr, lp["w_g"], quant)).reshape(-1, h, dv)
        gated = _rms(orow, lp["head_norm"], eps) * gate
        return _mm(gated.reshape(-1, h * dv), lp["w_o"], quant)

    return _in_row_blocks(out, block, x, o)


def _full_mixer(lp, x, cfg, quant, q_block, block):
    import jax
    import jax.numpy as jnp

    s, width = x.shape
    heads = cfg["num_attention_heads"]
    d = width // heads
    eps = float(cfg["rms_norm_eps"])
    hg = _heads_at_a_time(heads)
    groups = heads // hg

    def inverse_rms(w):
        """[S, 1]: the whole-width norm's statistic of ``x w``, in row blocks."""
        return _in_row_blocks(
            lambda rows: jax.lax.rsqrt(jnp.mean(
                jnp.square(_mm(rows, w, quant)), axis=-1, keepdims=True) + eps),
            block, x)

    r_q, r_k = inverse_rms(lp["wq"]), inverse_rms(lp["wk"])
    q_block = min(q_block, s)
    while s % q_block:
        q_block //= 2
    cols = jnp.arange(s)

    def columns(w):                          # [..., H d] -> [G, ..., hg d]
        return jnp.moveaxis(w.reshape(*w.shape[:-1], groups, hg * d), -2, 0)

    # a few heads at a time, each group rematerialised, as the linear mixer:
    # q, k, v of every head at once and their cotangents are six arrays of
    # the model's width
    @jax.checkpoint
    def one_group(part):
        q = (_mm(x, part["wq"], quant) * r_q * part["nq"]).reshape(s, hg, d)
        k = _round_to((_mm(x, part["wk"], quant) * r_k * part["nk"])
                      .reshape(s, hg, d), quant)
        v = _round_to(_mm(x, part["wv"], quant).reshape(s, hg, d), quant)

        @jax.checkpoint
        def one(args):
            qblk, start = args
            scores = jnp.einsum("qnd,snd->nqs", _round_to(qblk, quant), k,
                                precision="highest") * (d ** -0.5)
            rows = start + jnp.arange(q_block)
            seen = cols[None, :] <= rows[:, None]
            probs = jax.nn.softmax(jnp.where(seen[None], scores, -1e30),
                                   axis=-1)
            return jnp.einsum("nqs,snd->qnd", _round_to(probs, quant), v,
                              precision="highest")

        out = jax.lax.map(one, (q.reshape(s // q_block, q_block, hg, d),
                                jnp.arange(0, s, q_block)))
        return out.reshape(s, hg * d)

    out = jax.lax.map(one_group, dict(
        wq=columns(lp["wq"]), wk=columns(lp["wk"]), wv=columns(lp["wv"]),
        nq=columns(lp["q_norm"]), nk=columns(lp["k_norm"])))
    return _mm(jnp.moveaxis(out, 0, 1).reshape(s, width), lp["wo"], quant)


def layer_kinds(cfg: Dict[str, Any]):
    kinds, n = list(cfg["layer_types"]), cfg["num_hidden_layers"]
    return (kinds * (-(-n // len(kinds))))[:n]


def reference_hidden(params: Dict[str, Any], tokens, cfg: Dict[str, Any],
                     quant: Optional[str] = None, block: int = 4096):
    """tokens: [S] int32 -> final-norm hidden [S, h] float32."""
    import jax
    import jax.numpy as jnp

    eps = float(cfg["rms_norm_eps"])
    s = tokens.shape[0]
    # the scores of a block of queries of five heads: [5, block, S] float32,
    # 0.34 GB at most
    q_block = max(8, min(256, 2 ** 24 // s))
    x = params["embed_tokens"][tokens].astype(jnp.float32)

    def layer(kind):
        @jax.checkpoint
        def run(x, lp):
            lp = _f32(lp)
            if kind == LINEAR:
                mixed = _linear_mixer(lp, x, cfg, quant, block)
            else:
                mixed = _full_mixer(lp, x, cfg, quant, q_block, block)
            x = x + _rms(mixed, lp["mixer_norm"], eps)

            def mlp(u):
                return _mm(jax.nn.silu(_mm(u, lp["w_gate"], quant))
                           * _mm(u, lp["w_up"], quant), lp["w_down"], quant)
            return x + _rms(_in_row_blocks(mlp, block, x), lp["mlp_norm"], eps)
        return run

    met = {"linear": 0, "full": 0}
    for kind in layer_kinds(cfg):
        stack = "linear" if kind == LINEAR else "full"
        i = met[stack]
        x = layer(kind)(x, jax.tree.map(lambda a: a[i], params[stack]))
        met[stack] += 1
    return _rms(x, params["final_norm"].astype(jnp.float32), eps)


def reference_logits(params, tokens, cfg, quant=None):
    """tokens: [S] -> logits [S, V] float32 over the vocabulary held."""
    import jax.numpy as jnp

    return _mm(reference_hidden(params, tokens, cfg, quant),
               params["lm_head"].astype(jnp.float32), quant)


def not_for_the_compile_cache():
    """A host callback that does nothing: a program that holds one is not
    written to jax's persistent compilation cache. The reference and the
    control run once a run, after the window, and what they would write
    pushes an accepted cell's programs out of the chip machine's capped cache
    (``mellum_reference.py`` has the whole reason)."""
    import jax

    jax.debug.callback(lambda: None)


def reference_loss(params, tokens, targets, cfg, quant=None, block: int = 2048):
    """Mean next-token cross-entropy over rows; tokens/targets: [R, S]. The
    head in blocks of ``block`` rows: the logits of 32,768 rows over 25,088
    columns are 3.3 GB in float32."""
    import jax
    import jax.numpy as jnp

    not_for_the_compile_cache()
    head = params["lm_head"].astype(jnp.float32)

    def row(args):
        t, y = args
        x = reference_hidden(params, t, cfg, quant)

        def nll(rows, gold):
            logits = _mm(rows, head, quant)
            gold = jnp.take_along_axis(logits, gold[:, None], axis=-1)[:, 0]
            return jax.scipy.special.logsumexp(logits, axis=-1) - gold

        return jnp.mean(_in_row_blocks(nll, block, x, y))

    return jnp.mean(jax.lax.map(row, (tokens, targets)))


def make_gap_fn(cfg, quant=None):
    """``harness/reference.py`` ``gap_fn_of`` over this decoder."""
    return gap_fn_of(lambda p, t: reference_logits(p, t, cfg, quant))


def make_greedy_fn(cfg, quant=None):
    """``harness/reference.py`` ``greedy_fn_of`` over this decoder."""
    return greedy_fn_of(lambda p, t: reference_logits(p, t, cfg, quant))
