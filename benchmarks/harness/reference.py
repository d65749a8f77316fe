"""The plain reference: the decoder of the configuration's source in
straightforward ``jax.numpy`` and float32, no kernels, no cache, no batching
tricks. Written from the published description of the Mistral / Llama block
(pre-norm RMSNorm, rotary embeddings in the half-rotation layout the program
also uses, grouped-query causal attention, SwiGLU MLP, untied head), not
from ``models/llama.py``. It takes the weights the BENCHMARK made from the
seed (``weights.py``) and upcasts them; nothing the program computed enters.

Departures from a textbook forward, none of which changes the mathematics:
layers are scanned (weights upcast one layer at a time, so 2 B parameters
never sit in float32 at once), attention is computed in query blocks (so an
S x S score matrix never exists), and both are wrapped in ``jax.checkpoint``
for the gradient.

``quant`` computes the same decoder with every matmul input rounded to a
lower precision: the CONTROL, the step below the configuration's bfloat16
that would tempt a later PR (``fp8``), or the configuration's own precision
(``bf16``) for tests.
"""

from __future__ import annotations

from typing import Any, Dict, Optional


def _round_to(x, quant: Optional[str]):
    """Round forward, pass the gradient straight through: the backward
    matmuls then see the rounded operands and unrounded cotangents, as a
    low-precision training recipe with higher-precision gradients does.
    (Rounding the cotangents to e4m3 as well underflows them to nothing.)"""
    import jax

    if quant is None:
        return x
    return x + jax.lax.stop_gradient(_rounded(x, quant) - x)


def _rounded(x, quant: str):
    import jax.numpy as jnp

    if quant == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if quant == "fp8":
        # per-tensor scale to e4m3's range, as an fp8 serving path would
        amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12)
        scale = 448.0 / amax
        return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale
    raise ValueError(f"unknown precision {quant!r}")


def _mm(a, b, quant):
    import jax.numpy as jnp

    return jnp.matmul(_round_to(a, quant), _round_to(b, quant),
                      precision="highest")


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
    import jax
    import jax.numpy as jnp

    def row(args):
        t, y = args
        logits = reference_logits(params, t, cfg, quant, block)
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
        return jnp.mean(logz - gold)

    return jnp.mean(jax.lax.map(row, (tokens, targets)))


def make_gap_fn(cfg, quant=None):
    """jitted (params, tokens[length], chosen[length]) -> per position the
    reference's largest logit minus its logit of ``chosen`` (the token that
    was emitted after seeing tokens[: i + 1]); 0 where they agree."""
    import jax
    import jax.numpy as jnp

    def f(params, tokens, chosen):
        logits = reference_logits(params, tokens, cfg, quant)
        picked = jnp.take_along_axis(logits, chosen[:, None], axis=-1)[:, 0]
        return jnp.max(logits, axis=-1) - picked

    return jax.jit(f)


def make_greedy_fn(cfg, quant=None):
    """jitted (params, tokens[length], pos) -> argmax token after tokens[:pos].
    Full recompute per token: no cache, by design."""
    import jax
    import jax.numpy as jnp

    def f(params, tokens, pos):
        logits = reference_logits(params, tokens, cfg, quant)
        return jnp.argmax(logits[pos - 1]).astype(jnp.int32)

    return jax.jit(f)


def teacher_forced_gaps(gap_fn, params, prompt, out_tokens, length: int):
    """Gaps of every emitted token of one request against the reference
    conditioned on the tokens actually emitted before it. Returns a list of
    len(out_tokens) floats."""
    import numpy as np

    n, m = len(prompt), len(out_tokens)
    if n + m > length + 1:
        raise ValueError(f"request of {n}+{m} tokens exceeds check length {length}")
    seq = np.zeros((length,), np.int32)
    full = list(prompt) + list(out_tokens)
    seq[: min(len(full), length)] = full[:length]
    chosen = np.zeros((length,), np.int32)
    # the token emitted after position i (0-based) is full[i + 1]
    chosen[n - 1: n - 1 + m] = out_tokens
    gaps = np.asarray(gap_fn(params, seq, chosen))
    return [float(g) for g in gaps[n - 1: n - 1 + m]]


def greedy_decode(greedy_fn, params, prompt, steps: int, length: int):
    """The reference (or the control) put in the program's place."""
    import numpy as np

    seq = np.zeros((length,), np.int32)
    seq[: len(prompt)] = prompt
    out = []
    pos = len(prompt)
    for _ in range(steps):
        tok = int(greedy_fn(params, seq, np.int32(pos)))
        out.append(tok)
        seq[pos] = tok
        pos += 1
    return out


def summarize_gaps(gaps):
    gaps = [float(g) for g in gaps]
    if not gaps:
        return {"decisions": 0, "mean_gap": float("inf"), "max_gap": float("inf"),
                "mismatch_share": 1.0}
    return {"decisions": len(gaps), "mean_gap": sum(gaps) / len(gaps),
            "max_gap": max(gaps),
            "mismatch_share": sum(g > 0 for g in gaps) / len(gaps)}
