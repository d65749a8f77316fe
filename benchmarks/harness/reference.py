"""What every family's plain reference shares, and nothing of any family:
the lower precisions a CONTROL is computed in (``quant``), and the comparison
of the tokens the served path emitted with a reference's logits.

A family's reference (``families/<name>_reference.py``) is its decoder in
straightforward ``jax.numpy`` and float32, written from the source's
published description and not from the program; it does its matmuls through
``mm`` so that ``quant`` means the same step below the configuration's
precision in every family: ``fp8`` (e4m3, per-tensor scale) under bfloat16,
``bf16`` for tests that need a sound stand-in for the program.
"""

from __future__ import annotations

from typing import Optional


def round_to(x, quant: Optional[str]):
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


def mm(a, b, quant):
    import jax.numpy as jnp

    return jnp.matmul(round_to(a, quant), round_to(b, quant),
                      precision="highest")


def mean_cross_entropy(logits_fn, tokens, targets):
    """Mean next-token cross-entropy over rows; tokens/targets: [R, S];
    ``logits_fn``: tokens[S] -> logits [S, V], one row at a time."""
    import jax
    import jax.numpy as jnp

    def row(args):
        t, y = args
        logits = logits_fn(t)
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
        return jnp.mean(logz - gold)

    return jnp.mean(jax.lax.map(row, (tokens, targets)))


def gap_fn_of(logits_fn):
    """``logits_fn``: (params, tokens[length]) -> [length, V]. Returns jitted
    (params, tokens[length], chosen[length]) -> per position the reference's
    largest logit minus its logit of ``chosen`` (the token that was emitted
    after seeing tokens[: i + 1]); 0 where they agree."""
    import jax
    import jax.numpy as jnp

    def f(params, tokens, chosen):
        logits = logits_fn(params, tokens)
        picked = jnp.take_along_axis(logits, chosen[:, None], axis=-1)[:, 0]
        return jnp.max(logits, axis=-1) - picked

    return jax.jit(f)


def greedy_fn_of(logits_fn):
    """jitted (params, tokens[length], pos) -> argmax token after tokens[:pos].
    Full recompute per token: no cache, by design."""
    import jax
    import jax.numpy as jnp

    def f(params, tokens, pos):
        logits = logits_fn(params, tokens)
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
