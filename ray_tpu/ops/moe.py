"""A dropless mixture-of-experts layer that is told which experts it holds.

``routed_experts`` scores every token against ALL ``n_router_outputs`` experts
of the published layer, takes the top ``top_k`` and normalises their weights
over all the chosen ones, as the whole layer would; it then computes the part
of the result that the experts HELD HERE (``held = (lo, hi)``, the rows of
``experts`` are experts ``lo .. hi - 1``) give, for every token routed to
them. There is no capacity and no token is dropped. What the absent experts
would add is left out and nothing stands in for them or for their exchange:
on one chip of an expert-parallel deployment this is the chip's partial sum.
With ``held = (0, n_router_outputs)`` it is the whole layer.

Two ways to do the products, chosen by the caller from its token count:

- ``"ragged"``: assignments sorted by expert, one grouped product a side
  (``lax.ragged_dot``, which XLA:TPU lowers to its grouped-matmul kernel and
  which costs what the assignments need), unsorted and summed. For prefill.
  On a small share the sorted assignments are COMPACTED first: the held ones
  sort first, so a block of ``_capacity`` rows (a static size from the
  call's shapes: the expected held count with ``COMPACT_SLACK`` to spare)
  is gathered, multiplied, weighted and added to its tokens, and not the
  ``T * top_k`` rows of which the share holds an eighth. Still dropless: a
  routing that leans on this share runs the same body over the next block
  until every held assignment is covered.
- ``"dense"``: every held expert over every token, the unchosen ones weighted
  zero. A decode batch of a hundred rows touches nearly every held expert
  anyway, so the weights are read once either way and the extra
  multiplications hide under that read. For decode.

An expert's form is the caller's (``form``): ``"relu2"``, not gated,
``relu(x W_up)^2 W_down`` (Nemotron-H), or ``"swiglu"``, gated by a third
matrix, ``(silu(x W_gate) * x W_up) W_down`` (Laguna). So is the router's
scoring (``scoring``): ``"sigmoid_bias"``, sigmoid scores, the choice by
score + a correction bias, the weights the scores without it (Nemotron-H);
or ``"softmax"``, a softmax over all the router's outputs in float32, the
choice and the weights both by it (Laguna, the Qwen-MoE lineage). Either way
the chosen weights are normalised over all the chosen and scaled.

``parallel/expert.py`` is the older capacity-based layer (it drops past a
capacity); only its tests and ``__graft_entry__.py`` use it.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

# rows of a tile of XLA:TPU's grouped product: ``ragged-dot-metadata`` plans
# ``rows / 512 + groups - 1`` tiles (read from the compiled program, v5e)
RAGGED_TILE = 512
# the compacted product's block over the EXPECTED held count, T * top_k *
# held / router outputs. A Laguna prefill chunk (32,768 assignments, 32 of
# 256 held, 4,096 +- 60 expected under a seeded router) took 2.26 ms a layer
# at 2 (8,192 rows) and 1.86 at 1.5 (6,144) where uncompacted took 5.05
# (PERF.md 6, PR 37). At 2 a share of a half runs uncompacted, as it did, and
# a share that draws twice its expectation of a chunk still takes one trip
COMPACT_SLACK = 2


def relu2_mlp(x, w_up, w_down):
    """The experts' form, for one dense expert: relu(x W_up)^2 W_down."""
    up = jnp.maximum(jnp.matmul(x, w_up, preferred_element_type=jnp.float32), 0.0)
    return jnp.matmul((up * up).astype(x.dtype), w_down)


def swiglu_mlp(x, w_gate, w_up, w_down):
    """The gated form, for one dense expert: (silu(x W_gate) * x W_up) W_down."""
    gate = jnp.matmul(x, w_gate, preferred_element_type=jnp.float32)
    up = jnp.matmul(x, w_up, preferred_element_type=jnp.float32)
    return jnp.matmul((jax.nn.silu(gate) * up).astype(x.dtype), w_down)


def route(x, router: Dict[str, Any], top_k: int, scale: float,
          scoring: str = "sigmoid_bias"):
    """x: [T, h]. Scores in float32 over all the router's outputs.
    ``"sigmoid_bias"``: sigmoid scores; the choice is the top ``top_k`` of
    score + ``bias`` (the published ``e_score_correction_bias``), the weights
    are the scores WITHOUT the bias. ``"softmax"``: a softmax over the
    outputs; choice and weights by it, no bias. The weights are over the sum
    of the chosen, times ``scale``. Returns (experts [T, k] int32, weights
    [T, k] float32)."""
    logits = jnp.matmul(x.astype(jnp.float32), router["w"].astype(jnp.float32),
                        precision="highest")
    if scoring == "softmax":
        # the top scores ARE the weights: reading them again by index cost a
        # 4,096-token chunk 0.33 ms a layer (PERF.md 6, PR 37)
        scores = jax.nn.softmax(logits, axis=-1)
        weights, chosen = jax.lax.top_k(scores, top_k)
    elif scoring == "sigmoid_bias":
        scores = jax.nn.sigmoid(logits)
        _, chosen = jax.lax.top_k(scores + router["bias"].astype(jnp.float32), top_k)
        weights = jnp.take_along_axis(scores, chosen, axis=-1)
    else:
        raise ValueError(f"unknown router scoring {scoring!r}")
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True) * scale
    return chosen.astype(jnp.int32), weights


def routed_experts(x, router: Dict[str, Any], experts: Dict[str, Any], *,
                   held: Tuple[int, int], top_k: int, scale: float,
                   impl: str = "ragged", counted=None,
                   scoring: str = "sigmoid_bias", form: str = "relu2"):
    """x: [T, h]; router: {"w": [h, R], "bias": [R]} (no bias under
    ``"softmax"``); experts: {"w_up": [E, h, f], "w_down": [E, f, h]} and,
    for ``"swiglu"``, "w_gate" like "w_up", with E = hi - lo. Returns the held
    experts' weighted sum [T, h], and with ``counted`` ([T] bool, the rows
    that are live requests) also int32 [4]: their routed choices, those that
    fell on held experts, held experts with at least one, and the fullest
    held expert's count; under ``"ragged"`` int32 [6], with the calls of the
    compacted product (1 or 0) and the blocks it ran beyond its first (0
    unless the share held more than ``_capacity`` of this call's choices)."""
    lo, hi = held
    n = hi - lo
    assert experts["w_up"].shape[0] == n, (experts["w_up"].shape, held)
    if form not in ("relu2", "swiglu"):
        raise ValueError(f"unknown expert form {form!r}")
    chosen, weights = route(x, router, top_k, scale, scoring)
    here = (chosen >= lo) & (chosen < hi)
    local = jnp.where(here, chosen - lo, n)          # n: "not held here"
    if impl == "dense":
        out = _dense(x, experts, local, weights, n, form)
    elif impl == "ragged":
        out, blocks = _ragged(x, experts, local, weights, n, form,
                              router["w"].shape[1])
    else:
        raise ValueError(f"unknown expert product {impl!r}")
    if counted is None:
        return out
    load = jnp.zeros((n + 1,), jnp.int32).at[local].add(
        counted[:, None].astype(jnp.int32))[:n]
    counts = jnp.stack([jnp.sum(counted) * top_k, jnp.sum(load),
                        jnp.sum(load > 0), jnp.max(load)]).astype(jnp.int32)
    if impl == "ragged":
        counts = jnp.concatenate([counts, blocks])
    return out, counts


def _act(up, gate, form: str):
    """The expert's activation in float32: ``up`` (and ``gate``) -> the rows
    the down projection takes."""
    if form == "swiglu":
        return jax.nn.silu(gate) * up
    up = jnp.maximum(up, 0.0)
    return up * up


def _dense(x, experts, local, weights, n: int, form: str):
    onehot = local[..., None] == jnp.arange(n)                  # [T, k, E]
    per_expert = jnp.sum(jnp.where(onehot, weights[..., None], 0.0), axis=1)
    up = jnp.einsum("th,ehf->etf", x, experts["w_up"],
                    preferred_element_type=jnp.float32)
    gate = jnp.einsum("th,ehf->etf", x, experts["w_gate"],
                      preferred_element_type=jnp.float32) \
        if form == "swiglu" else None
    down = jnp.einsum("etf,efh->eth", _act(up, gate, form).astype(x.dtype),
                      experts["w_down"], preferred_element_type=jnp.float32)
    return jnp.einsum("te,eth->th", per_expert, down).astype(x.dtype)


def _capacity(assignments: int, n: int, width: int) -> int:
    """Rows of the compacted product's block: what a share of ``n`` of the
    router's ``width`` outputs expects of ``assignments`` routed choices,
    times ``COMPACT_SLACK``, in whole tiles of the grouped product. Static:
    the call's shapes alone."""
    expected = assignments * n / width
    return math.ceil(expected * COMPACT_SLACK / RAGGED_TILE) * RAGGED_TILE


def _grouped(rows, experts, sizes, form: str):
    """rows sorted by expert, ``sizes`` rows each -> the experts' outputs in
    float32, unweighted. Rows past ``sum(sizes)`` belong to no group:
    whatever the grouped product left there is not to be read."""
    up = jax.lax.ragged_dot(
        rows, experts["w_up"], sizes, preferred_element_type=jnp.float32)
    gate = jax.lax.ragged_dot(
        rows, experts["w_gate"], sizes, preferred_element_type=jnp.float32) \
        if form == "swiglu" else None
    return jax.lax.ragged_dot(_act(up, gate, form).astype(rows.dtype),
                              experts["w_down"], sizes,
                              preferred_element_type=jnp.float32)


def _ragged(x, experts, local, weights, n: int, form: str, width: int):
    """-> (the weighted sum [T, h], int32 [2]: 1 and the blocks the
    compacted product ran beyond its first; zeros where the block would hold
    every assignment and the product runs over all of them at once)."""
    t, k = local.shape
    flat = local.reshape(-1)
    order = jnp.argsort(flat, stable=True)       # held first, by expert
    cap = _capacity(t * k, n, width)
    if cap >= t * k:
        sizes = jnp.bincount(flat, length=n + 1)[:n].astype(jnp.int32)
        down = _grouped(x[order // k], experts, sizes, form)     # [T*k, h]
        valid = (jnp.arange(t * k) < jnp.sum(sizes))[:, None]
        down = jnp.where(valid, down * weights.reshape(-1)[order][:, None], 0.0)
        back = jnp.zeros((t * k,), jnp.int32).at[order].set(
            jnp.arange(t * k, dtype=jnp.int32))
        return jnp.sum(down[back].reshape(t, k, -1), axis=1).astype(x.dtype), \
            jnp.zeros((2,), jnp.int32)
    # a count by comparison: bincount's scatter of 32,768 ones took 0.29 ms
    ends = jnp.cumsum(jnp.sum(flat[:, None] == jnp.arange(n), axis=0,
                              dtype=jnp.int32))
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends[:-1]])
    flat_weights = weights.reshape(-1)

    def block(carry):
        """Sorted rows [lo, lo + cap): each expert's group clipped to them."""
        b, acc = carry
        lo = b * cap
        at = lo + jnp.arange(cap, dtype=jnp.int32)
        which = order[jnp.minimum(at, t * k - 1)]
        token = which // k
        sizes = jnp.clip(ends, lo, lo + cap) - jnp.clip(starts, lo, lo + cap)
        down = _grouped(x[token], experts, sizes, form)          # [cap, h]
        down = down * flat_weights[which][:, None]
        # a segment sum by token; rows past the held ones go nowhere
        token = jnp.where(at < ends[-1], token, t)
        return b + 1, acc.at[token].add(down, mode="drop")

    blocks, out = jax.lax.while_loop(
        lambda carry: carry[0] * cap < ends[-1], block,
        (jnp.int32(0), jnp.zeros((t, x.shape[1]), jnp.float32)))
    return out.astype(x.dtype), jnp.stack([1, jnp.maximum(blocks - 1, 0)])
