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

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp


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
        scores = jax.nn.softmax(logits, axis=-1)
        _, chosen = jax.lax.top_k(scores, top_k)
    elif scoring == "sigmoid_bias":
        scores = jax.nn.sigmoid(logits)
        _, chosen = jax.lax.top_k(scores + router["bias"].astype(jnp.float32), top_k)
    else:
        raise ValueError(f"unknown router scoring {scoring!r}")
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
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
    held expert's count."""
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
        out = _ragged(x, experts, local, weights, n, form)
    else:
        raise ValueError(f"unknown expert product {impl!r}")
    if counted is None:
        return out
    load = jnp.zeros((n + 1,), jnp.int32).at[local].add(
        counted[:, None].astype(jnp.int32))[:n]
    counts = jnp.stack([jnp.sum(counted) * top_k, jnp.sum(load),
                        jnp.sum(load > 0), jnp.max(load)]).astype(jnp.int32)
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


def _ragged(x, experts, local, weights, n: int, form: str):
    t, k = local.shape
    flat = local.reshape(-1)
    order = jnp.argsort(flat, stable=True)       # held first, by expert
    sizes = jnp.bincount(flat, length=n + 1)[:n].astype(jnp.int32)
    rows = x[order // k]                                         # [T*k, h]
    up = jax.lax.ragged_dot(
        rows, experts["w_up"], sizes, preferred_element_type=jnp.float32)
    gate = jax.lax.ragged_dot(
        rows, experts["w_gate"], sizes, preferred_element_type=jnp.float32) \
        if form == "swiglu" else None
    down = jax.lax.ragged_dot(_act(up, gate, form).astype(x.dtype),
                              experts["w_down"], sizes,
                              preferred_element_type=jnp.float32)
    # rows past the held assignments belong to no group: whatever the
    # grouped product left there is not read
    valid = (jnp.arange(t * k) < jnp.sum(sizes))[:, None]
    down = jnp.where(valid, down * weights.reshape(-1)[order][:, None], 0.0)
    back = jnp.zeros((t * k,), jnp.int32).at[order].set(
        jnp.arange(t * k, dtype=jnp.int32))
    return jnp.sum(down[back].reshape(t, k, -1), axis=1).astype(x.dtype)
