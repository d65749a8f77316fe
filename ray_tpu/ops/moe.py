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
  (``ops/grouped_matmul.py``: one Pallas kernel whose visits follow the
  experts' sizes, so it costs what the assignments need and nothing for the
  rows of a block that hold none), unsorted and summed. For prefill.
  On a small share the sorted assignments are COMPACTED first: the held ones
  sort first, so a block of ``_capacity`` rows (a static size from the
  call's shapes: the expected held count with ``COMPACT_SLACK`` to spare)
  is gathered, multiplied, and its rows, weighted, are summed onto their
  tokens (``ops/rows_to_tokens.py``: one Pallas kernel that walks the
  block's rows and keeps the result in VMEM, where XLA's row scatter-add ran
  at a ninth of the memory's bandwidth), and not the ``T * top_k`` rows of
  which the share holds an eighth. Still dropless: a
  routing that leans on this share runs the same body over the next block
  until every held assignment is covered. REVERSE MODE: the block loop is a
  ``lax.while_loop`` whose trip count follows the routing, which JAX cannot
  differentiate, so the compacted product is a ``custom_vjp``
  (``_compacted`` / ``_compacted_bwd``): the reverse pass walks the same
  blocks, as many as the held assignments need (dropless in both
  directions), keeps nothing of the forward but the layer's inputs,
  multiplies each block's rows by W_up (and W_gate) again, and gives the
  cotangent to the tokens (the same kernel over the block's row
  cotangents), to the held experts' matrices (a grouped product
  whose contracted dimension is the ragged one) and to the routing weights,
  so to the router through the chosen weights and their normalisation, and
  not through the choice. Where the block would hold every assignment (a
  share of a half or more) the product runs uncompacted and the grouped
  product's own ``custom_vjp`` (its other two entry points) differentiates
  it.
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
the chosen weights are normalised over all the chosen and scaled. A
``"sigmoid_bias"`` router may also be GROUP-LIMITED (``groups = (n_group,
topk_group)``, ``route``): the choice is among the outputs of the best
``topk_group`` of ``n_group`` groups (``ling-3.0-flash-serve``: the 4 best of
8 groups of 64; a share of whole groups then holds all or nothing of a
token's group). Every other configuration has one group and passes none.

``parallel/expert.py`` is the older capacity-based layer (it drops past a
capacity); only its tests and ``__graft_entry__.py`` use it.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops.grouped_matmul import ROW_TILE, grouped_dot, grouped_outer
from ray_tpu.ops.rows_to_tokens import rows_to_tokens

# what ``_capacity`` rounds a block to: the rows one visit of the grouped
# product fetches and writes (``ops/grouped_matmul.py``; a tile is fetched
# only if an expert holds a row of it, so a block's unheld tail costs nothing
# there, only in the passes around the product). It was the tile of XLA:TPU's
# ``ragged-dot``, which planned ``rows / 512 + groups - 1`` of them whatever
# the sizes
RAGGED_TILE = ROW_TILE
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
          scoring: str = "sigmoid_bias",
          groups: Optional[Tuple[int, int]] = None):
    """x: [T, h]. Scores in float32 over all the router's outputs.
    ``"sigmoid_bias"``: sigmoid scores; the choice is the top ``top_k`` of
    score + ``bias`` (the published ``e_score_correction_bias``), the weights
    are the scores WITHOUT the bias. ``"softmax"``: a softmax over the
    outputs; choice and weights by it, no bias. The weights are over the sum
    of the chosen, times ``scale``. ``groups = (n_group, topk_group)`` limits
    the choice (``"sigmoid_bias"`` only; the published ``noaux_tc``): the
    outputs lie in ``n_group`` groups of equal size, a group's score is the
    sum of its two largest score + ``bias``, and only the ``topk_group`` best
    groups' outputs may be chosen. None or one group: no limit, and the lines
    every configuration but ``ling-3.0-flash-serve`` (8 groups, 4 kept) runs.
    Returns (experts [T, k] int32, weights [T, k] float32)."""
    logits = jnp.matmul(x.astype(jnp.float32), router["w"].astype(jnp.float32),
                        precision="highest")
    if scoring == "softmax":
        # the top scores ARE the weights: reading them again by index cost a
        # 4,096-token chunk 0.33 ms a layer (PERF.md 6, PR 37)
        scores = jax.nn.softmax(logits, axis=-1)
        weights, chosen = jax.lax.top_k(scores, top_k)
    elif scoring == "sigmoid_bias":
        scores = jax.nn.sigmoid(logits)
        choice = scores + router["bias"].astype(jnp.float32)
        if groups is not None and groups[0] > 1:
            choice = _in_the_best_groups(choice, *groups)
        _, chosen = jax.lax.top_k(choice, top_k)
        weights = jnp.take_along_axis(scores, chosen, axis=-1)
    else:
        raise ValueError(f"unknown router scoring {scoring!r}")
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True) * scale
    return chosen.astype(jnp.int32), weights


def _in_the_best_groups(choice, n_group: int, topk_group: int):
    """choice: [T, R] -> the same with the outputs outside each row's
    ``topk_group`` best of ``n_group`` groups at -inf. A group's score: the
    sum of its two largest entries."""
    t, r = choice.shape
    grouped = choice.reshape(t, n_group, r // n_group)
    best_two, _ = jax.lax.top_k(grouped, 2)
    _, kept = jax.lax.top_k(jnp.sum(best_two, axis=-1), topk_group)
    allowed = jnp.any(kept[:, :, None] == jnp.arange(n_group), axis=1)
    return jnp.where(allowed[:, :, None], grouped, -jnp.inf).reshape(t, r)


def routed_experts(x, router: Dict[str, Any], experts: Dict[str, Any], *,
                   held: Tuple[int, int], top_k: int, scale: float,
                   impl: str = "ragged", counted=None,
                   scoring: str = "sigmoid_bias", form: str = "relu2",
                   groups: Optional[Tuple[int, int]] = None):
    """x: [T, h]; router: {"w": [h, R], "bias": [R]} (no bias under
    ``"softmax"``); experts: {"w_up": [E, h, f], "w_down": [E, f, h]} and,
    for ``"swiglu"``, "w_gate" like "w_up", with E = hi - lo. Returns the held
    experts' weighted sum [T, h], and with ``counted`` ([T] bool, the rows
    that are live requests) also int32 [4]: their routed choices, those that
    fell on held experts, held experts with at least one, and the fullest
    held expert's count; under ``"ragged"`` int32 [6], with the calls of the
    compacted product (1 or 0) and the blocks it ran beyond its first (0
    unless the share held more than ``_capacity`` of this call's choices).
    ``groups``: ``route``'s group limit."""
    lo, hi = held
    n = hi - lo
    assert experts["w_up"].shape[0] == n, (experts["w_up"].shape, held)
    if form not in ("relu2", "swiglu"):
        raise ValueError(f"unknown expert form {form!r}")
    with jax.named_scope("router"):
        chosen, weights = route(x, router, top_k, scale, scoring, groups)
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
    float32, unweighted. Rows past ``sum(sizes)`` belong to no group: the
    grouped product writes nothing there, and what the memory held (not
    zeros, maybe not numbers) is not to be read."""
    up = grouped_dot(rows, experts["w_up"], sizes)
    gate = grouped_dot(rows, experts["w_gate"], sizes) \
        if form == "swiglu" else None
    return grouped_dot(_act(up, gate, form).astype(rows.dtype),
                       experts["w_down"], sizes)


def _ragged(x, experts, local, weights, n: int, form: str, width: int):
    """-> (the weighted sum [T, h], int32 [2]: 1 and the blocks the
    compacted product ran beyond its first; zeros where the block would hold
    every assignment and the product runs over all of them at once)."""
    t, k = local.shape
    cap = _capacity(t * k, n, width)
    if cap >= t * k:
        flat = local.reshape(-1)
        order = jnp.argsort(flat, stable=True)   # held first, by expert
        sizes = jnp.bincount(flat, length=n + 1)[:n].astype(jnp.int32)
        down = _grouped(x[order // k], experts, sizes, form)     # [T*k, h]
        # the rows past the held ones are masked BEFORE they meet the
        # weights: what the product left there may be no number, and a
        # product's cotangent would hand it on to the weights times zero
        valid = (jnp.arange(t * k) < jnp.sum(sizes))[:, None]
        down = jnp.where(valid, down, 0.0) * weights.reshape(-1)[order][:, None]
        back = jnp.zeros((t * k,), jnp.int32).at[order].set(
            jnp.arange(t * k, dtype=jnp.int32))
        return jnp.sum(down[back].reshape(t, k, -1), axis=1).astype(x.dtype), \
            jnp.zeros((2,), jnp.int32)
    # the matrices in the order the product takes them (a dict would reach
    # the compiled program sorted by name: another text for the same work)
    return _compacted(x, tuple(experts[name] for name in _names(form)),
                      weights, local, n, form, cap)


def _names(form: str):
    return ("w_up", "w_gate", "w_down") if form == "swiglu" \
        else ("w_up", "w_down")


def _block_plan(local, n: int):
    """The sorted order of the assignments (held first, by expert) and each
    held expert's first and last sorted row."""
    flat = local.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    # a count by comparison: bincount's scatter of 32,768 ones took 0.29 ms
    ends = jnp.cumsum(jnp.sum(flat[:, None] == jnp.arange(n), axis=0,
                              dtype=jnp.int32))
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends[:-1]])
    return order, starts, ends


def _block_rows(b, order, starts, ends, cap: int, k: int):
    """Sorted rows [lo, lo + cap) of block ``b``: their position, the
    assignment and the token each is, and each expert's group clipped to
    them."""
    lo = b * cap
    at = lo + jnp.arange(cap, dtype=jnp.int32)
    which = order[jnp.minimum(at, order.shape[0] - 1)]
    token = which // k
    sizes = jnp.clip(ends, lo, lo + cap) - jnp.clip(starts, lo, lo + cap)
    return at, which, token, sizes


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _compacted(x, matrices, weights, local, n: int, form: str, cap: int):
    """The compacted product: blocks of ``cap`` sorted rows until every held
    assignment is covered -> (the weighted sum [T, h], int32 [2]: 1 and the
    blocks beyond the first). ``matrices``: the experts' in ``_names``'
    order."""
    experts = dict(zip(_names(form), matrices))
    t, k = local.shape
    order, starts, ends = _block_plan(local, n)
    flat_weights = weights.reshape(-1)

    def block(carry):
        b, acc = carry
        at, which, token, sizes = _block_rows(b, order, starts, ends, cap, k)
        down = _grouped(x[token], experts, sizes, form)          # [cap, h]
        # a segment sum by token onto the carry, whose memory the result
        # takes; rows past the held ones go nowhere, and what they hold is
        # never read (``ops/rows_to_tokens.py``). What the carry holds
        # counts from the second block on: the first starts from zeros
        # without reading it
        return b + 1, rows_to_tokens(
            down, jnp.where(at < ends[-1], token, t), t, flat_weights[which],
            onto=(acc, b > 0))

    # ONE block body in the program, run at least once (with no held
    # assignment at all its groups are empty, its rows go nowhere and the
    # result is zeros): a first block written out beside the loop, as the
    # reverse pass has it, made a warm start of Laguna's five prefill
    # programs 1.4 s longer each (PERF.md 6, PR 48)
    blocks, out = jax.lax.while_loop(
        lambda carry: (carry[0] == 0) | (carry[0] * cap < ends[-1]), block,
        (jnp.int32(0), jnp.zeros((t, x.shape[1]), jnp.float32)))
    return out.astype(x.dtype), jnp.stack([1, blocks - 1])


def _compacted_fwd(x, matrices, weights, local, n, form, cap):
    # what the reverse pass keeps: the layer's inputs. It multiplies each
    # block's rows by W_up (and W_gate) again, which under a layer that is
    # rematerialised anyway costs less than keeping them: the second forward
    # of this product is then dead code (PERF.md 6, PR 46)
    return _compacted(x, matrices, weights, local, n, form, cap), \
        (x, matrices, weights, local)


def _compacted_bwd(n, form, cap, kept, cotangent):
    """The reverse of ``_compacted``, dropless as it is: the same blocks in
    the same order, as many as the held assignments need. A block gathers its
    rows, multiplies them by W_up (and W_gate) again, and takes the cotangent
    of the result's rows back through the three grouped products: to the
    rows (the product over the matrices' LAST dimension, the stacks read as
    they lie; summed onto their tokens by ``rows_to_tokens``, as the forward
    sums its outputs), to each held expert's matrices
    (the outer product over ITS rows) and to the routing weights (``<act,
    g W_down^T>``: the unweighted output is never formed). Nothing flows to
    ``local``: the choice is no function of the scores in reverse mode.
    Rows past the held ones hold whatever the products left there, through
    every elementwise pass: nothing zeroes them, because the outer product
    reads no row that is not its expert's and everything else a row feeds
    is that row's own, which goes nowhere: ``rows_to_tokens`` selects such a
    row away and the weights' scatter drops it."""
    x, matrices, weights, local = kept
    names = _names(form)
    experts = dict(zip(names, matrices))
    g, _ = cotangent
    t, k = local.shape
    order, starts, ends = _block_plan(local, n)
    flat_weights = weights.reshape(-1)
    dt = x.dtype

    def reverse(b):
        """Block ``b`` -> (the tokens its rows are and what flows to them,
        the assignments they are and what flows to their weights, what flows
        to the experts' matrices); rows past the held ones go nowhere."""
        at, which, token, sizes = _block_rows(b, order, starts, ends, cap, k)
        valid = at < ends[-1]
        rows = x[token]
        up = grouped_dot(rows, experts["w_up"], sizes)
        gate = grouped_dot(rows, experts["w_gate"], sizes) \
            if form == "swiglu" else None
        act, act_vjp = jax.vjp(lambda u, v: _act(u, v, form), up, gate)
        w = flat_weights[which][:, None]
        g_rows = g[token].astype(dt)
        back = grouped_dot(g_rows, experts["w_down"], sizes,
                           transposed=True)                  # [cap, f]
        d_w = jnp.sum(act * back, axis=-1)
        d_up, d_gate = act_vjp(back * w)
        d_up = d_up.astype(dt)
        grads = {"w_down": grouped_outer((act * w).astype(dt), g_rows, sizes),
                 "w_up": grouped_outer(rows, d_up, sizes)}
        d_rows = grouped_dot(d_up, experts["w_up"], sizes, transposed=True)
        if form == "swiglu":
            d_gate = d_gate.astype(dt)
            grads["w_gate"] = grouped_outer(rows, d_gate, sizes)
            d_rows = d_rows + grouped_dot(d_gate, experts["w_gate"], sizes,
                                          transposed=True)
        return (jnp.where(valid, token, t), d_rows,
                jnp.where(valid, which, t * k), d_w, grads)

    def block(carry):
        b, dx, d_weights, d_experts = carry
        token, d_rows, which, d_w, grads = reverse(b)
        return (b + 1, dx + rows_to_tokens(d_rows, token, t),
                d_weights.at[which].set(d_w, mode="drop"),
                {name: d_experts[name] + grads[name] for name in names})

    # the first block outside the loop: a share that fits one block (every
    # call but a routing that leans on this share) then adds nothing to three
    # float32 stacks of zeros, which cost a layer's chunk as much as a
    # grouped product (PERF.md 6, PR 46); with no held assignment at all its
    # groups are empty and its rows go nowhere
    token, d_rows, which, d_w, grads = reverse(jnp.int32(0))
    _, dx, d_weights, d_experts = jax.lax.while_loop(
        lambda carry: carry[0] * cap < ends[-1], block,
        (jnp.int32(1),
         rows_to_tokens(d_rows, token, t),
         jnp.zeros((t * k,), jnp.float32).at[which].set(d_w, mode="drop"),
         grads))
    d_matrices = tuple(d_experts[name].astype(experts[name].dtype)
                       for name in names)
    return (dx.astype(dt), d_matrices,
            d_weights.reshape(t, k).astype(weights.dtype), None)


_compacted.defvjp(_compacted_fwd, _compacted_bwd)
