"""The gated delta rule with ONE decay a head (``model_type`` olmo_hybrid's
linear layers; Gated DeltaNet), forward AND reverse, for whole rows from a
zero state: the TRAINING path's recurrent layer.

    S_t = exp(g_t) S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T
    o_t = S_t q_t                    S: [d_v, d_k] a head, float32

``g_t <= 0`` is one float32 number a head with NO lower bound, ``beta_t`` one
number in (0, 2) (``I - beta k k^T`` is then no contraction), ``d_k`` and
``d_v`` differ (96 and 192 as published). ``ops/kda.py`` holds the rule with a
decay a CHANNEL at ``d_k = d_v = 128`` for the serving path; what this module
shares with it is imported from there (the MXU products, the three-pass chain
product, the lockstep of a grid step's heads) and nothing of it is changed.

``gdn_chunk`` takes ``CHUNK`` (64) rows at a time with the state resident in
VMEM (``gdn_chunk_fwd`` in a profile). ``G_i`` the sum of ``g`` from the
chunk's start through row ``i``, ``Gam_ij = exp(G_i - G_j)`` for ``j <= i``
(one matrix a head, from the DIFFERENCE under the mask, at most 1: no
sub-chunks and no bound on ``g`` are needed), ``Kb = beta K``, ``S`` the
state the chunk starts from:

    A  = strictly_lower((Kb K^T) * Gam)        T = (I + A)^-1
    Vn = T (beta V - (Kb * exp(G)) S^T)        what the chunk's rows write
    O  = (Q * exp(G)) S^T + lower((Q K^T) * Gam) Vn
    S' = exp(G_last) S + Vn^T (K * exp(G_last - G))

(``Vn = U - W S^T`` with ``U = T beta V``, ``W = T (Kb exp(G))`` in the WY
form; the state is resident here, so ``T`` multiplies the difference once and
not its two terms.) ``T`` is exact block algebra and no power series over 64
rows: from 1 x 1 blocks (each its own inverse) the inverse of the diagonal
blocks of twice the size, ``[[a, 0], [m, b]]^-1 = [[a^-1, 0], [-b^-1 m a^-1,
b^-1]]``, six times. It is forward substitution in another order: no term is
a power of ``A``, so nothing grows where keys repeat and ``beta`` is near 2.
Under bfloat16 inputs those ten [64, 64] products are three bfloat16 passes
each (``kda._dot_split``); every other product takes bfloat16 operands and
sums in float32; float32 inputs multiply at ``highest`` everywhere (tests).
``g``, ``beta``, the decays and the state are float32 always.

The reverse pass (``jax.custom_vjp``) keeps NO state of the forward. A group
of heads at a time (a ``lax.scan`` over the groups a grid step holds), it
first runs the forward's state recurrence again and writes every chunk's
starting state (``gdn_chunk_bwd_states``: 73.7 kB a head and chunk in float32,
98 kB as HBM tiles it; 1.5 GB for 30 heads x 512 chunks, so 0.5 GB for the ten
of a group, alive only until the group's sweep has read them), then sweeps
the chunks from the last to the first with the state's cotangent resident
(``gdn_chunk_bwd``), recomputing ``Gam``, ``A``, ``T`` and ``Vn`` of the chunk
from its inputs and its starting state:

    dVn = Aq^T dO + Kf dS'^T          dR = T^T dVn       (R = beta V - Ke S^T)
    dA  = -strictly_lower(dR Vn^T)    dAq = lower(dO Vn^T)
    dS  = exp(G_last) dS' + dO^T Qe - dR^T Ke
    dQ  = (dO S) * e + (dAq * Gam) K
    dKb = (dA * Gam) K - (dR S) * e
    dK  = (dA * Gam)^T Kb + (dAq * Gam)^T Q + (Vn dS') * f + beta dKb
    dV  = beta dR       dbeta = <dKb, K> + <dR, V>
    dG_i = <dQe_i, Qe_i> + <dKe_i, Ke_i> - <dKf_i, Kf_i> + sum_j M_ij - sum_j M_ji
    dG_last += sum_i <dKf_i, Kf_i> + exp(G_last) <dS', S>,   M = dA * A + dAq * Aq

and ``dg`` is the sum of ``dG`` from a row to its chunk's end (outside the
kernel). What a grid step holds: ``HEADS`` heads (the first of 10, 6, 5, 3, 2,
1 that divides the head count; 30 heads are three steps of ten) traced in
lockstep, each head's products at its own widths: a [64, 96] operand is 0.75
of a lane tile and a [64, 192] one 1.5, and Mosaic pads them to 1 and 2. Rows
that share a right operand are stacked (``[Kb ; Q] K^T``, ``[Ke ; Qe] S^T``,
``[dO ; -dR]`` against ``Vn^T``, ``S`` and, as a left operand transposed,
``[Qe ; Ke]``), so most products stream 128 rows. PERF.md 3 has the share of
the MXU's rows that is padding.

A number a row (``G``, ``beta``) reaches the kernel as a ROW of its head,
``[heads, 64]``, and is turned to a column ([64, 1], what scales a row of K)
by one exact product with the identity a grid step; the reverse pass turns
its columns of ``dG`` and ``dbeta`` back the same way.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.kda import (
    _NN, _NT, _TN, _dot, _dot_split, _in_lockstep, _kda_impl)

CHUNK = 64
# heads a grid step holds: the first that divides the head count
HEADS = (10, 6, 5, 3, 2, 1)
LANES = 128


def gdn_recurrence(q, k, v, g, beta):
    """The equations at the top as a ``lax.scan`` over time, a token a step,
    in float32 (what the kernels are held to, and ``impl="reference"``). q,
    k: [B, S, H, d_k]; v: [B, S, H, d_v]; g, beta: [B, S, H]. Returns o
    [B, S, H, d_v] float32."""

    def step(state, part):
        qt, kt, vt, gt, bt = part
        state = jnp.exp(gt)[..., None, None] * state          # [B, H, dv, dk]
        seen = jnp.einsum("bhvk,bhk->bhv", state, kt, precision="highest")
        write = bt[..., None] * (vt - seen)
        state = state + write[..., :, None] * kt[..., None, :]
        return state, jnp.einsum("bhvk,bhk->bhv", state, qt,
                                 precision="highest")

    time_major = [jnp.moveaxis(t.astype(jnp.float32), 1, 0)
                  for t in (q, k, v, g, beta)]
    b, _, h, dk = q.shape
    _, o = jax.lax.scan(
        step, jnp.zeros((b, h, v.shape[-1], dk), jnp.float32), time_major)
    return jnp.moveaxis(o, 0, 1)


# --------------------------------------------------------------------------- #
# One chunk of one head
# --------------------------------------------------------------------------- #
def _masks():
    """The chunk's [CHUNK, CHUNK] masks, from whole iotas (Mosaic aborts on a
    slice of one and on ``%``): the identity, ``j <= i``, ``j < i``, and for
    each block size 2, 4 .. 32 the blocks left of and below the diagonal
    that the doubling of the inverse multiplies."""
    row = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 1)
    levels, b = [], 1
    while b < CHUNK:
        levels.append((row // b == col // b + 1) & ((col // b) & 1 == 0))
        b *= 2
    return dict(eye=row == col, lower=row >= col, strict=row > col,
                levels=levels)


def _unit_lower_inverse(a, masks, chain):
    """(I + a)^-1 for a strictly lower [CHUNK, CHUNK] ``a``: the diagonal
    blocks' inverses at twice the size from those at the size, ``t - t m t``
    with ``m`` the blocks of ``a`` between them. A generator (it yields
    between its products, ``_in_lockstep``); returns the inverse."""
    first, *levels = masks["levels"]
    t = masks["eye"].astype(jnp.float32) - jnp.where(first, a, 0.0)
    for level in levels:
        mt = chain(jnp.where(level, a, 0.0), t)
        yield
        t = t - chain(t, mt)
        yield
    return t


def _chunk_parts(q, k, v, g_row, g_col, b_col, masks):
    """What a chunk of one head brings to its products. q, k: [CHUNK, d_k];
    v: [CHUNK, d_v]; g_row [1, CHUNK] and g_col [CHUNK, 1]: ``G`` both ways;
    b_col [CHUNK, 1]: beta."""
    f32 = jnp.float32
    q, k, v = (t.astype(f32) for t in (q, k, v))
    g_last = g_row[:, CHUNK - 1:]                                   # [1, 1]
    e, f = jnp.exp(g_col), jnp.exp(g_last - g_col)
    lower = masks["lower"]
    gam = jnp.where(lower, jnp.exp(jnp.where(lower, g_col - g_row, 0.0)), 0.0)
    kb = b_col * k
    return dict(q=q, k=k, v=v, beta=b_col, e=e, f=f, gam=gam, kb=kb,
                ke=kb * e, qe=q * e, kf=k * f, vb=b_col * v,
                # a row of the state's width: Mosaic broadcasts a [1, 1] over
                # lanes or over sublanes, not over both at once
                decay=jnp.exp(jnp.broadcast_to(g_last, (1, k.shape[-1]))),
                decay_one=jnp.exp(g_last))


def _fwd_head(parts, state, masks, mxu, want_o: bool):
    """One chunk of one head: (o [CHUNK, d_v] float32 or None, the state
    after the chunk). state: [d_v, d_k] float32. A generator."""
    p = parts
    chain = _dot if mxu == jnp.float32 else _dot_split
    left = [p["kb"], p["q"]] if want_o else [p["kb"]]
    pairs = _dot(jnp.concatenate(left, axis=0), p["k"], _NT, mxu)
    left = [p["ke"], p["qe"]] if want_o else [p["ke"]]
    seen = _dot(jnp.concatenate(left, axis=0), state, _NT, mxu)
    yield
    a = jnp.where(masks["strict"], pairs[:CHUNK] * p["gam"], 0.0)
    t = yield from _unit_lower_inverse(a, masks, chain)
    vn = _dot(t, p["vb"] - seen[:CHUNK], _NN, mxu)
    yield
    new = p["decay"] * state + _dot(vn, p["kf"], _TN, mxu)
    if not want_o:
        return None, new
    o = seen[CHUNK:] + _dot(pairs[CHUNK:] * p["gam"], vn, _NN, mxu)
    return o, new


def _bwd_head(parts, do, state, dstate, masks, mxu):
    """One chunk of one head in reverse. do: [CHUNK, d_v]; state: what the
    chunk started from; dstate: the cotangent of what it left. Returns (dq,
    dk, dv, the part of dG that is a column [CHUNK, 1], dbeta as a column,
    the part of dG that is a row [1, CHUNK], the cotangent of the chunk's
    starting state). A generator."""
    p = parts
    chain = _dot if mxu == jnp.float32 else _dot_split
    lower, strict, gam = masks["lower"], masks["strict"], p["gam"]
    do = do.astype(jnp.float32)
    kb_q = jnp.concatenate([p["kb"], p["q"]], axis=0)
    pairs = _dot(kb_q, p["k"], _NT, mxu)
    seen = _dot(p["ke"], state, _NT, mxu)
    yield
    a = jnp.where(strict, pairs[:CHUNK] * gam, 0.0)
    aq = pairs[CHUNK:] * gam
    t = yield from _unit_lower_inverse(a, masks, chain)
    vn = _dot(t, p["vb"] - seen, _NN, mxu)
    dvn = _dot(aq, do, _TN, mxu) + _dot(p["kf"], dstate, _NT, mxu)
    yield
    dr = _dot(t, dvn, _TN, mxu)
    yield
    both = jnp.concatenate([do, -dr], axis=0)                  # [2 C, d_v]
    d_pairs = _dot(both, vn, _NT, mxu)                         # dAq over dA
    d_seen = _dot(both, state, _NN, mxu)                       # dQe over dKe
    dkf = _dot(vn, dstate, _NN, mxu)
    new = p["decay"] * dstate + _dot(
        both, jnp.concatenate([p["qe"], p["ke"]], axis=0), _TN, mxu)
    yield
    daq = jnp.where(lower, d_pairs[:CHUNK], 0.0)
    da = jnp.where(strict, d_pairs[CHUNK:], 0.0)
    m = da * a + daq * aq                                      # dGam * Gam
    scaled = jnp.concatenate([da * gam, daq * gam], axis=0)    # dP over dR2
    from_k = _dot(scaled, p["k"], _NN, mxu)                    # dKb over dQ
    to_k = _dot(scaled, kb_q, _TN, mxu)
    yield
    dqe, dke = d_seen[:CHUNK], d_seen[CHUNK:]
    dkb = from_k[:CHUNK] + dke * p["e"]
    dq = dqe * p["e"] + from_k[CHUNK:]
    dk = to_k + dkf * p["f"] + p["beta"] * dkb
    dv = p["beta"] * dr

    def along(x):                                  # [CHUNK, d] -> [CHUNK, 1]
        return jnp.sum(x, axis=1, keepdims=True)

    at_end = along(dkf * p["kf"])
    g_col = along(dqe * p["qe"] + dke * p["ke"]) - at_end + along(m)
    b_col = along(dkb * p["k"]) + along(dr * p["v"])
    # G_last is G of the chunk's last row: what reaches it goes to that lane
    to_last = jnp.sum(at_end, axis=0, keepdims=True) \
        + p["decay_one"] * jnp.sum(along(dstate * state), axis=0,
                                   keepdims=True)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, CHUNK), 1)
    g_row = jnp.where(lane == CHUNK - 1, to_last, 0.0) \
        - jnp.sum(m, axis=0, keepdims=True)
    return dq, dk, dv, g_col, b_col, g_row, new


# --------------------------------------------------------------------------- #
# The kernels
# --------------------------------------------------------------------------- #
def _columns(gb, heads: int, masks):
    """gb: [rows >= 2 heads, CHUNK] float32, ``G`` of each head a row and then
    ``beta`` of each -> a (g_row, g_col, b_col) a head. One product with the
    identity turns every row to a column; at ``highest`` it is exact."""
    cols = _dot(masks["eye"].astype(jnp.float32), gb, _NT)   # [CHUNK, rows]
    return [(gb[h:h + 1], cols[:, h:h + 1], cols[:, heads + h:heads + h + 1])
            for h in range(heads)]


def _fwd_kernel(first_ref, q_ref, k_ref, v_ref, gb_ref, out_ref, s_ref, *,
                heads: int, mxu, want_o: bool):
    # first_ref: the call's first head group (the index maps read it); q_ref,
    # k_ref: [1, heads, CHUNK, d_k]; v_ref: [1, heads, CHUNK, d_v]; gb_ref:
    # [1, 1, 1, rows, CHUNK]; out_ref: o [1, heads, CHUNK, d_v], or
    # (``want_o`` false) the state each chunk STARTS from, [1, heads, 1, d_v,
    # d_k]; s_ref: the resident state, scratch [heads, d_v, d_k]
    del first_ref

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros(s_ref.shape, s_ref.dtype)

    if not want_o:
        out_ref[0, :, 0] = s_ref[...]
    masks = _masks()
    done = _in_lockstep(
        _fwd_head(_chunk_parts(q_ref[0, h], k_ref[0, h], v_ref[0, h], *turned,
                               masks), s_ref[h], masks, mxu, want_o)
        for h, turned in enumerate(_columns(gb_ref[0, 0, 0], heads, masks)))
    for h, (o, state) in enumerate(done):
        if want_o:
            out_ref[0, h] = o.astype(out_ref.dtype)
        s_ref[h] = state


def _bwd_kernel(first_ref, q_ref, k_ref, v_ref, gb_ref, do_ref, s_ref, dq_ref,
                dk_ref, dv_ref, dgb_ref, ds_ref, *, heads: int, mxu):
    # the grid's last axis counts the chunks from the LAST (the index maps
    # turn it). s_ref: [1, heads, 1, d_v, d_k], the state each chunk started
    # from; ds_ref: the resident cotangent of the state, scratch; dgb_ref:
    # [1, 1, 1, rows, CHUNK], dG of each head a row and then dbeta of each
    del first_ref

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_ref[...] = jnp.zeros(ds_ref.shape, ds_ref.dtype)

    masks = _masks()
    done = _in_lockstep(
        _bwd_head(_chunk_parts(q_ref[0, h], k_ref[0, h], v_ref[0, h], *turned,
                               masks), do_ref[0, h], s_ref[0, h, 0], ds_ref[h],
                  masks, mxu)
        for h, turned in enumerate(_columns(gb_ref[0, 0, 0], heads, masks)))
    rows = dgb_ref.shape[3]
    lane = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, LANES), 1)
    sub = jax.lax.broadcasted_iota(jnp.int32, (rows, CHUNK), 0)
    cols = jnp.zeros((CHUNK, LANES), jnp.float32)
    as_rows = jnp.zeros((rows, CHUNK), jnp.float32)
    for h, (dq, dk, dv, g_col, b_col, g_row, dstate) in enumerate(done):
        dq_ref[0, h] = dq.astype(dq_ref.dtype)
        dk_ref[0, h] = dk.astype(dk_ref.dtype)
        dv_ref[0, h] = dv.astype(dv_ref.dtype)
        ds_ref[h] = dstate
        cols = jnp.where(lane == h, g_col, cols)
        cols = jnp.where(lane == heads + h, b_col, cols)
        as_rows = jnp.where(sub == h, g_row, as_rows)
    # the columns back to rows: out[r, i] = cols[i, r], exact at ``highest``
    pick = (jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1))
    dgb_ref[0, 0, 0] = as_rows + _dot(pick.astype(jnp.float32), cols, _NT)


def _heads_a_step(h: int) -> int:
    return next(n for n in HEADS if h % n == 0)


def _laid_out(q, k, v, g, beta):
    """The kernels' operands from [B, S, H, d] rows (S a whole number of
    chunks): q, k, v heads first, and ``gb`` [B, chunks, H / hb, rows, CHUNK]:
    of each group of ``hb`` heads, ``G`` (the sum of ``g`` within the chunk) a
    row a head, then ``beta`` a row a head, then zero rows to a multiple of
    8."""
    b, s, h, _ = q.shape
    hb, n = _heads_a_step(h), s // CHUNK

    def rows(x):                          # [B, S, H] -> [B, n, H / hb, hb, C]
        return x.reshape(b, n, CHUNK, h // hb, hb).transpose(0, 1, 3, 4, 2)

    g = g.astype(jnp.float32).reshape(b, n, CHUNK, h)
    gb = jnp.concatenate([rows(jnp.cumsum(g, axis=2)),
                          rows(beta.astype(jnp.float32))], axis=3)
    gb = jnp.pad(gb, ((0, 0),) * 3 + ((0, -2 * hb % 8), (0, 0)))
    return [t.transpose(0, 2, 1, 3) for t in (q, k, v)], gb


def _call(kernel, name: str, first, operands, specs, out_specs, out_shapes,
          hb: int, groups: int, interpret: bool):
    """One kernel over a grid (batch, ``groups`` head groups from group
    ``first`` on, chunks), the state (or its cotangent) resident in a
    scratch. ``specs`` / ``out_specs``: a (block shape, index map over (i, j,
    c, first_ref)) each."""
    b, _, s, dk = operands[0].shape
    dv = operands[2].shape[-1]
    as_spec = lambda spec: pl.BlockSpec(*spec)  # noqa: E731
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, groups, s // CHUNK),
            in_specs=[as_spec(x) for x in specs],
            out_specs=jax.tree.map(as_spec, out_specs,
                                   is_leaf=lambda x: isinstance(x, tuple)),
            scratch_shapes=[pltpu.VMEM((hb, dv, dk), jnp.float32)]),
        out_shape=out_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name=name, interpret=interpret,
    )(jnp.reshape(first, (1,)).astype(jnp.int32), *operands)


def _specs(hb: int, dk: int, dv: int, rows: int, turn=lambda c: c,
           offset: bool = True):
    """(block shape, index map) of the kernels' operands over a grid (batch,
    head groups, chunks): ``turn`` maps the grid's last index to the chunk;
    with ``offset`` the head group counts from the call's first (operands
    that hold EVERY group), without it from 0 (results of the call's own
    groups)."""
    def group(j, first):
        return j + first[0] if offset else j

    def rows_of(d):
        return ((1, hb, CHUNK, d),
                lambda i, j, c, first: (i, group(j, first), turn(c), 0))
    return dict(
        k=rows_of(dk), v=rows_of(dv),
        gb=((1, 1, 1, rows, CHUNK),
            lambda i, j, c, first: (i, turn(c), group(j, first), 0, 0)),
        state=((1, hb, 1, dv, dk),
               lambda i, j, c, first: (i, group(j, first), turn(c), 0, 0)))


def _mxu(q):
    return jnp.float32 if q.dtype == jnp.float32 else jnp.bfloat16


def _forward(q, k, v, gb, *, first=0, groups=None, want_o: bool,
             interpret: bool):
    """The forward kernel over laid-out operands, ``groups`` head groups
    from ``first`` on (default: all): o [B, heads, S, d_v] in v's type, or
    (``want_o`` false, the reverse pass's first sweep) the state every chunk
    starts from, [B, heads, chunks, d_v, d_k] float32."""
    b, h, s, dk = q.shape
    dv, rows = v.shape[-1], gb.shape[3]
    hb = h // gb.shape[2]
    groups = groups or h // hb
    spec, own = _specs(hb, dk, dv, rows), _specs(hb, dk, dv, rows, offset=False)
    if want_o:
        out = own["v"], jax.ShapeDtypeStruct((b, groups * hb, s, dv), v.dtype)
    else:
        out = own["state"], jax.ShapeDtypeStruct(
            (b, groups * hb, s // CHUNK, dv, dk), jnp.float32)
    return _call(
        functools.partial(_fwd_kernel, heads=hb, mxu=_mxu(q), want_o=want_o),
        "gdn_chunk_fwd" if want_o else "gdn_chunk_bwd_states", first,
        (q, k, v, gb), [spec["k"], spec["k"], spec["v"], spec["gb"]], *out,
        hb, groups, interpret)


def _padded(s: int, *rows):
    """Rows [B, S, ...] padded to a whole number of chunks with zeros: no
    decay (g = 0) and nothing written (beta = 0) there."""
    pad = -s % CHUNK
    if not pad:
        return rows
    return tuple(jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                 for t in rows)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _gdn_kernels(q, k, v, g, beta, interpret: bool):
    s = q.shape[1]
    (q, k, v), gb = _laid_out(*_padded(s, q, k, v, g, beta))
    o = _forward(q, k, v, gb, want_o=True, interpret=interpret)
    return o.transpose(0, 2, 1, 3)[:, :s]


def _gdn_kernels_fwd(q, k, v, g, beta, interpret):
    # what a remat policy keeps by name; the reverse pass wants the INPUTS
    o = checkpoint_name(_gdn_kernels(q, k, v, g, beta, interpret), "gdn_out")
    return o, (q, k, v, g, beta)


def _gdn_kernels_bwd(interpret, kept, do):
    q, k, v, g, beta = kept
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    (qh, kh, vh), gb = _laid_out(*_padded(s, q, k, v, g, beta))
    (do,) = _padded(s, do)
    do = do.astype(v.dtype).transpose(0, 2, 1, 3)
    n, groups, rows = gb.shape[1:4]
    hb = h // groups
    turn = lambda c: n - 1 - c  # noqa: E731 - the last chunk first
    spec = _specs(hb, dk, dv, rows, turn)
    own = _specs(hb, dk, dv, rows, turn, offset=False)

    def of_group(d, dtype):
        return jax.ShapeDtypeStruct((b, hb, n * CHUNK, d), dtype)

    def one_group(_, j):
        """A group of ``hb`` heads at a time: its chunks' starting states
        (73.7 kB a head and chunk) live only until its sweep has read them."""
        states = _forward(qh, kh, vh, gb, first=j, groups=1, want_o=False,
                          interpret=interpret)
        return None, _call(
            functools.partial(_bwd_kernel, heads=hb, mxu=_mxu(q)),
            "gdn_chunk_bwd", j, (qh, kh, vh, gb, do, states),
            [spec["k"], spec["k"], spec["v"], spec["gb"], spec["v"],
             own["state"]],
            [own["k"], own["k"], own["v"], own["gb"]],
            [of_group(dk, q.dtype), of_group(dk, k.dtype),
             of_group(dv, v.dtype),
             jax.ShapeDtypeStruct((b, n, 1, rows, CHUNK), jnp.float32)],
            hb, 1, interpret)

    _, (dq, dk_, dv_, dgb) = jax.lax.scan(one_group, None, jnp.arange(groups))

    def to_rows(x):                    # [groups, B, hb, S, d] -> [B, S, H, d]
        return x.transpose(1, 3, 0, 2, 4).reshape(b, -1, h, x.shape[-1])[:, :s]

    def per_row(x):      # [groups, B, n, 1, hb, C] -> [B, n, C, H]
        return x[:, :, :, 0].transpose(1, 2, 4, 0, 3).reshape(b, n, CHUNK, h)

    # G_i is the sum of g through row i: g_t reaches every G from t on
    d_g = per_row(dgb[:, :, :, :, :hb])
    dg = jnp.flip(jnp.cumsum(jnp.flip(d_g, axis=2), axis=2), axis=2)
    dbeta = per_row(dgb[:, :, :, :, hb:2 * hb])
    return (to_rows(dq), to_rows(dk_), to_rows(dv_),
            dg.reshape(b, -1, h)[:, :s].astype(g.dtype),
            dbeta.reshape(b, -1, h)[:, :s].astype(beta.dtype))


_gdn_kernels.defvjp(_gdn_kernels_fwd, _gdn_kernels_bwd)


@functools.partial(jax.jit, static_argnames=("impl",))
def gdn_chunk(q, k, v, g, beta, *, impl: str = "auto"):
    """q, k: [B, S, H, d_k] (q scaled and k normalised by the caller); v:
    [B, S, H, d_v]; g: [B, S, H] float32, the logarithm of the decay, at most
    0; beta: [B, S, H]. Whole rows from a zero state. Returns o [B, S, H,
    d_v] in v's type; differentiable in all five (the reverse pass above).
    ``impl``: "pallas", "pallas_interpret", "reference" (the recurrence, a
    token a step, differentiated by jax), or "auto": the kernels on a
    TPU."""
    impl = _kda_impl(impl)
    if impl == "reference":
        return gdn_recurrence(q, k, v, g, beta).astype(v.dtype)
    return _gdn_kernels(q, k, v, g.astype(jnp.float32),
                        beta.astype(jnp.float32), impl == "pallas_interpret")
