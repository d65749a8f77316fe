"""Kimi Delta Attention's core: a delta-rule state with a decay per CHANNEL,

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                        S: [d_k, d_v] a head, float32

with ``alpha_t = exp(a_t)``, ``a_t`` a vector of ``d_k`` negative numbers
(the configuration bounds them below: ``kda_lower_bound``), ``beta_t`` one
number a head. Neither of ``ops/ssm.py``'s scans computes it: their update is
a decay plus an outer product; here the state is also multiplied by ``I -
beta k k^T``, so a chunk of it is a triangular SYSTEM and not a sum.

``kda_prefill`` (``kda_chunk_fwd`` in a profile) takes a prompt ``CHUNK`` (64)
rows at a time with the state resident in VMEM. Write ``G_i`` for the sum of
``a`` from the chunk's start through row ``i``, and ``S`` for the state at
the chunk's start. Then, rows as matrices,

    A_ij  = sum_c k_i[c] k_j[c] exp(G_i[c] - G_j[c])          j <  i
    Aq_ij = sum_c q_i[c] k_j[c] exp(G_i[c] - G_j[c])          j <= i
    U     = (I + Diag(beta) A)^-1 Diag(beta) (V - (K * exp(G)) S)
    O     = (Q * exp(G)) S + Aq U
    S'    = Diag(exp(G_last)) S + (K * exp(G_last - G))^T U

(the WY / UT-transform form: ``U`` is what the rows of the chunk write, all
at once). The pair decay ``exp(G_i - G_j)`` is a product over 128 channels of
their own decays and does not factor into ``exp(G_i) exp(-G_j)`` over a
chunk: at ``a = -5`` a chunk's ``exp(-G)`` is e^320. Over a SUB-chunk of
``SUB`` (16) rows it is e^80 at most, inside float32 and bfloat16, which is
what the lower bound of -5 is for. So ``A`` is put together from 16 x 16
blocks: a diagonal block factors about its sub-chunk's start; a block below
the diagonal as (decay from the row's sub-chunk's start, times whole
sub-chunks between) x (decay from the column to ITS sub-chunk's end), every
factor at most 1. The inverse is exact block algebra and no power series over
64 rows (whose terms would grow like binomial coefficients where keys
repeat): a Neumann product over the 16-row diagonal blocks (nilpotent:
``(I - N)(I + N^2)(I + N^4)(I + N^8)`` is the whole series), then the four
block rows by substitution (``(I + M)^-1 = (I - M)(I + M^2)`` for a
strictly block-lower ``M`` of four blocks).

``kda_step`` (``kda_step`` in a profile) is one token of every slot: the
state of one layer read once, moved and written once, in place.

What enters the MXU follows the inputs: bfloat16 q / k / v multiply as
bfloat16 with float32 sums, except the inverse's chain, whose float32
matrices multiply in three bfloat16 passes (``_dot_split``); float32 inputs
multiply at ``highest`` everywhere (tests). ``a``, the decays and the state
are float32 always. What a product holds: the MXU takes a row of its left
operand a cycle whatever the operands' widths, and a [64, 64] float32
matrix half fills its registers' lanes, so the chunk kernel keeps every [64,
64] matrix of a PAIR of heads side by side, ``[x_a | x_b]`` [64, 128], and
multiplies it by ``blockdiag(y_a, y_b)``: one product and whole registers
where there were two and half-empty ones (an absent partner is a zero block
of the same product). Products that share a right operand are stacked on the
rows (``[N^2 ; inv] @ N^2``); rows whose block is zero are not multiplied
(the first ``i`` sub-chunks of a block ``i`` below the diagonal, of the block
rows' ``m`` and ``m^2``); the Neumann product runs over the diagonal blocks
alone, the pair's eight side by side [16, 128]; and a chain product's three
passes are one product three times as deep. A head and chunk took 40
passes of the MXU (30 of them the chain's [64, 64] products, each waiting
for the one before) that streamed 2,880 rows of left operands through it;
it takes 20 now (a pair 40, of whole 128 x 128 tiles) that stream 1,152.
And the pairs of a grid step are traced in LOCKSTEP (``_in_lockstep``): the
scheduler keeps to the order of the trace, and chains traced one after
another waited on every product. On a v5e, a piece of 2,048 rows x 32 heads
(PERF.md 6, PR 54): 1.72 ms as it was; the same body in lockstep 0.64; in
pairs 0.97; both, 0.44; without the zero rows 0.33; sixteen heads a grid
step 0.26.

The serving engine pads. A row at or past its prompt's ``lengths`` entry
takes ``a = 0`` and ``beta = 0``: no decay, nothing written, so the state
stays what the last real row left; a chunk wholly past it is not computed.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 64
SUB = 16
# heads a grid step of the step kernel holds: 8 rows of [heads, 128] are one
# float32 tile of its tokens. The chunk kernel takes twice as many where
# they divide the head count (eight pairs in lockstep; thirty-two heads'
# blocks are over the kernel's 16 MB of VMEM), else as many, else one
HEADS = 8

_NN = (((1,), (0,)), ((), ()))   # a @ b
_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_TN = (((0,), (0,)), ((), ()))   # a.T @ b


def _kda_impl(impl: str) -> str:
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "reference"
    if impl not in ("pallas", "pallas_interpret", "reference"):
        raise ValueError(f"unknown kda impl {impl!r}")
    return impl


def _dot(a, b, dims=_NN, dtype=jnp.float32):
    """A product on the MXU, summed in float32: bfloat16 operands as they
    are, float32 operands at ``highest``."""
    if dtype == jnp.float32:
        return jax.lax.dot_general(
            a.astype(jnp.float32), b.astype(jnp.float32), dims,
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
    return jax.lax.dot_general(a.astype(dtype), b.astype(dtype), dims,
                               preferred_element_type=jnp.float32)


def _dot_split(a, b):
    """a @ b for float32 operands in three bfloat16 passes (high x high, low
    x high, high x low; what is left is 2^-16 of a term): the inverse's chain
    under bfloat16 inputs. One pass lost 9% of an output where keys repeat
    and nothing decays (the chain's terms then cancel); ``highest`` is six.
    The three are ONE product three times as deep, ``[a_hi | a_lo | a_hi] @
    [b_hi ; b_hi ; b_lo]``: each half is rounded to bfloat16 once, where the
    product takes it (a rounded value read by a product AND by a subtraction
    is laid out twice), and nothing is added up outside the product."""
    def halves(x):
        high = x.astype(jnp.bfloat16).astype(jnp.float32)
        return high, x - high

    (a_hi, a_lo), (b_hi, b_lo) = halves(a), halves(b)
    return _dot(jnp.concatenate([a_hi, a_lo, a_hi], axis=1),
                jnp.concatenate([b_hi, b_hi, b_lo], axis=0), _NN,
                jnp.bfloat16)


# --------------------------------------------------------------------------- #
# The recurrence itself
# --------------------------------------------------------------------------- #
def kda_recurrence(q, k, v, a, beta, state0):
    """The equations at the top as a ``lax.scan`` over time, a token a step,
    in float32 (CPU tests and backends without the kernels; what the kernels
    are held to). q, k, v, a: [B, S, H, D]; beta: [B, S, H]; state0:
    [B, H, D, D], stored ``[d_v, d_k]`` as everywhere in this module's
    arguments. Returns (o [B, S, H, D] float32, the last state)."""

    def step(state, part):
        qt, kt, vt, at, bt = part                        # [B, H, D], [B, H]
        state = jnp.exp(at)[..., None] * state
        seen = jnp.einsum("bhk,bhkv->bhv", kt, state, precision="highest")
        write = bt[..., None] * (vt - seen)
        state = state + kt[..., None] * write[..., None, :]
        return state, jnp.einsum("bhk,bhkv->bhv", qt, state,
                                 precision="highest")

    time_major = [jnp.moveaxis(t.astype(jnp.float32), 1, 0)
                  for t in (q, k, v, a, beta)]
    state, o = jax.lax.scan(
        step, state0.astype(jnp.float32).swapaxes(-1, -2), time_major)
    return jnp.moveaxis(o, 0, 1), state.swapaxes(-1, -2)


# --------------------------------------------------------------------------- #
# Prefill: chunks
# --------------------------------------------------------------------------- #
def _rows_of_subs(parts, d: int):
    """``parts``: one [1, d] row a sub-chunk -> [CHUNK, d], each row
    repeated over its sub-chunk's rows."""
    return jnp.concatenate(
        [jnp.broadcast_to(p, (SUB, d)) for p in parts], axis=0)


def _beside(parts):
    """[a | b] along the lanes; a pair's absent partner is a zero block."""
    return jnp.concatenate(
        list(parts) + [jnp.zeros_like(parts[0])] * (2 - len(parts)), axis=1)


def _blockdiag(parts):
    """[[a, 0], [0, b]]: ``[x_a | x_b] @ blockdiag(y_a, y_b)`` is ``[x_a y_a
    | x_b y_b]``, both heads' product in one (the zeros are exact); a pair's
    absent partner is a zero block."""
    zero = jnp.zeros_like(parts[0])
    a, b = (list(parts) + [zero])[:2]
    return jnp.concatenate([_beside([a, zero]), _beside([zero, b])], axis=0)


def _head_rows(q, k, kb, vb, g):
    """What ONE head brings to its chunk's products, each [rows, d] float32.
    q, k: [CHUNK, d]; kb, vb: ``beta * k``, ``beta * v``; g: the sum of ``a``
    from each row's SUB-chunk's start through the row, float32."""
    f32 = jnp.float32
    n, d = CHUNK // SUB, q.shape[-1]
    q, k, kb, vb = (t.astype(f32) for t in (q, k, kb, vb))
    zero = jnp.zeros((1, d), f32)
    ends = [g[SUB * i + SUB - 1:SUB * i + SUB] for i in range(n)]
    before = [zero]                     # the sum of the sub-chunks before i
    for i in range(n - 1):
        before.append(before[-1] + ends[i])
    g_chunk = g + _rows_of_subs(before, d)        # from the chunk's start
    g_last = before[-1] + ends[-1]                # [1, d]: the whole chunk
    # a diagonal block factors about its sub-chunk's MIDDLE row: e^40 either
    # way, where e^-80 about its start would push a small q or k under the
    # smallest float32 and the e^80 beside it would bring nothing back
    g_mid = g - _rows_of_subs(
        [g[SUB * i + SUB // 2 - 1:SUB * i + SUB // 2] for i in range(n)], d)

    def rows(log_decay, first=0):       # kb over q, from row ``first`` on
        decay = jnp.exp(log_decay[first:])
        return [kb[first:] * decay, q[first:] * decay]

    # a block ``apart`` sub-chunks below the diagonal: the row's decay from
    # its sub-chunk's start, times the whole sub-chunks between, against the
    # column's from its row to ITS sub-chunk's end. The first ``apart``
    # sub-chunks of rows have no such block and are not multiplied
    below, between = [], jnp.zeros_like(g)
    for apart in range(1, n):
        below += rows(g + between, apart * SUB)
        between = between + _rows_of_subs([zero] * apart + ends[:-apart], d)
    return dict(
        same=jnp.concatenate(rows(g_mid), axis=0),
        same_k=k * jnp.exp(-g_mid),
        below=jnp.concatenate(below, axis=0),
        below_k=k * jnp.exp(_rows_of_subs(ends, d) - g),
        k_in=kb * jnp.exp(g_chunk), vb=vb, q_in=q * jnp.exp(g_chunk),
        k_out=k * jnp.exp(g_last - g_chunk), decay=jnp.exp(g_last))


def _chunk_pair(heads, mxu):
    """One chunk of a PAIR of heads (or of one: its partner is then a zero
    block of the same products). ``heads``: a (q, k, kb, vb, g, state) each,
    as ``_head_rows`` takes them, and state: [d_v, d_k] float32 (the decay
    then runs ALONG a row of it). A [CHUNK, CHUNK] matrix of the chunk lives
    as the pair's ``[x_a | x_b]``, [CHUNK, 2 CHUNK]: whole lanes, and one
    product against a ``_blockdiag`` is both heads'. A generator: it yields
    between its stages, so that the pairs of a grid step can be taken a
    stage each in turn (``_in_lockstep``), and RETURNS an (o [CHUNK, d]
    float32, the state after the chunk) a head."""
    f32 = jnp.float32
    n, wide = CHUNK // SUB, 2 * CHUNK
    states = [head[5] for head in heads]
    parts = [_head_rows(*head[:5]) for head in heads]
    d = states[0].shape[-1]
    yield

    def of(name):
        return [part[name] for part in parts]

    same = _dot(_beside(of("same")), _blockdiag(of("same_k")), _NT, mxu)
    below = _dot(_beside(of("below")), _blockdiag(of("below_k")), _NT, mxu)
    yield
    # masks from whole iotas: Mosaic aborts on a slice of one and on ``%``
    row = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, wide), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, wide), 1)
    left = lane < CHUNK
    col = jnp.where(left, lane, lane - CHUNK)
    apart = row // SUB - col // SUB

    def pairs(half, under):                      # half 0: kb's rows, 1: q's
        out = jnp.where((apart == 0) & under, same[half * CHUNK:][:CHUNK], 0.0)
        first = 0
        for i in range(1, n):                    # rows i SUB.. of ``below``
            rows = CHUNK - i * SUB
            block = jnp.concatenate(
                [jnp.zeros((i * SUB, wide), f32),
                 below[first + half * rows:][:rows]], axis=0)
            out, first = jnp.where(apart == i, block, out), first + 2 * rows
        return out

    lower, a_q = pairs(0, row > col), pairs(1, row >= col)  # Diag(beta) A, Aq
    chain = _dot if mxu == f32 else _dot_split

    # (I + lower)^-1. First its diagonal blocks, (I - N)(I + N^2)(I + N^4)
    # (I + N^8) each (N^16 = 0): the pair's 2 n blocks side by side, [SUB, 2
    # CHUNK], against the blockdiag of as many; a product that shares its
    # right operand with another is stacked on the other's rows
    diag = jnp.where(apart == 0, lower, 0.0)
    small = sum(diag[SUB * i:][:SUB] for i in range(n))
    own = jax.lax.broadcasted_iota(jnp.int32, (wide, wide), 0) // SUB \
        == jax.lax.broadcasted_iota(jnp.int32, (wide, wide), 1) // SUB

    def blocks(x, y):
        return chain(x, jnp.where(
            own, jnp.concatenate([y] * (2 * n), axis=0), 0.0))

    inv = (jax.lax.broadcasted_iota(jnp.int32, (SUB, wide), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (SUB, wide), 1) & (SUB - 1)
           ).astype(f32) - small
    power = blocks(small, small)
    yield
    for _ in range(2):
        both = blocks(jnp.concatenate([power, inv], axis=0), power)
        power, inv = both[:SUB], inv + both[SUB:]
        yield
    inv = inv + blocks(inv, power)
    inv = jnp.where(apart == 0, jnp.concatenate([inv] * n, axis=0), 0.0)
    yield

    # then the block rows: (I + m)^-1 = (I + m^2)(I - m), m^4 = 0. m is
    # strictly block-lower: its first sub-chunk of rows is zero, and m^2's
    # first two, and are not multiplied
    def rows_from(first, x, y):        # x[first:] @ blockdiag(y_a, y_b)
        y = jnp.concatenate([jnp.where(left, y, 0.0),
                             jnp.where(left, 0.0, y)], axis=0)
        return jnp.concatenate(
            [jnp.zeros((first, wide), f32), chain(x[first:], y)], axis=0)

    m = rows_from(SUB, inv, lower - diag)
    yield
    inv, m2 = inv - rows_from(SUB, m, inv), rows_from(2 * SUB, m, m)
    yield
    inv = inv + rows_from(2 * SUB, m2, inv)
    yield
    # what the chunk's rows write, and its outputs
    wu = _dot(inv, _blockdiag([jnp.concatenate([part["k_in"], part["vb"]],
                                               axis=1) for part in parts]),
              _NN, mxu)                                      # [C, 2 x 2 d]
    yield
    us = [wu[:, (2 * i + 1) * d:(2 * i + 2) * d]
          - _dot(wu[:, 2 * i * d:(2 * i + 1) * d], state, _NT, mxu)
          for i, state in enumerate(states)]
    yield
    o = _dot(a_q, _blockdiag(us), _NN, mxu)                  # [C, 2 d]
    return [(_dot(part["q_in"], state, _NT, mxu) + o[:, i * d:(i + 1) * d],
             part["decay"] * state + _dot(u, part["k_out"], _TN, mxu))
            for i, (part, state, u) in enumerate(zip(parts, states, us))]


def _in_lockstep(bodies):
    """Take generators of equally many stages a stage each in turn; what
    they return. The scheduler follows the order a kernel was traced in: of
    chains traced one after another it runs one after another and waits for
    each product; traced in turn, one pair's product fills another's wait."""
    bodies, returned = list(bodies), []
    while not returned:
        for body in bodies:
            try:
                next(body)
            except StopIteration as end:
                returned.append(end.value)
    return returned


def _chunk_kernel(len_ref, q_ref, k_ref, kb_ref, vb_ref, g_ref, s0_ref,
                  o_ref, s_ref, *, heads: int, mxu):
    # q_ref .. g_ref, o_ref: [1, heads, CHUNK, d]; s0_ref, s_ref:
    # [1, heads, d_v, d_k]. s_ref's block is the same over the chunk axis of
    # the grid: it IS the resident state
    chunk = pl.program_id(2)

    @pl.when(chunk == 0)
    def _():
        s_ref[...] = s0_ref[...]

    live = chunk * CHUNK < len_ref[pl.program_id(0)]

    @pl.when(live)
    def _():
        pairs = [range(first, min(first + 2, heads))
                 for first in range(0, heads, 2)]
        done = _in_lockstep(_chunk_pair(
            [(q_ref[0, h], k_ref[0, h], kb_ref[0, h], vb_ref[0, h],
              g_ref[0, h], s_ref[0, h]) for h in pair], mxu) for pair in pairs)
        for pair, pair_done in zip(pairs, done):
            for h, (o, state) in zip(pair, pair_done):
                o_ref[0, h] = o.astype(o_ref.dtype)
                s_ref[0, h] = state

    @pl.when(jnp.logical_not(live))
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("impl",))
def kda_prefill(q, k, v, a, beta, state0, lengths, *,
                impl: str = "auto") -> Tuple[jax.Array, jax.Array]:
    """q, k, v: [B, S, H, D] (q scaled and k normalised by the caller); a:
    [B, S, H, D] float32, the logarithm of the decay, at most 0 and at least
    -80 / ``SUB``; beta: [B, S, H]; state0: [B, H, D, D] float32, the state
    the rows start from, stored ``[d_v, d_k]``; lengths: [B] true lengths (S
    is the padded piece;
    0: the row is all padding and the state comes back as it was). Returns
    (o [B, S, H, D] in q's dtype, the state after each row's last real
    token). ``impl``: "pallas", "pallas_interpret", "reference" (the
    recurrence, a token a step), or "auto": the kernel on a TPU."""
    impl = _kda_impl(impl)
    bsz, s, h, d = q.shape
    real = (jnp.arange(s)[None, :] < lengths[:, None])[..., None]
    a = jnp.where(real[..., None], a.astype(jnp.float32), 0.0)
    beta = jnp.where(real, beta.astype(jnp.float32), 0.0)
    state0 = state0.astype(jnp.float32)
    if impl == "reference":
        o, state = kda_recurrence(q, k, v, a, beta, state0)
        return o.astype(q.dtype), state
    pad = -s % CHUNK
    if pad:  # a = 0 and beta = 0 there: the state stays
        q, k, v, a = (jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
                      for t in (q, k, v, a))
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    n = (s + pad) // CHUNK
    g = jnp.cumsum(a.reshape(bsz, -1, SUB, h, d), axis=2).reshape(a.shape)
    scaled = beta[..., None]
    heads_first = [t.transpose(0, 2, 1, 3) for t in (
        q, k, (scaled * k).astype(k.dtype), (scaled * v).astype(v.dtype), g)]
    hb = next(n for n in (2 * HEADS, HEADS, 1) if h % n == 0)
    mxu = jnp.float32 if q.dtype == jnp.float32 else jnp.bfloat16
    rows = pl.BlockSpec((1, hb, CHUNK, d), lambda i, j, c, _n: (i, j, c, 0))
    held = pl.BlockSpec((1, hb, d, d), lambda i, j, c, _n: (i, j, 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_chunk_kernel, heads=hb, mxu=mxu),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(bsz, h // hb, n),
            in_specs=[rows] * 5 + [held], out_specs=[rows, held]),
        out_shape=[jax.ShapeDtypeStruct((bsz, h, s + pad, d), q.dtype),
                   jax.ShapeDtypeStruct(state0.shape, jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="kda_chunk_fwd",
        interpret=impl == "pallas_interpret",
    )(lengths.astype(jnp.int32), *heads_first, state0)
    return o.transpose(0, 2, 1, 3)[:, :s], state


# --------------------------------------------------------------------------- #
# Decode: one token a slot
# --------------------------------------------------------------------------- #
def _step_kernel(beta_ref, q_ref, k_ref, v_ref, a_ref, s_in, o_ref, s_out, *,
                 heads: int, mxu):
    # beta_ref: [B, H] in SMEM; q_ref, k_ref, v_ref, a_ref, o_ref: [1, heads,
    # d], a slot's tokens of ``heads`` heads, one tile; s_in, s_out: [1, 1,
    # heads, d, d], their states ([d_v, d_k]: the decay then runs ALONG a
    # row, a row vector over every row) in one layer of the whole state,
    # which the call aliases. A head's rows are taken by a mask and not by a
    # slice: every product then has 8 rows, one of them the head's, and the
    # outputs of the heads add up to the tile
    slot, first = pl.program_id(0), pl.program_id(1) * heads
    f32 = jnp.float32
    q, k, v, a = (r[0].astype(f32) for r in (q_ref, k_ref, v_ref, a_ref))
    mine = jax.lax.broadcasted_iota(jnp.int32, q.shape, 0)
    out = jnp.zeros(q.shape, f32)
    for h in range(heads):
        only = mine == h
        kh = jnp.where(only, k, 0.0)
        state = jnp.exp(a[h:h + 1]) * s_in[0, 0, h]
        write = beta_ref[slot, first + h] * (
            jnp.where(only, v, 0.0) - _dot(kh, state, _NT, mxu))
        state = state + _dot(write, kh, _TN, mxu)
        s_out[0, 0, h] = state
        out = out + _dot(jnp.where(only, q, 0.0), state, _NT, mxu)
    o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("layer", "impl"))
def kda_step(q, k, v, a, beta, state, *, layer: int = 0,
             impl: str = "auto") -> Tuple[jax.Array, jax.Array]:
    """One token a slot, in place. q, k, v: [B, H, D]; a: [B, H, D] float32,
    the logarithm of the decay (0 for a slot that must not move); beta:
    [B, H] (0 for such a slot); state: [L, rows >= B, H, D, D] float32
    (``[d_v, d_k]`` a head), EVERY layer's state of every slot (and a trash
    row): the call moves rows ``[0,
    B)`` of layer ``layer`` and hands the whole array back, aliased, as
    ``ops/ssm.py`` ``mamba1_step`` does. Returns (o [B, H, D] in q's dtype,
    state)."""
    impl = _kda_impl(impl)
    bsz, h, d = q.shape
    a, beta = a.astype(jnp.float32), beta.astype(jnp.float32)
    if impl == "reference":
        o, moved = kda_recurrence(q[:, None], k[:, None], v[:, None],
                                  a[:, None], beta[:, None],
                                  state[layer, :bsz])
        return o[:, 0].astype(q.dtype), state.at[layer, :bsz].set(moved)
    hb = HEADS if h % HEADS == 0 else h
    mxu = jnp.float32 if q.dtype == jnp.float32 else jnp.bfloat16
    token = pl.BlockSpec((1, hb, d), lambda i, j: (i, j, 0))
    slot = pl.BlockSpec((1, 1, hb, d, d), lambda i, j: (layer, i, j, 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_step_kernel, heads=hb, mxu=mxu),
        grid=(bsz, h // hb),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  token, token, token, token, slot],
        out_specs=[token, slot],
        out_shape=[jax.ShapeDtypeStruct((bsz, h, d), q.dtype),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        name="kda_step",
        interpret=impl == "pallas_interpret",
    )(beta, q, k, v, a, state)
    return o, state
