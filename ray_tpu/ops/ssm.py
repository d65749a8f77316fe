"""State-space mixer cores, and the causal depthwise convolution in front of
them.

Mamba-2 (state-space duality): one scalar decay a head,

    S_t = exp(dt_t * A) * S_{t-1} + dt_t * X_t (x) B_t        S: [H, P, N]
    Y_t = S_t C_t + D * X_t

for a prompt (``mamba2_prefill``, chunked matrix products) and for one token
a slot (``mamba2_step``).

Mamba-1 (the selective scan): a decay per CHANNEL AND STATE,

    S_t = exp(dt_t (x) A) * S_{t-1} + (dt_t * x_t) (x) B_t    S: [Din, N]
    y_t = S_t C_t + D * x_t                                   A: [Din, N]

so no chunk of it is a matrix product: ``mamba1_prefill`` is a Pallas kernel
that walks time with the state resident, ``mamba1_step`` one that moves every
slot's state by one token in place. Their state is laid out for the lanes,
``[N, Din // 128, 128]`` (state ``n`` of channel ``128 i + j`` at ``[n, i,
j]``): a ``[Din, N]`` array keeps 16 of a tile's 128 lanes.

``dt``, ``A``, the decay and ``S`` are float32: the state is a sum over
thousands of steps. ``X``, ``B``, ``C`` come in the model's dtype and enter
the Mamba-2 products as they are, accumulated in float32; the selective scan
is elementwise and takes them as float32.

The serving engine pads a prefill batch to a few rows x bucket. A position
at or past a row's true length takes ``dt = 0``: the decay is then 1 and the
input 0, so ``S`` stays what the last real token left, and the convolution
rows kept for decode are the last REAL inputs. What the padded positions
output is never read.

The Mamba-2 pair is plain ``jax.numpy`` / ``lax``: XLA fuses the elementwise
state update and runs the chunk products on the MXU.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# time steps of a prompt the selective scan takes a grid step: x, dt and y
# blocks of [256, 8, 128] float32 are 1 MB each, double buffered
SCAN_BLOCK_T = 256
# 128-lane rows of channels a grid step holds: 8 make a float32 vreg of each
# state index, 16 vregs of state for the 1,024 channels
SCAN_BLOCK_C = 8


def _heads_of_groups(t, heads: int):
    """[..., G, N] -> [..., H, N]: head h uses group h // (H / G)."""
    return jnp.repeat(t, heads // t.shape[-2], axis=-2)


def mamba2_prefill(x, dt, a, b, c, d_skip, state0, lengths, *,
                   chunk: int = 128) -> Tuple[jax.Array, jax.Array]:
    """x: [B, S, H, P]; dt: [B, S, H] float32, after softplus; a: [H]
    float32, negative; b, c: [B, S, G, N]; d_skip: [H]; state0: [B, H, P, N]
    float32; lengths: [B] true lengths (S is the padded bucket).
    Returns (y [B, S, H, P] in x's dtype, S after each row's last real token).

    One ``lax.scan`` over chunks of ``chunk`` positions carrying S. Inside a
    chunk, with ``cs`` the running sum of ``dt * A``:
    ``Y_t = sum_{s<=t} (C_t . B_s) exp(cs_t - cs_s) dt_s X_s  +  exp(cs_t) C_t S_in``
    and ``S_out = exp(cs_last) S_in + sum_s exp(cs_last - cs_s) dt_s X_s (x) B_s``.
    Every exponent is <= 0."""
    bsz, s, h, p = x.shape
    pad = -s % chunk
    if pad:
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                       for t in (x, dt, b, c))
    nc = (s + pad) // chunk
    real = jnp.arange(s + pad)[None, :] < lengths[:, None]
    dt = jnp.where(real[..., None], dt.astype(jnp.float32), 0.0)

    def chunks(t):  # [B, S, ...] -> [nc, B, chunk, ...]
        return jnp.moveaxis(t.reshape(bsz, nc, chunk, *t.shape[2:]), 1, 0)

    causal = jnp.tril(jnp.ones((chunk, chunk), bool))

    def one(state, part):
        xc, dtc, bc, cc = part
        cs = jnp.cumsum(dtc * a, axis=1)                        # [B, Q, H]
        bh, ch = _heads_of_groups(bc, h), _heads_of_groups(cc, h)
        scores = jnp.einsum("bthn,bshn->bhts", ch, bh,
                            preferred_element_type=jnp.float32)
        cst = cs.transpose(0, 2, 1)                              # [B, H, Q]
        # masked before the exponential: above the diagonal it is positive
        decay = jnp.exp(jnp.where(causal, cst[..., :, None] - cst[..., None, :],
                                  -jnp.inf))
        xdt = xc.astype(jnp.float32) * dtc[..., None]            # [B, Q, H, P]
        y = jnp.einsum("bhts,bshp->bthp", scores * decay, xdt)
        y += jnp.einsum("bthn,bhpn->bthp", ch.astype(jnp.float32), state) \
            * jnp.exp(cs)[..., None]
        last = cs[:, -1]                                         # [B, H]
        left = jnp.exp(last[:, None] - cs)                       # [B, Q, H]
        state = state * jnp.exp(last)[..., None, None] + jnp.einsum(
            "bshp,bshn->bhpn", xdt * left[..., None], bh.astype(jnp.float32))
        y += xc.astype(jnp.float32) * d_skip[:, None]
        return state, y.astype(x.dtype)

    state, y = jax.lax.scan(
        one, state0.astype(jnp.float32),
        (chunks(x), chunks(dt), chunks(b), chunks(c)))
    y = jnp.moveaxis(y, 0, 1).reshape(bsz, nc * chunk, h, p)
    return y[:, :s], state


def mamba2_step(x, dt, a, b, c, d_skip, state) -> Tuple[jax.Array, jax.Array]:
    """One token a row. x: [B, H, P]; dt: [B, H] float32 after softplus (0
    for a row that must not move); a: [H]; b, c: [B, G, N]; state:
    [B, H, P, N] float32. Returns (y [B, H, P] in x's dtype, new state)."""
    h = x.shape[1]
    bh = _heads_of_groups(b, h).astype(jnp.float32)
    ch = _heads_of_groups(c, h).astype(jnp.float32)
    x32 = x.astype(jnp.float32)
    state = state * jnp.exp(dt * a)[..., None, None] \
        + (x32 * dt[..., None])[..., None] * bh[:, :, None, :]
    y = jnp.einsum("bhpn,bhn->bhp", state, ch) + x32 * d_skip[:, None]
    return y.astype(x.dtype), state


def causal_conv_prefill(x, weight, bias, lengths):
    """Depthwise causal convolution over the sequence. x: [B, S, C]; weight:
    [K, C] (tap k multiplies the input K-1-k positions back); bias: [C].
    Returns (y [B, S, C], the last K-1 REAL input rows [B, K-1, C], zeros
    where a row is shorter than that)."""
    k = weight.shape[0]
    s = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    w32 = weight.astype(jnp.float32)
    y = sum(padded[:, j:j + s].astype(jnp.float32) * w32[j] for j in range(k))
    y = (y + bias.astype(jnp.float32)).astype(x.dtype)
    # padded row (length + j) is input row (length - (K-1) + j)
    rows = lengths[:, None] + jnp.arange(k - 1)[None, :]
    kept = jnp.take_along_axis(padded, rows[..., None], axis=1)
    return y, kept


def causal_conv_step(x, kept, weight, bias):
    """x: [B, C] the new input row; kept: [B, K-1, C] the rows before it.
    Returns (y [B, C], the window moved on by one row)."""
    window = jnp.concatenate([kept, x[:, None].astype(kept.dtype)], axis=1)
    y = jnp.einsum("bkc,kc->bc", window.astype(jnp.float32),
                   weight.astype(jnp.float32)) + bias.astype(jnp.float32)
    return y.astype(x.dtype), window[:, 1:]


# --------------------------------------------------------------------------- #
# Mamba-1: the selective scan
# --------------------------------------------------------------------------- #
def lanes(t):
    """[..., Din] -> [..., Din // 128, 128], the layout of the scan's state
    and of what its kernels read a vreg at a time."""
    return t.reshape(*t.shape[:-1], t.shape[-1] // LANES, LANES)


def _scan_impl(impl: str) -> str:
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "reference"
    if impl not in ("pallas", "pallas_interpret", "reference"):
        raise ValueError(f"unknown scan impl {impl!r}")
    return impl


def _channel_block(rows: int) -> int:
    return SCAN_BLOCK_C if rows % SCAN_BLOCK_C == 0 else 1


def _scan_fwd_kernel(b_ref, c_ref, x_ref, dt_ref, a_ref, d_ref, s0_ref,
                     y_ref, s_ref, *, block_t: int, n_state: int):
    # b_ref, c_ref: [1, block_t * N] in SMEM, scalars; x_ref, dt_ref, y_ref:
    # [1, block_t, cb, 128]; a_ref: [N, cb, 128]; d_ref: [cb, 128]; s0_ref,
    # s_ref: [1, N, cb, 128]. s_ref's block is the same over the time axis
    # of the grid: it IS the resident state, written back when the channel
    # block or the row changes
    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = s0_ref[...]

    a = a_ref[...]
    d_skip = d_ref[...]

    def step(t, state):
        x, dt = x_ref[0, t], dt_ref[0, t]
        dtx = dt * x
        y = d_skip * x
        moved = []
        for n in range(n_state):
            s = jnp.exp(dt * a[n]) * state[n] + dtx * b_ref[0, t * n_state + n]
            y = y + s * c_ref[0, t * n_state + n]
            moved.append(s)
        y_ref[0, t] = y
        return tuple(moved)

    state = jax.lax.fori_loop(
        0, block_t, step, tuple(s_ref[0, n] for n in range(n_state)))
    for n in range(n_state):
        s_ref[0, n] = state[n]


def _scan_reference(x, dt, a, b, c, d_skip, state0):
    """The recurrence as a ``lax.scan`` over time, a token a step (CPU tests
    and backends without the kernel). Shapes as ``mamba1_prefill``'s, dt
    already masked."""
    a4, d4 = lanes(a.T), lanes(d_skip.astype(jnp.float32))

    def step(state, part):
        xt, dtt, bt, ct = part                       # [B, C, 128], [B, N]
        state = jnp.exp(dtt[:, None] * a4) * state \
            + (dtt * xt)[:, None] * bt[:, :, None, None]
        y = jnp.sum(state * ct[:, :, None, None], axis=1) + d4 * xt
        return state, y

    time_major = [jnp.moveaxis(t, 1, 0) for t in (
        lanes(x.astype(jnp.float32)), lanes(dt), b.astype(jnp.float32),
        c.astype(jnp.float32))]
    state, y = jax.lax.scan(step, state0, time_major)
    return jnp.moveaxis(y, 0, 1).reshape(x.shape), state


@functools.partial(jax.jit, static_argnames=("impl",))
def mamba1_prefill(x, dt, a, b, c, d_skip, state0, lengths, *,
                   impl: str = "auto") -> Tuple[jax.Array, jax.Array]:
    """x: [B, S, Din]; dt: [B, S, Din] float32, after softplus; a: [Din, N]
    float32, negative; b, c: [B, S, N]; d_skip: [Din]; state0:
    [B, N, Din // 128, 128] float32 (``lanes``); lengths: [B] true lengths (S
    is the padded bucket). Returns (y [B, S, Din] in x's dtype, the state
    after each row's last real token, laid out as state0).

    The kernel (``selective_scan_fwd`` in a profile) walks a row's time in
    blocks of ``SCAN_BLOCK_T`` steps for 1,024 channels at a time; the state
    of those channels, 16 vregs, never leaves VMEM, ``B_t`` and ``C_t`` are
    scalars from SMEM, and a step is 16 x (exp, 4 multiplies, 2 adds) over a
    vreg. Nothing [S, Din, N] exists. ``impl``: "pallas", "pallas_interpret",
    "reference" (a ``lax.scan`` a token), or "auto": the kernel on a TPU."""
    impl = _scan_impl(impl)
    bsz, s, din = x.shape
    n = a.shape[1]
    real = jnp.arange(s)[None, :] < lengths[:, None]
    dt = jnp.where(real[..., None], dt.astype(jnp.float32), 0.0)
    state0 = state0.astype(jnp.float32)
    if impl == "reference":
        y, state = _scan_reference(x, dt, a, b, c, d_skip, state0)
        return y.astype(x.dtype), state
    block_t = min(SCAN_BLOCK_T, -(-s // 8) * 8)
    pad = -s % block_t
    x32, b32, c32 = (t.astype(jnp.float32) for t in (x, b, c))
    if pad:  # dt = 0 there: the state stays
        x32, dt, b32, c32 = (jnp.pad(t, ((0, 0), (0, pad), (0, 0)))
                             for t in (x32, dt, b32, c32))
    steps = (s + pad) // block_t
    rows = din // LANES
    cb = _channel_block(rows)
    scalars = pl.BlockSpec((1, block_t * n), lambda i, j, k: (i, k),
                           memory_space=pltpu.SMEM)
    seq = pl.BlockSpec((1, block_t, cb, LANES), lambda i, j, k: (i, k, j, 0))
    held = pl.BlockSpec((1, n, cb, LANES), lambda i, j, k: (i, 0, j, 0))
    y, state = pl.pallas_call(
        functools.partial(_scan_fwd_kernel, block_t=block_t, n_state=n),
        grid=(bsz, rows // cb, steps),
        in_specs=[scalars, scalars, seq, seq,
                  pl.BlockSpec((n, cb, LANES), lambda i, j, k: (0, j, 0)),
                  pl.BlockSpec((cb, LANES), lambda i, j, k: (j, 0)), held],
        out_specs=[seq, held],
        out_shape=[jax.ShapeDtypeStruct((bsz, s + pad, rows, LANES), jnp.float32),
                   jax.ShapeDtypeStruct(state0.shape, jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="selective_scan_fwd",
        interpret=impl == "pallas_interpret",
    )(b32.reshape(bsz, -1), c32.reshape(bsz, -1), lanes(x32), lanes(dt),
      lanes(a.T.astype(jnp.float32)), lanes(d_skip.astype(jnp.float32)), state0)
    return y.reshape(bsz, s + pad, din)[:, :s].astype(x.dtype), state


def _scan_step_kernel(b_ref, c_ref, x_ref, dt_ref, a_ref, d_ref, s_in, y_ref,
                      s_out, *, n_state: int):
    # b_ref, c_ref: [B, N] in SMEM; x_ref, dt_ref, y_ref: [1, C, 128]; a_ref:
    # [N, C, 128]; d_ref: [C, 128]; s_in, s_out: [1, 1, N, C, 128], one slot
    # of one layer of the whole state, which the call aliases
    i = pl.program_id(0)
    x, dt = x_ref[0], dt_ref[0]
    dtx = dt * x
    y = d_ref[...] * x
    for n in range(n_state):
        s = jnp.exp(dt * a_ref[n]) * s_in[0, 0, n] + dtx * b_ref[i, n]
        y = y + s * c_ref[i, n]
        s_out[0, 0, n] = s
    y_ref[0] = y


@functools.partial(jax.jit, static_argnames=("layer", "impl"))
def mamba1_step(x, dt, a, b, c, d_skip, state, *, layer: int = 0,
                impl: str = "auto") -> Tuple[jax.Array, jax.Array]:
    """One token a slot, in place. x: [B, Din]; dt: [B, Din] float32 after
    softplus (0 for a slot that must not move); a: [Din, N]; b, c: [B, N];
    state: [L, rows >= B, N, Din // 128, 128] float32, EVERY scan layer's
    state of every slot (and a trash row): the call moves rows ``[0, B)`` of
    layer ``layer`` and hands the whole array back, aliased (a slice of it
    in and out would copy a layer's state twice a layer a tick). Returns
    (y [B, Din] in x's dtype, state).

    The kernel (``selective_scan_step`` in a profile) takes a slot a grid
    step: its 320 KB of state in, moved, out."""
    impl = _scan_impl(impl)
    bsz, din = x.shape
    n = a.shape[1]
    x32, dt = x.astype(jnp.float32), dt.astype(jnp.float32)
    if impl == "reference":
        y, moved = _scan_reference(x32[:, None], dt[:, None], a, b[:, None],
                                   c[:, None], d_skip, state[layer, :bsz])
        return y[:, 0].astype(x.dtype), state.at[layer, :bsz].set(moved)
    rows = din // LANES
    whole_smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    token = pl.BlockSpec((1, rows, LANES), lambda i: (i, 0, 0))
    slot = pl.BlockSpec((1, 1, n, rows, LANES), lambda i: (layer, i, 0, 0, 0))
    y, state = pl.pallas_call(
        functools.partial(_scan_step_kernel, n_state=n),
        grid=(bsz,),
        in_specs=[whole_smem, whole_smem, token, token,
                  pl.BlockSpec((n, rows, LANES), lambda i: (0, 0, 0)),
                  pl.BlockSpec((rows, LANES), lambda i: (0, 0)), slot],
        out_specs=[token, slot],
        out_shape=[jax.ShapeDtypeStruct((bsz, rows, LANES), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="selective_scan_step",
        interpret=impl == "pallas_interpret",
    )(b.astype(jnp.float32), c.astype(jnp.float32), lanes(x32), lanes(dt),
      lanes(a.T.astype(jnp.float32)), lanes(d_skip.astype(jnp.float32)), state)
    return y.reshape(bsz, din).astype(x.dtype), state
