"""Mamba-2 (state-space duality) mixer core: the recurrence

    S_t = exp(dt_t * A) * S_{t-1} + dt_t * X_t (x) B_t        S: [H, P, N]
    Y_t = S_t C_t + D * X_t

for a prompt (``mamba2_prefill``, chunked) and for one token a slot
(``mamba2_step``), and the causal depthwise convolution in front of it.

``dt``, ``A``, the decay and ``S`` are float32: the state is a sum over
thousands of steps. ``X``, ``B``, ``C`` come in the model's dtype and enter
the products as they are, accumulated in float32.

The serving engine pads a prefill batch to a few rows x bucket. A position
at or past a row's true length takes ``dt = 0``: the decay is then 1 and the
input 0, so ``S`` stays what the last real token left, and the convolution
rows kept for decode are the last REAL inputs. What the padded positions
output is never read.

Plain ``jax.numpy`` / ``lax``: XLA fuses the elementwise state update and
runs the chunk products on the MXU. A Pallas scan is the next step only if a
trace shows these far from their roofline (PERF.md section 7).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


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
