"""Normalization ops.

RMSNorm stays in jnp for the forward math (XLA fuses the reduce + rsqrt +
scale on TPU; a Pallas kernel buys nothing — HBM-bound either way), but it
carries a custom VJP: without one, autodiff saves the fp32 upcast `x32` AND
the fp32 normalized `y32` for the backward pass — two full [B, S, H] fp32
tensors per call (5.5 GB/step on the 1B bench config). The custom rule saves
only the bf16 inputs and recomputes the (cheap, vector-unit) stats in bwd.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _rms_forward(x, weight, eps):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    r = jax.lax.rsqrt(var + eps)
    xhat = x32 * r
    return xhat, r


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rms_norm(x, weight, eps: float):
    xhat, _ = _rms_forward(x, weight, eps)
    return (xhat * weight.astype(jnp.float32)).astype(x.dtype)


def _rms_norm_fwd(x, weight, eps):
    return _rms_norm(x, weight, eps), (x, weight)




def _rms_norm_bwd(eps, residuals, g):
    x, weight = residuals
    xhat, r = _rms_forward(x, weight, eps)
    g32 = g.astype(jnp.float32)
    # out = xhat * w  ->  d_w sums over all leading dims; d_xhat = g * w
    dw_axes = tuple(range(g.ndim - weight.ndim))
    dw = jnp.sum(g32 * xhat, axis=dw_axes).astype(weight.dtype)
    dxhat = g32 * weight.astype(jnp.float32)
    # xhat = x * r with r = rsqrt(mean(x^2) + eps):
    # dx = r * (dxhat - xhat * mean(dxhat * xhat, -1))
    m = jnp.mean(dxhat * xhat, axis=-1, keepdims=True)
    dx = (r * (dxhat - xhat * m)).astype(x.dtype)
    return dx, dw


_rms_norm.defvjp(_rms_norm_fwd, _rms_norm_bwd)


def rms_norm(x, weight, eps: float = 1e-6):
    return _rms_norm(x, weight, eps)


def layer_norm(x, weight, bias, eps: float = 1e-5):
    """LayerNorm over the last axis with weight and bias; statistics in
    float32, the result in x's dtype. Plain ``jnp``: XLA fuses it, as the RMS
    norm's forward."""
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    centred = x32 - mean
    var = jnp.mean(centred * centred, axis=-1, keepdims=True)
    y = centred * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)
