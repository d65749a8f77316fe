"""ViT: vision transformer for the image-pipeline -> TPU config class.

Reference capability: the reference orchestrates external vision models
(BASELINE "ViT-L/CLIP image pipeline -> TPU"); here the model is native so
ray_tpu.data image pipelines have a first-class TPU training target.
TPU-first choices mirror models/llama.py:

- patchify is a RESHAPE + one dense matmul (no conv op): [B, Hi, Wi, 3] ->
  [B, N, P*P*3] @ patch_embed — the whole embedding rides the MXU;
- encoder layers are weight-STACKED [L, ...] and driven by one lax.scan
  (single compiled layer body, no Python-unrolled graph bloat);
- pre-RMSNorm blocks with non-causal attention via the shared ops
  (flash kernel on TPU, reference path on CPU meshes);
- mean-pool head (no CLS token): pooling is a reduce, classification one
  matmul.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import attention
from ray_tpu.ops.norms import rms_norm


@dataclass(frozen=True)
class ViTConfig:
    image_size: int = 224
    patch_size: int = 16
    num_channels: int = 3
    hidden_size: int = 768
    intermediate_size: int = 3072
    num_layers: int = 12
    num_heads: int = 12
    num_classes: int = 1000
    dtype: Any = jnp.bfloat16
    attention_impl: str = "auto"   # auto|flash|reference
    rms_eps: float = 1e-6

    @property
    def num_patches(self) -> int:
        return (self.image_size // self.patch_size) ** 2

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def num_params(self) -> int:
        h, f, L = self.hidden_size, self.intermediate_size, self.num_layers
        patch = self.patch_size ** 2 * self.num_channels * h
        per_layer = 4 * h * h + 2 * h * f + 2 * h
        return (patch + self.num_patches * h + L * per_layer + h
                + h * self.num_classes)

    @classmethod
    def tiny(cls, **kw) -> "ViTConfig":
        return cls(image_size=32, patch_size=8, hidden_size=64,
                   intermediate_size=128, num_layers=2, num_heads=4,
                   num_classes=10, dtype=jnp.float32,
                   attention_impl="reference", **kw)

    @classmethod
    def vit_l(cls, **kw) -> "ViTConfig":
        """ViT-L/16 (the BASELINE image-pipeline config class)."""
        return cls(hidden_size=1024, intermediate_size=4096, num_layers=24,
                   num_heads=16, **kw)


def vit_init(config: ViTConfig, key) -> Dict[str, Any]:
    h, f, L = config.hidden_size, config.intermediate_size, config.num_layers
    patch_dim = config.patch_size ** 2 * config.num_channels
    dt = config.dtype
    keys = jax.random.split(key, 8)

    def normal(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(dt)

    return {
        "patch_embed": normal(keys[0], (patch_dim, h), patch_dim),
        "pos_embed": (jax.random.normal(keys[1], (config.num_patches, h),
                                        jnp.float32) * 0.02).astype(dt),
        "layers": {
            "attn_norm": jnp.ones((L, h), dt),
            "wq": normal(keys[2], (L, h, h), h),
            "wk": normal(keys[3], (L, h, h), h),
            "wv": normal(keys[4], (L, h, h), h),
            "wo": normal(keys[5], (L, h, h), h),
            "mlp_norm": jnp.ones((L, h), dt),
            "w_up": normal(keys[6], (L, h, f), h),
            "w_down": normal(keys[7], (L, f, h), f),
        },
        "final_norm": jnp.ones((h,), dt),
        "head": normal(jax.random.fold_in(key, 99), (h, config.num_classes), h),
    }


def _attention(config: ViTConfig, q, k, v):
    return attention(q, k, v, causal=False, impl=config.attention_impl)


def _layer(config: ViTConfig, x, lp):
    b, n, h = x.shape
    nh, d = config.num_heads, config.head_dim
    y = rms_norm(x, lp["attn_norm"], config.rms_eps)
    q = (y @ lp["wq"]).reshape(b, n, nh, d)
    k = (y @ lp["wk"]).reshape(b, n, nh, d)
    v = (y @ lp["wv"]).reshape(b, n, nh, d)
    a = _attention(config, q, k, v).reshape(b, n, h)
    x = x + a @ lp["wo"]
    y = rms_norm(x, lp["mlp_norm"], config.rms_eps)
    x = x + jax.nn.gelu(y @ lp["w_up"]) @ lp["w_down"]
    return x


def patchify(config: ViTConfig, images) -> jax.Array:
    """[B, Hi, Wi, C] -> [B, N, P*P*C] by pure reshape/transpose."""
    b = images.shape[0]
    p = config.patch_size
    g = config.image_size // p
    x = images.reshape(b, g, p, g, p, config.num_channels)
    x = x.transpose(0, 1, 3, 2, 4, 5)  # B, g, g, p, p, C
    return x.reshape(b, g * g, p * p * config.num_channels)


def vit_forward(params: Dict[str, Any], images, config: ViTConfig) -> jax.Array:
    """images: [B, Hi, Wi, C] float -> logits [B, num_classes] (fp32)."""
    x = patchify(config, images.astype(config.dtype)) @ params["patch_embed"]
    x = x + params["pos_embed"][None]
    layer_fn = functools.partial(_layer, config)

    def scan_body(carry, lp):
        return layer_fn(carry, lp), None

    x, _ = jax.lax.scan(scan_body, x, params["layers"])
    x = rms_norm(x, params["final_norm"], config.rms_eps)
    pooled = x.mean(axis=1)
    return (pooled @ params["head"]).astype(jnp.float32)


def vit_loss(params: Dict[str, Any], images, labels,
             config: ViTConfig) -> jax.Array:
    """Mean softmax cross-entropy over [B] int labels."""
    logits = vit_forward(params, images, config)
    logp = jax.nn.log_softmax(logits)
    gold = jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]
    return -gold.mean()


def make_vit_train_step(config: ViTConfig, optimizer):
    """One jitted fwd+bwd+update step; returns (step_fn, init_fn)."""
    import optax

    def init(key):
        params = vit_init(config, key)
        return params, optimizer.init(params)

    @jax.jit
    def step(params, opt_state, images, labels):
        loss, grads = jax.value_and_grad(vit_loss)(params, images, labels,
                                                   config)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return step, init
