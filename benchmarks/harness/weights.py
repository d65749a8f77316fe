"""Seeded weights, made on the device by ONE jitted program.

The benchmark makes the weights and hands them to the program, so the plain
reference (``reference.py``) can be given the same values without touching
anything the program computed. The arithmetic is ``models/llama.py``
``llama_init`` copied (normal / sqrt(fan_in), cast to the served dtype); the
program's eager version runs one program per weight, which PR 21 found to be
most of replica start-up.
"""

from __future__ import annotations

import json
from typing import Any, Dict


def seed_key(seed: int):
    """``--seed`` may exceed 31 bits; fold the high bits in instead of
    overflowing the int32 a PRNG key is made from."""
    import jax

    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def llama_config_from_file(cfg: Dict[str, Any], **overrides):
    """Build the program's ``LlamaConfig`` from a configuration file that
    uses the source's key names (``hidden_size``, ``num_hidden_layers`` ...)."""
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig

    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg["torch_dtype"]]
    fields = dict(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        max_seq_len=cfg["deployment"]["max_seq_len"],
        rope_theta=float(cfg["rope_theta"]), rms_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]), dtype=dtype,
        remat=cfg["deployment"].get("remat"),
        attention_impl=cfg["deployment"].get("attention_impl", "auto"),
    )
    fields.update(overrides)
    return LlamaConfig(**fields)


def load_config_file(path: str, rehearse: bool = False) -> Dict[str, Any]:
    with open(path) as f:
        cfg = json.load(f)
    if rehearse:
        # tiny widths for the CPU rehearsal; never a measurement
        tiny = cfg["rehearsal"]
        dep = {**cfg["deployment"], **tiny.pop("deployment", {})}
        cfg = {**cfg, **tiny, "deployment": dep}
    return cfg


def init_weights(config, key) -> Dict[str, Any]:
    """The param pytree ``models/llama.py`` expects. Trace it under ``jit``."""
    import jax
    import jax.numpy as jnp

    h, hd = config.hidden_size, config.head_dim_
    nh, nkv = config.num_heads, config.num_kv_heads
    f, L, dt = config.intermediate_size, config.num_layers, config.dtype
    keys = jax.random.split(key, 9)

    def normal(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(dt)

    params = {
        "embed_tokens": normal(keys[0], (config.vocab_size, h), h),
        "layers": {
            "attn_norm": jnp.ones((L, h), dt),
            "wq": normal(keys[1], (L, h, nh * hd), h),
            "wk": normal(keys[2], (L, h, nkv * hd), h),
            "wv": normal(keys[3], (L, h, nkv * hd), h),
            "wo": normal(keys[4], (L, nh * hd, h), nh * hd),
            "mlp_norm": jnp.ones((L, h), dt),
            "w_gate": normal(keys[5], (L, h, f), h),
            "w_up": normal(keys[6], (L, h, f), h),
            "w_down": normal(keys[7], (L, f, h), f),
        },
        "final_norm": jnp.ones((h,), dt),
    }
    if not config.tie_embeddings:
        params["lm_head"] = normal(keys[8], (h, config.vocab_size), h)
    return params


def make_weights(config, seed: int) -> Dict[str, Any]:
    """One jitted call from the seed, in the dtype the weights are served in."""
    import jax

    return jax.jit(lambda k: init_weights(config, k))(seed_key(seed))
