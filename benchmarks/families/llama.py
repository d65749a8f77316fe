"""The Llama family (Llama, Mistral: pre-norm RMSNorm, rotary GQA attention,
SwiGLU, dense) over ``ray_tpu.models.llama``, ``models/paged_decode.py`` and
``serve/llm.py``. ``families/__init__.py`` says what a family gives.

The weights are made here and handed to the program, so the plain reference
(``llama_reference.py``) can be given the same values without touching
anything the program computed. The arithmetic is ``models/llama.py``
``llama_init`` copied (normal / sqrt(fan_in), cast to the served dtype); the
program's eager version runs one program per weight, which PR 21 found to be
most of replica start-up.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmarks.families.llama_reference import (  # noqa: F401 - the surface
    make_gap_fn, make_greedy_fn, reference_logits, reference_loss)
from benchmarks.harness import roofline
from benchmarks.harness.trace_reduce import FLASH_CALL_ROWS
from benchmarks.harness.weights import seed_key

# the programs' names in a profile (``families/__init__.py``, the serve surface)
DECODE_MODULE = "^jit_paged_decode_steps"
PREFILL_MODULE = "^jit_paged_prefill"
# a prefill call holds several rows: the flash call's batch says how many
PREFILL_ROWS_FROM = FLASH_CALL_ROWS


# ------------------------------------------------------ configuration, weights
def program_config(cfg: Dict[str, Any]):
    """The program's ``LlamaConfig`` from a configuration file that uses the
    source's key names (``hidden_size``, ``num_hidden_layers`` ...)."""
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig

    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg["torch_dtype"]]
    return LlamaConfig(
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


# ---------------------------------------------------------------------- serve
def make_engine(config, params, deployment: Dict[str, Any]):
    from ray_tpu.serve.llm import LLMEngine

    dep = deployment
    return LLMEngine(
        config, params, num_slots=dep["num_slots"],
        max_seq_len=dep["max_seq_len"], decode_chunk=dep["decode_chunk"],
        prefill_buckets=dep["prefill_buckets"], paged=True,
        page_size=dep["page_size"], total_pages=dep["total_pages"])


def set_weights(engine, params) -> None:
    engine.params = params


def largest_prefill_rows(bucket: int) -> int:
    """The rows of the tallest prefill program the engine brings up for
    ``bucket``: the largest of its row counts that an iteration's budget of
    padded tokens can fill (``serve/llm.py`` ``_rows_of``; 4 for every bucket
    up to 2048 since PR 30)."""
    from ray_tpu.serve.llm import PREFILL_ROWS, PREFILL_TOKENS_PER_ITER

    fit = [r for r in PREFILL_ROWS if r * bucket <= PREFILL_TOKENS_PER_ITER]
    return max(fit) if fit else min(PREFILL_ROWS)


def serve_programs(config, deployment: Dict[str, Any]) -> Dict[str, Any]:
    """The decode chunk over all slots and the tallest prefill program of
    each bucket (``largest_prefill_rows``), as ``LLMEngine`` builds them on a
    TPU (the Pallas paged-attention kernel)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import paged_decode as pd

    dep, shape = deployment, jax.ShapeDtypeStruct
    slots, page = dep["num_slots"], dep["page_size"]
    params = jax.eval_shape(lambda k: init_weights(config, k), jax.random.key(0))
    cache = jax.eval_shape(
        lambda: pd.init_paged_cache(config, dep["total_pages"], page))
    ints = shape((slots,), jnp.int32)
    active = shape((slots,), jnp.bool_)
    table = shape((slots, dep["max_seq_len"] // page), jnp.int32)
    key = jax.eval_shape(lambda: jax.random.key(0))
    decode = pd.make_paged_decode_fn(config, dep["decode_chunk"], page,
                                     use_kernel=True)
    programs = [("decode", decode, (params, cache, ints, ints, active, table, key))]
    prefill = pd.make_paged_prefill_fn(config, page)
    for bucket in dep["prefill_buckets"]:
        rows = largest_prefill_rows(bucket)
        programs.append((f"prefill_{rows}x{bucket}", prefill, (
            params, cache, shape((rows, bucket), jnp.int32),
            shape((rows, bucket // page), jnp.int32), shape((rows,), jnp.int32))))
    return {"weights": params, "state": cache, "programs": programs}


# ---------------------------------------------------------------------- train
def loss(params, tokens, targets, config):
    from ray_tpu.models.llama import llama_loss

    return llama_loss(params, tokens, targets, config)


def make_train_step(config, optimizer, mesh=None):
    from ray_tpu.train.step import make_train_step as make

    return make(config, optimizer, mesh=mesh)


def state_shardings(config, optimizer, mesh):
    from ray_tpu.parallel.sharding import DEFAULT_LLM_RULES
    from ray_tpu.train.step import _state_shardings, state_logical_axes

    return _state_shardings(state_logical_axes(config, optimizer), mesh,
                            DEFAULT_LLM_RULES)


def layer_matmul_params(cfg: Dict[str, Any]) -> int:
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    return h * nh * hd * 2 + h * nkv * hd * 2 + 3 * h * f


def train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    """Forward and backward, no recompute, no embedding gather: 6 flops per
    matmul parameter (layers and the output head), plus causal attention
    (forward once, backward twice)."""
    dense = cfg["num_hidden_layers"] * layer_matmul_params(cfg) \
        + cfg["hidden_size"] * cfg["vocab_size"]
    attn = cfg["num_hidden_layers"] * 3 * roofline.causal_attention_flops_fwd(
        seq, cfg["num_attention_heads"], cfg["head_dim"]) / seq
    return 6 * dense + attn
