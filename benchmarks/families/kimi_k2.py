"""The Kimi-K2 family (``model_type`` kimi_k2, the DeepSeek-V3 block:
multi-head latent attention over a cache of one compressed row a token a
layer, a leading dense layer, SwiGLU experts behind a sigmoid router with a
correction bias and one shared expert) over ``ray_tpu.models.kimi_k2`` and
``serve/llm.py``. ``families/__init__.py`` says what a family gives; this one
gives the ``serve`` surface (training of the family is not written in the
program). On a commit whose program lacks the family (the parent of the PR
that added it) a cell of it fails at its first request: ``_NoProgram``.

The weights are the program's seeded ``init_params`` (traceable, so one
jitted program makes them), handed to the engine and, the same values, to the
plain reference (``kimi_k2_reference.py``).

The configuration file states the chip's share: ``n_routed_experts`` and
``vocab_size`` are what is HELD here; ``n_router_outputs`` and
``held_experts`` say of how many, and which.

The bytes and operations its kernels NEED (the per-layer metrics' rooflines)
are at the bottom.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmarks.families.kimi_k2_reference import (  # noqa: F401 - the surface
    make_gap_fn, make_greedy_fn, reference_logits)
from benchmarks.harness.weights import seed_key

# the programs' names in a profile (``families/__init__.py``, the serve surface)
DECODE_MODULE = "^jit_kimi_k2_decode"
PREFILL_MODULE = "^jit_kimi_k2_prefill"
# a prefill call holds one row: the time is a call's
PREFILL_ROWS_FROM = None


class _NoProgram:
    """The engine of a commit whose program lacks this family: the replica
    starts and answers every request with an error, so the benchmark's
    command fails at its first warm-up request, soon and with a non-zero
    exit. (A replica whose CONSTRUCTOR raises is restarted by the serve
    controller until ``serve.run`` times out, a quarter of an hour later.)"""

    error = RuntimeError(
        "this program has no ray_tpu.models.kimi_k2: it cannot run a "
        "configuration of the kimi_k2 family")

    def generate(self, **_kw):
        raise self.error

    def generate_stream(self, **_kw):
        raise self.error

    def stats(self) -> Dict[str, Any]:
        return {}

    def stop(self) -> None:
        pass


def _program():
    """``ray_tpu.models.kimi_k2``, or None on a commit that lacks it."""
    try:
        from ray_tpu.models import kimi_k2
    except ImportError:
        return None
    return kimi_k2


def program_config(cfg: Dict[str, Any]):
    """The program's ``KimiK2Config`` from a configuration file that uses the
    source's key names; None where the program has no such family."""
    import jax.numpy as jnp

    km = _program()
    if km is None:
        return None
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg["torch_dtype"]]
    dep = cfg["deployment"]
    return km.KimiK2Config(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        first_k_dense_replace=cfg["first_k_dense_replace"],
        num_attention_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        n_routed_experts=cfg["n_routed_experts"],
        n_router_outputs=cfg["n_router_outputs"],
        held_experts=tuple(cfg["held_experts"]),
        num_experts_per_tok=cfg["num_experts_per_tok"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        rope_theta=float(cfg["rope_theta"]), rope_scaling=cfg["rope_scaling"],
        max_seq_len=dep["max_seq_len"], dtype=dtype,
        attention_impl=dep.get("attention_impl", "auto"))


def init_weights(config, key) -> Dict[str, Any]:
    return _program().init_params(config, key)


def make_weights(config, seed: int) -> Dict[str, Any]:
    """One jitted call from the seed, in the dtype the weights are served in."""
    import jax

    if config is None:
        return {}
    return jax.jit(lambda k: init_weights(config, k))(seed_key(seed))


# ---------------------------------------------------------------------- serve
def make_engine(config, params, deployment: Dict[str, Any]):
    from ray_tpu.serve.llm import LLMEngine

    if config is None:
        return _NoProgram()
    dep = deployment
    return LLMEngine(
        config, params, num_slots=dep["num_slots"],
        max_seq_len=dep["max_seq_len"], decode_chunk=dep["decode_chunk"],
        prefill_buckets=dep["prefill_buckets"], page_size=dep["page_size"],
        total_pages=dep["total_pages"])


def set_weights(engine, params) -> None:
    engine.params = params


def serve_programs(config, deployment: Dict[str, Any]) -> Dict[str, Any]:
    """The decode chunk over all slots and the tallest prefill program of
    each bucket (``families/llama.py`` ``largest_prefill_rows``: the engine is
    the same), as ``LLMEngine`` builds them on a TPU (the latent
    paged-attention kernel)."""
    import jax
    import jax.numpy as jnp

    from benchmarks.families.llama import largest_prefill_rows

    km = _program()
    dep, shape = deployment, jax.ShapeDtypeStruct
    slots, page = dep["num_slots"], dep["page_size"]
    params = jax.eval_shape(lambda k: init_weights(config, k), jax.random.key(0))
    cache = jax.eval_shape(
        lambda: km.init_cache(config, slots, dep["total_pages"], page))
    ints = shape((slots,), jnp.int32)
    active = shape((slots,), jnp.bool_)
    table = shape((slots, -(-dep["max_seq_len"] // page)), jnp.int32)
    key = jax.eval_shape(lambda: jax.random.key(0))
    decode = km.make_paged_decode_fn(config, dep["decode_chunk"], page,
                                     use_kernel=True)
    programs = [("decode", decode, (params, cache, ints, ints, active, table, key))]
    prefill = km.make_paged_prefill_fn(config, page)
    for bucket in dep["prefill_buckets"]:
        rows = largest_prefill_rows(bucket)
        programs.append((f"prefill_{rows}x{bucket}", prefill, (
            params, cache, shape((rows, bucket), jnp.int32),
            shape((rows, bucket // page), jnp.int32), shape((rows,), jnp.int32))))
    return {"weights": params, "state": cache, "programs": programs}


# ------------------------------------------------- bytes and operations needed
# What the mechanism must move, whatever implements it: a cached token is
# ``kv_lora_rank + qk_rope_head_dim`` values a layer (1,152 B as published);
# the lanes a device pads a stored row with are the implementation's, not the
# mechanism's, and are not counted here.
def latent_row_bytes(cfg: Dict[str, Any], itemsize: int = 2) -> int:
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * itemsize


def latent_attn_decode_bytes(cfg, calls: float, rows: int, attended_per_tick,
                             itemsize: int = 2) -> float:
    """``calls`` calls of the latent decode kernel (one a layer a tick) over
    ``rows`` slots NEED (``readers/bytes_roofline.py``): every attended latent
    row ONCE, scores and values from the same read (``attended_per_tick`` is
    the engine's ``attn_rows_latent`` over its decode ticks, so summed over
    the layers and divided here by their number); plus, a call, every slot's
    queries in the latent space (heads x the row's width) and its outputs
    (heads x ``kv_lora_rank``)."""
    heads = cfg["num_attention_heads"]
    row = latent_row_bytes(cfg, itemsize)
    per_call = rows * heads * (row + cfg["kv_lora_rank"] * itemsize)
    return calls * (attended_per_tick / cfg["num_hidden_layers"] * row
                    + per_call)


def latent_attn_decode_flops(cfg, attended: float) -> float:
    """The operations of attending over ``attended`` latent rows: a head's
    score over a cached row is a dot of the row's width (576), its value a
    row of ``kv_lora_rank`` (512): 2 x 64 x (576 + 512) = 139,264 a row as
    published, 121 a byte of the row: half of a v5e's ridge, so the kernel's
    roofline is the bytes' (``latent_attn_decode_roofline``) and its share of
    the MXU's peak is 0.50 times that reading."""
    return attended * 2.0 * cfg["num_attention_heads"] * (
        2 * cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])


def flash_mla_fwd_flops(cfg, pairs_per_prefill: float, batch: int, heads: int,
                        seq: int, head_dim: int) -> float:
    """One call of the unabsorbed prefill attention's kernel over [batch,
    heads, seq, head_dim] NEEDS (``readers/counted_flops_roofline.py``): its
    ``heads`` heads' scores and values over the causal (query, key) pairs of
    the prompts a prefill program holds, ``pairs_per_prefill`` (the engine's
    ``prefill_attn_pairs`` over its ``prefill_calls``: pairs of ONE layer, and
    one call of the kernel is a part of one layer): a score is a dot of
    ``qk_nope_head_dim + qk_rope_head_dim``, a value a row of ``v_head_dim``,
    2 x (192 + 128) a head a pair as published. The bucket's padding, the
    masked half of a diagonal block and the lanes a kernel pads a head with
    are not counted."""
    return pairs_per_prefill * 2.0 * heads * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"])
