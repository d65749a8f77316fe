"""The Ling-3.0 hybrid family (``model_type`` bailing_hybrid: groups of five
Kimi-Delta-Attention layers and one latent-attention layer, a leading dense
layer, SwiGLU experts behind a group-limited sigmoid router with a correction
bias and one shared expert) over ``ray_tpu.models.ling_hybrid`` and
``serve/llm.py``. ``families/__init__.py`` says what a family gives; this one
gives the ``serve`` surface (training of the family is not written in the
program). On a commit whose program lacks the family (the parent of the PR
that added it) a cell of it fails at its first request: ``_NoProgram``.

The weights are the program's seeded ``init_params`` (traceable, so one
jitted program makes them), handed to the engine and, the same values, to the
plain reference (``ling_hybrid_reference.py``).

The configuration file states the chip's share: ``num_experts`` and
``vocab_size`` are what is HELD here; ``n_router_outputs`` and
``held_experts`` say of how many, and which.

The bytes and operations its kernels NEED (the per-layer metrics' rooflines)
are at the bottom.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmarks.families.kimi_k2 import _NoProgram as _KimiNoProgram
from benchmarks.families.ling_hybrid_reference import (  # noqa: F401 - the surface
    is_mla, make_gap_fn, make_greedy_fn, reference_logits)
from benchmarks.harness.weights import seed_key

# the programs' names in a profile (``families/__init__.py``, the serve surface)
DECODE_MODULE = "^jit_ling_decode"
PREFILL_MODULE = "^jit_ling_prefill"
# a prefill call holds one row: the time is a call's
PREFILL_ROWS_FROM = None


class _NoProgram(_KimiNoProgram):
    """``families/kimi_k2.py``'s, under this family's name: the replica
    starts and answers every request with an error, so the benchmark's
    command fails at its first warm-up request, soon and with a non-zero
    exit."""

    error = RuntimeError(
        "this program has no ray_tpu.models.ling_hybrid: it cannot run a "
        "configuration of the ling_hybrid family")


def _program():
    """``ray_tpu.models.ling_hybrid``, or None on a commit that lacks it."""
    try:
        from ray_tpu.models import ling_hybrid
    except ImportError:
        return None
    return ling_hybrid


def program_config(cfg: Dict[str, Any]):
    """The program's ``LingHybridConfig`` from a configuration file that uses
    the source's key names; None where the program has no such family."""
    import jax.numpy as jnp

    lm = _program()
    if lm is None:
        return None
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg["torch_dtype"]]
    dep = cfg["deployment"]
    same = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "moe_shared_expert_intermediate_size",
            "num_hidden_layers", "first_k_dense_replace", "layer_group_size",
            "num_attention_heads", "head_dim", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "short_conv_kernel_size", "num_experts", "n_router_outputs",
            "num_experts_per_tok", "n_group", "topk_group")
    return lm.LingHybridConfig(
        **{key: cfg[key] for key in same},
        held_experts=tuple(cfg["held_experts"]),
        kda_lower_bound=float(cfg["kda_lower_bound"]),
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        rope_theta=float(cfg["rope_theta"]), max_seq_len=dep["max_seq_len"],
        dtype=dtype, attention_impl=dep.get("attention_impl", "auto"),
        kda_impl=dep.get("kda_impl", "auto"))


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
    paged-attention kernel, the two delta-rule kernels)."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from benchmarks.families.llama import largest_prefill_rows

    lm = _program()
    config = dataclasses.replace(config, kda_impl="pallas")
    dep, shape = deployment, jax.ShapeDtypeStruct
    slots, page = dep["num_slots"], dep["page_size"]
    params = jax.eval_shape(lambda k: init_weights(config, k), jax.random.key(0))
    cache = jax.eval_shape(
        lambda: lm.init_cache(config, slots, dep["total_pages"], page))
    ints = shape((slots,), jnp.int32)
    active = shape((slots,), jnp.bool_)
    table = shape((slots, -(-dep["max_seq_len"] // page)), jnp.int32)
    key = jax.eval_shape(lambda: jax.random.key(0))
    decode = lm.make_paged_decode_fn(config, dep["decode_chunk"], page,
                                     use_kernel=True)
    programs = [("decode", decode, (params, cache, ints, ints, active, table, key))]
    prefill = lm.make_paged_prefill_fn(config, page)
    for bucket in dep["prefill_buckets"]:
        rows = largest_prefill_rows(bucket)
        programs.append((f"prefill_{rows}x{bucket}", prefill, (
            params, cache, shape((rows, bucket), jnp.int32),
            shape((rows, bucket // page), jnp.int32), shape((rows,), jnp.int32),
            shape((rows,), jnp.int32))))
    return {"weights": params, "state": cache, "programs": programs}


# ------------------------------------------------- bytes and operations needed
def layers_of(cfg: Dict[str, Any], mla: bool) -> int:
    """The latent-attention layers of the configuration's depth, or the KDA
    ones."""
    return sum(is_mla(cfg, i) == mla for i in range(cfg["num_hidden_layers"]))


def kda_row_flops(cfg: Dict[str, Any]) -> float:
    """What ONE token of ONE KDA layer needs of the delta rule, whatever
    implements it: a head reads its state with k (``S^T k``), writes ``k
    (...)^T`` into it and reads it with q, three products of ``d x d``, 2
    operations an entry: 6 d^2 a head, 3,145,728 at 32 heads of 128. The
    decay (d^2 multiplications a head), the chunked form's pair matrices and
    its inverse are the implementation's and are not counted."""
    d = cfg["head_dim"]
    return 6.0 * cfg["num_attention_heads"] * d * d


def kda_chunk_fwd_flops(cfg, kda_rows_per_prefill: float, batch: int,
                        heads: int, seq: int, head_dim: int) -> float:
    """One call of the chunk kernel over a piece [batch, heads, seq, head_dim]
    NEEDS (``readers/counted_flops_roofline.py``): its share of the rows a
    prefill program's KDA layers took, ``kda_rows_per_prefill`` (the engine's
    ``kda_rows`` over its ``prefill_calls``: prompt tokens x KDA layers),
    times ``kda_row_flops``. A program of one prompt of ``n`` tokens calls the
    kernel once a KDA layer a piece that holds a row of it, ``ceil(n / seq)``
    pieces; over prompts spread evenly that is ``n / seq + 1 / 2`` in the
    mean, so a call's share is 1 / (KDA layers x that). The bucket's padding
    and the rows of a piece past the prompt are not counted."""
    layers = layers_of(cfg, False)
    tokens = kda_rows_per_prefill / layers
    calls = layers * (tokens / (batch * seq) + 0.5)
    return kda_rows_per_prefill * kda_row_flops(cfg) / calls


def kda_step_bytes(cfg, calls: float, rows: int, updates_per_tick,
                   state_itemsize: int = 4, itemsize: int = 2) -> float:
    """``calls`` calls of the one-token kernel (one a KDA layer a tick) NEED
    (``readers/bytes_roofline.py``): every LIVE slot's state of that layer
    read once and written once (``updates_per_tick`` is the engine's
    ``kda_state_updates`` over its decode ticks: live slots x KDA layers, so
    divided here by the layers), 2 x 32 x 128 x 128 x 4 B = 4.19 MB a slot as
    published; plus q, k, v in and o out and the decay's logarithm in
    float32. A dead slot's state need not move."""
    heads, d = cfg["num_attention_heads"], cfg["head_dim"]
    live = updates_per_tick / layers_of(cfg, False)
    per_slot = 2 * heads * d * d * state_itemsize \
        + heads * d * (4 * itemsize + state_itemsize)
    return calls * live * per_slot
