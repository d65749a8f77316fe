"""The Laguna family (``model_type`` laguna: window and full attention layers
with different head counts, two rotary schemes, a per-head attention gate, a
leading dense layer and SwiGLU experts behind a softmax router) over
``ray_tpu.models.laguna`` and ``serve/llm.py``. ``families/__init__.py`` says
what a family gives; this one gives the ``serve`` surface (training of the
family is not written in the program). On a commit whose program lacks the
family (the parent of the PR that added it) a cell of it fails at its first
request: ``_NoProgram``.

The weights are the program's seeded ``init_params`` (traceable, so one
jitted program makes them), handed to the engine and, the same values, to the
plain reference (``laguna_reference.py``).

The configuration file states the chip's share: ``num_experts`` and
``vocab_size`` are what is HELD here; ``n_router_outputs`` and
``held_experts`` say of how many, and which.

The bytes and operations its kernels NEED (the per-layer metrics' rooflines)
are at the bottom.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmarks.families.laguna_reference import (  # noqa: F401 - the surface
    make_gap_fn, make_greedy_fn, reference_logits)
from benchmarks.harness.weights import seed_key

# the programs' names in a profile (``families/__init__.py``, the serve surface)
DECODE_MODULE = "^jit_laguna_decode"
PREFILL_MODULE = "^jit_laguna_prefill"
# a prefill call holds one row: the time is a call's
PREFILL_ROWS_FROM = None


class _NoProgram:
    """The engine of a commit whose program lacks this family: the replica
    starts and answers every request with an error, so the benchmark's
    command fails at its first warm-up request, soon and with a non-zero
    exit. (A replica whose CONSTRUCTOR raises is restarted by the serve
    controller until ``serve.run`` times out, a quarter of an hour later.)"""

    error = RuntimeError(
        "this program has no ray_tpu.models.laguna: it cannot run a "
        "configuration of the laguna family")

    def generate(self, **_kw):
        raise self.error

    def generate_stream(self, **_kw):
        raise self.error

    def stats(self) -> Dict[str, Any]:
        return {}

    def stop(self) -> None:
        pass


def _program():
    """``ray_tpu.models.laguna``, or None on a commit that lacks it."""
    try:
        from ray_tpu.models import laguna
    except ImportError:
        return None
    return laguna


def program_config(cfg: Dict[str, Any]):
    """The program's ``LagunaConfig`` from a configuration file that uses the
    source's key names; None where the program has no such family."""
    import jax.numpy as jnp

    lg = _program()
    if lg is None:
        return None
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg["torch_dtype"]]
    dep = cfg["deployment"]
    return lg.LagunaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        layer_types=tuple(cfg["layer_types"]),
        mlp_layer_types=tuple(cfg["mlp_layer_types"]),
        num_attention_heads_per_layer=tuple(
            cfg["num_attention_heads_per_layer"]),
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], sliding_window=cfg["sliding_window"],
        rope_parameters=cfg["rope_parameters"],
        num_experts=cfg["num_experts"],
        n_router_outputs=cfg["n_router_outputs"],
        held_experts=tuple(cfg["held_experts"]),
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        shared_expert_intermediate_size=cfg["shared_expert_intermediate_size"],
        moe_routed_scaling_factor=float(cfg["moe_routed_scaling_factor"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]),
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
    the same), as ``LLMEngine`` builds them on a TPU (the Pallas
    paged-attention kernel, with and without a window)."""
    import jax
    import jax.numpy as jnp

    from benchmarks.families.llama import largest_prefill_rows

    lg = _program()
    dep, shape = deployment, jax.ShapeDtypeStruct
    slots, page = dep["num_slots"], dep["page_size"]
    params = jax.eval_shape(lambda k: init_weights(config, k), jax.random.key(0))
    cache = jax.eval_shape(
        lambda: lg.init_cache(config, slots, dep["total_pages"], page))
    ints = shape((slots,), jnp.int32)
    active = shape((slots,), jnp.bool_)
    table = shape((slots, -(-dep["max_seq_len"] // page)), jnp.int32)
    key = jax.eval_shape(lambda: jax.random.key(0))
    decode = lg.make_paged_decode_fn(config, dep["decode_chunk"], page,
                                     use_kernel=True)
    programs = [("decode", decode, (params, cache, ints, ints, active, table, key))]
    prefill = lg.make_paged_prefill_fn(config, page)
    for bucket in dep["prefill_buckets"]:
        rows = largest_prefill_rows(bucket)
        programs.append((f"prefill_{rows}x{bucket}", prefill, (
            params, cache, shape((rows, bucket), jnp.int32),
            shape((rows, bucket // page), jnp.int32), shape((rows,), jnp.int32),
            shape((rows,), jnp.int32))))
    return {"weights": params, "state": cache, "programs": programs}


# ------------------------------------------------- bytes and operations needed
def _layers(cfg: Dict[str, Any], kind: str) -> int:
    return cfg["layer_types"].count(kind)


def _decode_attention_bytes(cfg, kind: str, calls: float, rows: int,
                            attended_per_call: float, itemsize: int) -> float:
    """``calls`` decode-attention calls of layers of ``kind`` (one a layer a
    tick) over ``rows`` slots NEED: the K and V rows one call actually
    attends over (``attended_per_call``, summed over its slots), each
    ``n_kv x head_dim`` wide a side, read once; plus q and the output of
    every slot."""
    layer = cfg["layer_types"].index(kind)
    nq = cfg["num_attention_heads_per_layer"][layer]
    row = cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize
    return calls * (attended_per_call * 2 * row
                    + 2 * rows * nq * cfg["head_dim"] * itemsize)


def full_attn_decode_bytes(cfg, calls: float, rows: int, live_tokens: float,
                           itemsize: int = 2) -> float:
    """A full layer's call attends over every token in the cache of every
    live slot: ``live_tokens``, over the profile's own seconds
    (``readers/traced_bytes_roofline.py``; the engine counts the same rows a
    tick as ``attn_rows_full``, over its three full layers)."""
    return _decode_attention_bytes(cfg, "full_attention", calls, rows,
                                   live_tokens, itemsize)


def window_attn_decode_bytes(cfg, calls: float, rows: int, attended_per_tick,
                             itemsize: int = 2) -> float:
    """A sliding layer is charged the ``min(length, window)`` rows a slot
    attends over, not the pages that hold them nor the rows behind them:
    ``attended_per_tick`` is the engine's ``attn_rows_window`` over its
    decode ticks, so summed over the sliding layers and divided here by
    their number (24 x 512 a layer a tick once every slot is past the
    window, whatever the contexts: the window's first quarter reads as its
    profile does)."""
    return _decode_attention_bytes(
        cfg, "sliding_attention", calls, rows,
        attended_per_tick / _layers(cfg, "sliding_attention"), itemsize)


def flash_window_fwd_flops(cfg, batch: int, heads: int, seq: int,
                           head_dim: int) -> float:
    """One call of the windowed flash forward over [batch, heads, seq,
    head_dim]: QK^T and PV, 2 matmuls x 2 flops, over the ``min(i + 1,
    window)`` keys query ``i`` sees."""
    w = min(cfg["sliding_window"], seq)
    seen = w * (w + 1) // 2 + (seq - w) * w
    return 4.0 * batch * heads * head_dim * seen
