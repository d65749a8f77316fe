"""The phi4flash family (``model_type`` phi4flash: a Mamba-1 self-decoder
with window differential attention, ONE full layer whose K/V every cross
layer reads, Gated Memory Units) over ``ray_tpu.models.phi4flash`` and
``serve/llm.py``. ``families/__init__.py`` says what a family gives; this one
gives the ``serve`` surface (training of the family is not written in the
program). On a commit whose program lacks the family (the parent of the PR
that added it) a cell of it fails at its first request: ``_NoProgram``.

The weights are the program's seeded ``init_params`` (a compiled program a
layer kind), handed to the engine and, the same values, to the plain
reference (``phi4flash_reference.py``).

The bytes and operations its kernels NEED (the per-layer metrics' rooflines)
are at the bottom: counted from the equations, whatever implements them.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmarks.families.laguna import _NoProgram as _NoLagunaProgram
from benchmarks.families.phi4flash_reference import (  # noqa: F401 - the surface
    kind_of, make_gap_fn, make_greedy_fn, reference_logits, sizes)
from benchmarks.harness.weights import seed_key

# the programs' names in a profile (``families/__init__.py``, the serve surface)
DECODE_MODULE = "^jit_phi4flash_decode"
PREFILL_MODULE = "^jit_phi4flash_prefill"
# a prefill call holds one row: the time is a call's
PREFILL_ROWS_FROM = None


class _NoProgram(_NoLagunaProgram):
    """The engine of a commit whose program lacks this family: it answers
    every request with an error, so the benchmark's command fails at its
    first warm-up request (``families/laguna.py`` has why)."""

    error = RuntimeError(
        "this program has no ray_tpu.models.phi4flash: it cannot run a "
        "configuration of the phi4flash family")


def _program():
    """``ray_tpu.models.phi4flash``, or None on a commit that lacks it."""
    try:
        from ray_tpu.models import phi4flash
    except ImportError:
        return None
    return phi4flash


def program_config(cfg: Dict[str, Any]):
    """The program's ``Phi4FlashConfig`` from a configuration file that uses
    the source's key names; None where the program has no such family."""
    import jax.numpy as jnp

    pf = _program()
    if pf is None:
        return None
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg["torch_dtype"]]
    dep, sz = cfg["deployment"], sizes(cfg)
    return pf.Phi4FlashConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        sliding_window=cfg["sliding_window"], mb_per_layer=cfg["mb_per_layer"],
        mamba_d_state=sz["d_state"], mamba_d_conv=sz["d_conv"],
        mamba_expand=cfg.get("mamba_expand", 2), mamba_dt_rank=sz["dt_rank"],
        layer_norm_eps=float(cfg["layer_norm_eps"]),
        max_seq_len=dep["max_seq_len"], dtype=dtype,
        attention_impl=dep.get("attention_impl", "auto"),
        scan_impl=dep.get("scan_impl", "auto"))


def init_weights(config, key) -> Dict[str, Any]:
    return _program().init_params(config, key)


def make_weights(config, seed: int) -> Dict[str, Any]:
    """From the seed, in the dtype the weights are served in. Called eagerly:
    the program's ``init_params`` compiles a small program a layer KIND (one
    jitted program of the 32 unrolled layers was 15.6 of the 39.8 MB the cell
    wrote into an empty compile cache; PERF.md 6, PR 38)."""
    if config is None:
        return {}
    return init_weights(config, seed_key(seed))


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
    the same), as ``LLMEngine`` builds them on a TPU (the Pallas kernels)."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from benchmarks.families.llama import largest_prefill_rows

    pf = _program()
    config = dataclasses.replace(config, attention_impl="flash",
                                 scan_impl="pallas")
    dep, shape = deployment, jax.ShapeDtypeStruct
    slots, page = dep["num_slots"], dep["page_size"]
    params = jax.eval_shape(lambda k: init_weights(config, k), jax.random.key(0))
    cache = jax.eval_shape(
        lambda: pf.init_cache(config, slots, dep["total_pages"], page))
    ints = shape((slots,), jnp.int32)
    active = shape((slots,), jnp.bool_)
    table = shape((slots, -(-dep["max_seq_len"] // page)), jnp.int32)
    key = jax.eval_shape(lambda: jax.random.key(0))
    decode = pf.make_paged_decode_fn(config, dep["decode_chunk"], page,
                                     use_kernel=True)
    programs = [("decode", decode, (params, cache, ints, ints, active, table, key))]
    prefill = pf.make_paged_prefill_fn(config, page)
    for bucket in dep["prefill_buckets"]:
        rows = largest_prefill_rows(bucket)
        programs.append((f"prefill_{rows}x{bucket}", prefill, (
            params, cache, shape((rows, bucket), jnp.int32),
            shape((rows, bucket // page), jnp.int32), shape((rows,), jnp.int32),
            shape((rows,), jnp.int32))))
    return {"weights": params, "state": cache, "programs": programs}


# ------------------------------------------------- bytes and operations needed
def _kinds(cfg: Dict[str, Any]):
    return [kind_of(layer, cfg) for layer in range(cfg["num_hidden_layers"])]


def page_readers(cfg: Dict[str, Any]) -> int:
    """Layers that read the ONE layer's pages in a decode tick: the full
    layer and every cross layer (8 of the published 32)."""
    kinds = _kinds(cfg)
    return kinds.count("full") + kinds.count("cross")


def kv_row_bytes(cfg: Dict[str, Any], itemsize: int = 2) -> int:
    """K and V of one cached token in one layer: ``n_kv x head_dim`` a side
    (2 x 20 x 64 x 2 B = 5,120 B published; the whole model's, since one
    layer keeps pages)."""
    return 2 * cfg["num_key_value_heads"] * sizes(cfg)["head_dim"] * itemsize


def _qo_bytes(cfg: Dict[str, Any], rows: int, itemsize: int) -> float:
    """q in and the attended rows out, one call over ``rows`` slots: a query
    head is ``head_dim`` wide, what it attends ``2 x head_dim`` (a pair's V
    halves side by side)."""
    return rows * cfg["num_attention_heads"] * 3 * sizes(cfg)["head_dim"] * itemsize


def shared_kv_decode_bytes(cfg, calls: float, rows: int, live_tokens: float,
                           itemsize: int = 2) -> float:
    """``calls`` reads of the shared pages (one a reading layer a tick) NEED:
    K and V of every token in the cache of every live slot
    (``live_tokens``, over the profile's own seconds:
    ``readers/traced_bytes_roofline.py``), read once a call, plus q and the
    output. The engine counts the same rows a tick as ``attn_rows_shared``,
    over its readers."""
    return calls * (live_tokens * kv_row_bytes(cfg, itemsize)
                    + _qo_bytes(cfg, rows, itemsize))


def window_attn_decode_bytes(cfg, calls: float, rows: int, attended_per_tick,
                             itemsize: int = 2) -> float:
    """A window layer is charged the ``min(length, window)`` rows a slot
    attends over: ``attended_per_tick`` is the engine's ``attn_rows_window``
    over its decode ticks, summed over the window layers and divided here by
    their number."""
    layers = _kinds(cfg).count("window")
    return calls * (attended_per_tick / layers * kv_row_bytes(cfg, itemsize)
                    + _qo_bytes(cfg, rows, itemsize))


def flash_diff_fwd_flops(cfg, batch: int, heads: int, seq: int,
                         width: int) -> float:
    """One call of the windowed flash forward over ``seq`` rows of a prompt
    as differential attention NEEDS it: every query head's ``q_i k_i^T`` at
    ``head_dim`` (64) and its product with ``[v_1 | v_2]`` at ``2 x
    head_dim`` (128), over the ``min(i + 1, window)`` keys query ``i`` sees.
    ``heads`` and ``width`` are the call's own (the packed form: the heads,
    and rows of ``2 x head_dim``); the score product the kernel runs at the
    packed width is twice what is counted here."""
    d = sizes(cfg)["head_dim"]
    w = min(cfg["sliding_window"], seq)
    seen = w * (w + 1) // 2 + (seq - w) * w
    return 2.0 * batch * cfg["num_attention_heads"] * (d + 2 * d) * seen


def selective_scan_fwd_bytes(cfg, batch: int, seq: int, itemsize: int = 2) -> float:
    """What the call must move, whatever implements it: x in and y out a
    channel a step at the width the model is served in, dt in float32 (the
    decay is its exponential over thousands of steps), B and C a state. The
    program's kernel takes x and gives y as float32, a half more."""
    sz = sizes(cfg)
    return float(batch * seq * ((2 * itemsize + 4) * sz["d_inner"]
                                + 2 * itemsize * sz["d_state"]))


def selective_scan_step_bytes(cfg, calls: float, rows: int, _mean=None) -> float:
    """``calls`` one-token updates (one a scan layer a tick) over ``rows``
    slots NEED: every slot's state read and written in float32, plus x, dt
    and y a channel and B, C a state."""
    sz = sizes(cfg)
    state = sz["d_inner"] * sz["d_state"] * 4
    return calls * rows * (2 * state + 3 * sz["d_inner"] * 4
                           + 2 * sz["d_state"] * 4)
