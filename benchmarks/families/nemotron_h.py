"""The Nemotron-H family (hybrid decoder: Mamba-2, mixture-of-experts and
attention layers chosen by ``hybrid_override_pattern``) over
``ray_tpu.models.nemotron_h`` and ``serve/llm.py``. ``families/__init__.py``
says what a family gives; this one gives the ``serve`` surface (training of
the family is not written in the program). On a commit whose program lacks
the family (the parent of the PR that added it) a cell of it fails at its
first request: ``_NoProgram``.

The weights are the program's seeded ``init_params`` (traceable, so one
jitted program makes them), handed to the engine and, the same values, to the
plain reference (``nemotron_h_reference.py``).

The configuration file states the chip's share: ``n_routed_experts`` and
``vocab_size`` are what is HELD here; ``n_router_outputs`` and
``held_experts`` say of how many, and which.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmarks.families.nemotron_h_reference import (  # noqa: F401 - the surface
    make_gap_fn, make_greedy_fn, reference_logits)
from benchmarks.harness.trace_reduce import FLASH_CALL_ROWS
from benchmarks.harness.weights import seed_key

# the programs' names in a profile (``families/__init__.py``, the serve surface)
DECODE_MODULE = "^jit_nemotron_h_decode"
PREFILL_MODULE = "^jit_nemotron_h_prefill"
# a prefill call holds several rows: the flash call's batch says how many
PREFILL_ROWS_FROM = FLASH_CALL_ROWS


class _NoProgram:
    """The engine of a commit whose program lacks this family: the replica
    starts and answers every request with an error, so the benchmark's
    command fails at its first warm-up request, soon and with a non-zero
    exit. (A replica whose CONSTRUCTOR raises is restarted by the serve
    controller until ``serve.run`` times out, a quarter of an hour later.)"""

    error = RuntimeError(
        "this program has no ray_tpu.models.nemotron_h: it cannot run a "
        "configuration of the nemotron_h family")

    def generate(self, **_kw):
        raise self.error

    def generate_stream(self, **_kw):
        raise self.error

    def stats(self) -> Dict[str, Any]:
        return {}

    def stop(self) -> None:
        pass


def _program():
    """``ray_tpu.models.nemotron_h``, or None on a commit that lacks it."""
    try:
        from ray_tpu.models import nemotron_h
    except ImportError:
        return None
    return nemotron_h


def program_config(cfg: Dict[str, Any]):
    """The program's ``NemotronHConfig`` from a configuration file that uses
    the source's key names; None where the program has no such family."""
    import jax.numpy as jnp

    nh = _program()
    if nh is None:
        return None
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg["torch_dtype"]]
    return nh.NemotronHConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        pattern=cfg["hybrid_override_pattern"],
        mamba_num_heads=cfg["mamba_num_heads"],
        mamba_head_dim=cfg["mamba_head_dim"],
        ssm_state_size=cfg["ssm_state_size"], n_groups=cfg["n_groups"],
        conv_kernel=cfg["conv_kernel"], chunk_size=cfg["chunk_size"],
        time_step_min=cfg["time_step_min"], time_step_max=cfg["time_step_max"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        n_routed_experts=cfg["n_routed_experts"],
        n_router_outputs=cfg["n_router_outputs"],
        held_experts=tuple(cfg["held_experts"]),
        num_experts_per_tok=cfg["num_experts_per_tok"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        moe_intermediate_size=cfg["moe_intermediate_size"],
        moe_shared_expert_intermediate_size=cfg[
            "moe_shared_expert_intermediate_size"],
        norm_eps=float(cfg["norm_eps"]),
        max_seq_len=cfg["deployment"]["max_seq_len"], dtype=dtype,
        attention_impl=cfg["deployment"].get("attention_impl", "auto"))


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
        prefill_buckets=dep["prefill_buckets"], paged=True,
        page_size=dep["page_size"], total_pages=dep["total_pages"])


def set_weights(engine, params) -> None:
    engine.params = params


def serve_programs(config, deployment: Dict[str, Any]) -> Dict[str, Any]:
    """The decode chunk over all slots and the tallest prefill program of
    each bucket (``families/llama.py`` ``largest_prefill_rows``: the engine is
    the same), as ``LLMEngine`` builds them on a TPU (the Pallas
    paged-attention kernel)."""
    import jax
    import jax.numpy as jnp

    from benchmarks.families.llama import largest_prefill_rows

    nh = _program()
    dep, shape = deployment, jax.ShapeDtypeStruct
    slots, page = dep["num_slots"], dep["page_size"]
    params = jax.eval_shape(lambda k: init_weights(config, k), jax.random.key(0))
    cache = jax.eval_shape(
        lambda: nh.init_cache(config, slots, dep["total_pages"], page))
    ints = shape((slots,), jnp.int32)
    active = shape((slots,), jnp.bool_)
    table = shape((slots, dep["max_seq_len"] // page), jnp.int32)
    key = jax.eval_shape(lambda: jax.random.key(0))
    decode = nh.make_paged_decode_fn(config, dep["decode_chunk"], page,
                                     use_kernel=True)
    programs = [("decode", decode, (params, cache, ints, ints, active, table, key))]
    prefill = nh.make_paged_prefill_fn(config, page)
    for bucket in dep["prefill_buckets"]:
        rows = largest_prefill_rows(bucket)
        programs.append((f"prefill_{rows}x{bucket}", prefill, (
            params, cache, shape((rows, bucket), jnp.int32),
            shape((rows, bucket // page), jnp.int32), shape((rows,), jnp.int32),
            shape((rows,), jnp.int32))))
    return {"weights": params, "state": cache, "programs": programs}


# ------------------------------------------------- bytes and operations needed
def expert_bytes(cfg: Dict[str, Any], itemsize: int = 2) -> int:
    """One routed expert's two matrices."""
    return 2 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * itemsize


def moe_decode_bytes(cfg: Dict[str, Any], calls: float, rows: int,
                     touched_per_tick: float, itemsize: int = 2) -> float:
    """What the routed products of ``calls`` decode ticks of ONE expert layer
    NEED to move: the matrices of the held experts a live token reached
    (``touched_per_tick``: the engine's ``moe_experts_touched`` over its
    decode ticks, so summed over the expert layers of a tick; divided here by
    their number), read once, and the activations in and out of every row."""
    layers = cfg["hybrid_override_pattern"].count("E")
    return calls * (touched_per_tick / layers * expert_bytes(cfg, itemsize)
                    + rows * 2 * cfg["hidden_size"] * itemsize)


def ssm_update_bytes(cfg: Dict[str, Any], calls: float, rows: int,
                     _mean=None) -> float:
    """``calls`` decode ticks of one Mamba layer over ``rows`` slots: each
    slot's float32 state [H, P, N] read once and written once."""
    state = cfg["mamba_num_heads"] * cfg["mamba_head_dim"] \
        * cfg["ssm_state_size"] * 4
    return calls * 2 * rows * state
