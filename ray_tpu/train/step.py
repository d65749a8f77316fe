"""Sharded train/eval step construction.

The compiled-step analogue of the reference's Train worker loop (reference:
python/ray/train/_internal/session.py — but there the step is torch eager +
NCCL allreduce; here the WHOLE step, gradients + optimizer + collectives, is
one pjit-compiled XLA program over the mesh: gradients reduce over (dp, fsdp)
via XLA's sharding propagation, parameters/optimizer state stay sharded per
the logical rules).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax

from ray_tpu.models.llama import LlamaConfig, cross_entropy_loss, llama_forward, llama_init, llama_logical_axes, llama_loss
from ray_tpu.parallel.sharding import (
    DEFAULT_LLM_RULES,
    ShardingRules,
    axes_is_leaf,
    logical_sharding,
)
from ray_tpu.utils.compile_cache import enable_compile_cache


class TrainState(NamedTuple):
    step: jax.Array
    params: Any
    opt_state: Any


def default_optimizer(
    lr: float = 3e-4,
    weight_decay: float = 0.1,
    b1: float = 0.9,
    b2: float = 0.95,
    grad_clip: float = 1.0,
    warmup_steps: int = 100,
    total_steps: int = 10000,
):
    schedule = optax.warmup_cosine_decay_schedule(
        init_value=0.0, peak_value=lr, warmup_steps=warmup_steps,
        decay_steps=max(total_steps, warmup_steps + 1), end_value=lr * 0.1,
    )
    return optax.chain(
        optax.clip_by_global_norm(grad_clip),
        optax.adamw(schedule, b1=b1, b2=b2, weight_decay=weight_decay),
    )


def state_logical_axes(config: LlamaConfig, optimizer, sample_params=None) -> Any:
    """Logical axes for the full TrainState: optimizer moments mirror the
    param axes; scalars (step, counts) carry no axes."""
    param_axes = llama_logical_axes(config)
    if sample_params is None:
        sample_params = jax.eval_shape(lambda k: llama_init(config, k), jax.random.key(0))
    opt_shape = jax.eval_shape(optimizer.init, sample_params)

    # Optimizer moments mirror the params pytree nested somewhere inside the
    # optax state (e.g. state[1][0].mu['layers']['wq']). Match each optimizer
    # leaf to a param by KEY-PATH SUFFIX (never by shape — square weights
    # like wq/wo are shape-ambiguous): the trailing path of a moment leaf
    # equals the param's path. Scalars (count, step) get None (replicated).
    from jax.tree_util import tree_flatten_with_path

    def path_key(entry):
        return getattr(entry, "key", getattr(entry, "name", getattr(entry, "idx", None)))

    param_paths = {}
    flat_axes, _ = tree_flatten_with_path(param_axes, is_leaf=lambda v: isinstance(v, tuple))
    for path, axes in flat_axes:
        param_paths[tuple(path_key(p) for p in path)] = axes
    flat_pshapes, _ = tree_flatten_with_path(sample_params)
    param_shape_by_path = {
        tuple(path_key(p) for p in path): tuple(leaf.shape) for path, leaf in flat_pshapes
    }

    flat_opt, opt_treedef = tree_flatten_with_path(opt_shape)
    opt_axes_leaves = []
    for path, leaf in flat_opt:
        keys = tuple(path_key(p) for p in path)
        axes = None
        for i in range(len(keys)):
            suffix = keys[i:]
            if suffix in param_paths and param_shape_by_path[suffix] == tuple(leaf.shape):
                axes = param_paths[suffix]
                break
        opt_axes_leaves.append(axes)
    opt_axes_tree = jax.tree_util.tree_unflatten(opt_treedef, opt_axes_leaves)
    return TrainState(step=None, params=param_axes, opt_state=opt_axes_tree)


def _state_shardings(axes_tree, mesh, rules):
    import jax

    def to_sharding(a):
        if a is None:
            return jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
        return logical_sharding(mesh, rules, a)

    return jax.tree.map(to_sharding, axes_tree, is_leaf=axes_is_leaf)


def make_train_state_factory(
    config: LlamaConfig,
    optimizer,
    mesh=None,
    rules: ShardingRules = DEFAULT_LLM_RULES,
) -> Callable[[jax.Array], TrainState]:
    """Returns init(key) -> sharded TrainState; when a mesh is given, init is
    jitted with sharded out_shardings so parameters are created directly in
    their shards (no host-side full materialization)."""
    enable_compile_cache()

    def init(key) -> TrainState:
        params = llama_init(config, key)
        opt_state = optimizer.init(params)
        return TrainState(step=jnp.zeros((), jnp.int32), params=params, opt_state=opt_state)

    if mesh is None:
        return jax.jit(init)
    axes = state_logical_axes(config, optimizer)
    out_shardings = _state_shardings(axes, mesh, rules)
    return jax.jit(init, out_shardings=out_shardings)


def make_train_step(
    config: LlamaConfig,
    optimizer,
    mesh=None,
    rules: ShardingRules = DEFAULT_LLM_RULES,
    donate: bool = True,
):
    """(state, tokens, targets) -> (state, metrics). tokens/targets: [B, S]."""
    enable_compile_cache()

    def step_fn(state: TrainState, tokens, targets) -> Tuple[TrainState, Dict[str, jax.Array]]:
        def loss_fn(params):
            return llama_loss(params, tokens, targets, config, mesh=mesh, rules=rules)

        loss, grads = jax.value_and_grad(loss_fn)(state.params)
        updates, new_opt = optimizer.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        gnorm = optax.global_norm(grads)
        new_state = TrainState(step=state.step + 1, params=new_params, opt_state=new_opt)
        return new_state, {"loss": loss, "grad_norm": gnorm, "step": new_state.step}

    donate_argnums = (0,) if donate else ()
    if mesh is None:
        return jax.jit(step_fn, donate_argnums=donate_argnums)
    from ray_tpu.parallel.mesh import batch_sharding_spec

    batch_sh = jax.sharding.NamedSharding(mesh, batch_sharding_spec())
    axes = state_logical_axes(config, optimizer)
    state_sh = _state_shardings(axes, mesh, rules)
    return jax.jit(
        step_fn,
        in_shardings=(state_sh, batch_sh, batch_sh),
        out_shardings=(state_sh, jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())),
        donate_argnums=donate_argnums,
    )


def make_eval_step(config: LlamaConfig, mesh=None, rules: ShardingRules = DEFAULT_LLM_RULES):
    def eval_fn(params, tokens, targets):
        logits = llama_forward(params, tokens, config, mesh=mesh, rules=rules)
        return cross_entropy_loss(logits, targets)

    return jax.jit(eval_fn)
