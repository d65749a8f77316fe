"""Sharded train/eval step construction, for whichever model family the
caller's configuration belongs to.

The compiled-step analogue of the reference's Train worker loop (reference:
python/ray/train/_internal/session.py — but there the step is torch eager +
NCCL allreduce; here the WHOLE step, gradients + optimizer + collectives, is
one pjit-compiled XLA program over the mesh: gradients reduce over (dp, fsdp)
via XLA's sharding propagation, parameters/optimizer state stay sharded per
the logical rules).

Whose loss it steps: this module names no family. ``make_train_step``,
``make_train_state_factory`` and ``state_logical_axes`` ask the module that
holds the configuration's class (``_family``, the way ``serve/llm.py``
``_model_of`` asks a served model's module) for its ``loss(params, tokens,
targets, config, mesh=, rules=)``, ``init_params(config, key)`` and
``logical_axes(config)``: every module under ``models/`` that is trained
holds the three. A family whose loss also counts (``loss_and_counters`` ->
``(loss, {name: array})``) has what it counted beside ``loss`` and
``grad_norm`` in the step's output.
"""

from __future__ import annotations

import sys
from typing import Any, Callable, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import optax

from ray_tpu.parallel.sharding import (
    DEFAULT_LLM_RULES,
    ShardingRules,
    axes_is_leaf,
    logical_sharding,
)
from ray_tpu.profiling import host_events, step_ring
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


def _family(config):
    """The module that holds ``config``'s class, and beside it the family's
    ``init_params``, ``logical_axes`` and ``loss``: whoever made ``config``
    has imported it."""
    return sys.modules[type(config).__module__]


def state_logical_axes(config, optimizer, sample_params=None) -> Any:
    """Logical axes for the full TrainState: optimizer moments mirror the
    param axes; scalars (step, counts) carry no axes."""
    family = _family(config)
    param_axes = family.logical_axes(config)
    if sample_params is None:
        sample_params = jax.eval_shape(
            lambda k: family.init_params(config, k), jax.random.key(0))
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
    config,
    optimizer,
    mesh=None,
    rules: ShardingRules = DEFAULT_LLM_RULES,
) -> Callable[[jax.Array], TrainState]:
    """Returns init(key) -> sharded TrainState; when a mesh is given, init is
    jitted with sharded out_shardings so parameters are created directly in
    their shards (no host-side full materialization)."""
    enable_compile_cache()
    init_params = _family(config).init_params

    def init(key) -> TrainState:
        params = init_params(config, key)
        opt_state = optimizer.init(params)
        return TrainState(step=jnp.zeros((), jnp.int32), params=params, opt_state=opt_state)

    if mesh is None:
        return jax.jit(init)
    axes = state_logical_axes(config, optimizer)
    out_shardings = _state_shardings(axes, mesh, rules)
    return jax.jit(init, out_shardings=out_shardings)


def _loss_of(config, mesh, rules):
    """The family's loss as ``(params, tokens, targets) -> loss`` or, where
    its module has ``loss_and_counters``, ``-> (loss, counters)``."""
    family = _family(config)
    counted = getattr(family, "loss_and_counters", None)
    fn = counted or family.loss
    return (lambda params, tokens, targets: fn(
        params, tokens, targets, config, mesh=mesh, rules=rules)), \
        counted is not None


class _MarkedStep:
    """The compiled step as a train loop calls it. The call is the loop's
    ``dispatch`` mark in its ``profiling.StepRing``: the time inside it is the
    ENQUEUE, and a long one means the call blocked, on a compile or on a full
    queue. Everything else (``lower``, ``trace``, ...) is the jitted
    function's own."""

    def __init__(self, jitted):
        self._jitted = jitted

    def __call__(self, *args, **kwargs):
        ring = step_ring()
        ring.dispatch()
        try:
            return self._jitted(*args, **kwargs)
        finally:
            ring.dispatched()

    def __getattr__(self, name):
        return getattr(self._jitted, name)


def make_train_step(
    config,
    optimizer,
    mesh=None,
    rules: ShardingRules = DEFAULT_LLM_RULES,
    donate: bool = True,
):
    """(state, tokens, targets) -> (state, metrics). tokens/targets: [B, S].
    What the family's loss counted (``_loss_of``) goes into the metrics. The
    jitted step comes back inside ``_MarkedStep``."""
    enable_compile_cache()
    host_events()  # the ring counts compiles and collections a step
    loss, has_counters = _loss_of(config, mesh, rules)

    def step_fn(state: TrainState, tokens, targets) -> Tuple[TrainState, Dict[str, jax.Array]]:
        def loss_fn(params):
            return loss(params, tokens, targets)

        out, grads = jax.value_and_grad(loss_fn, has_aux=has_counters)(state.params)
        value, counters = out if has_counters else (out, {})
        with jax.named_scope("optimizer"):
            updates, new_opt = optimizer.update(grads, state.opt_state, state.params)
            new_params = optax.apply_updates(state.params, updates)
            gnorm = optax.global_norm(grads)
        new_state = TrainState(step=state.step + 1, params=new_params, opt_state=new_opt)
        return new_state, {"loss": value, "grad_norm": gnorm,
                           "step": new_state.step, **counters}

    donate_argnums = (0,) if donate else ()
    if mesh is None:
        return _MarkedStep(jax.jit(step_fn, donate_argnums=donate_argnums))
    from ray_tpu.parallel.mesh import batch_sharding_spec

    batch_sh = jax.sharding.NamedSharding(mesh, batch_sharding_spec())
    state_sh = _state_shardings(state_logical_axes(config, optimizer), mesh, rules)
    return _MarkedStep(jax.jit(
        step_fn,
        in_shardings=(state_sh, batch_sh, batch_sh),
        out_shardings=(state_sh, jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())),
        donate_argnums=donate_argnums,
    ))


def make_eval_step(config, mesh=None, rules: ShardingRules = DEFAULT_LLM_RULES):
    """(params, tokens, targets) -> the loss alone, no gradient."""
    loss, has_counters = _loss_of(config, mesh, rules)

    def eval_fn(params, tokens, targets):
        out = loss(params, tokens, targets)
        return out[0] if has_counters else out

    return jax.jit(eval_fn)
