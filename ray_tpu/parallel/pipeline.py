"""Pipeline parallelism: GPipe and 1F1B schedules over the ``pp`` mesh axis.

The reference's answer to PP is "compose external engines or build on aDAG
NCCL channels" (SURVEY §2.4); here it is a compiled-in construct:

- layers are grouped into ``pp`` stages; stage parameters are sharded over
  the pp axis (logical axis "stage");
- inside one ``shard_map``, every tick runs each stage on its current
  microbatch and shifts activations to the next stage with
  ``jax.lax.ppermute`` (neighbor ICI / cross-slice DCN hop);
- the whole schedule is ONE XLA program: no per-microbatch host round trips
  (the aDAG lesson — reference: dag/compiled_dag_node.py pre-provisioned
  loops — realized as a compiled loop instead of actor plumbing).

Two training schedules (``pipeline_train_step``):

- ``gpipe``: all forwards, then all backwards — activation stash depth M
  (every microbatch's stage input is live until its backward);
- ``1f1b``: backwards interleave with forwards as soon as the cotangent
  arrives from the right neighbor — stash depth min(M, 2*pp - 1), the
  1F1B memory bound (a stage holds at most ~2*pp in-flight microbatches),
  letting M scale without scaling activation memory.

Constraint: every stage must map activations of one shape to the same shape
(true for transformer blocks); the final projection/loss fold into
``loss_fn`` on the last stage.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,
    x: jax.Array,
    mesh,
    num_microbatches: int,
    axis_name: str = "pp",
):
    """Run a pp-stage pipeline.

    stage_fn(params_for_one_stage, activation[mb, ...]) -> activation
    stage_params: pytree, leaves with leading dim == pp (stage-stacked)
    x: [B, ...] with B % num_microbatches == 0
    Returns [B, ...] outputs (replicated over pp).
    """
    from jax.sharding import PartitionSpec as P

    shard_map = functools.partial(jax.shard_map, check_vma=False)

    pp = mesh.shape[axis_name]
    b = x.shape[0]
    if b % num_microbatches:
        raise ValueError(f"batch {b} not divisible by num_microbatches={num_microbatches}")
    mb = b // num_microbatches

    params_spec = jax.tree.map(lambda _: P(axis_name), stage_params)

    def per_device(params_local, x_full):
        # params_local leaves: [1, ...] (this stage); x_full: [B, ...] replicated
        params_here = jax.tree.map(lambda p: p[0], params_local)
        d = jax.lax.axis_index(axis_name)
        M = num_microbatches
        mbs = x_full.reshape((M, mb) + x_full.shape[1:])
        state = jnp.zeros_like(mbs[0])
        outputs = jnp.zeros_like(mbs)
        shift = [(i, i + 1) for i in range(pp - 1)]

        def tick(t, carry):
            state, outputs = carry
            mb_idx = t - d
            active = (mb_idx >= 0) & (mb_idx < M)
            take = jnp.clip(t, 0, M - 1)
            inp = jnp.where(d == 0, mbs[take], state)
            out = stage_fn(params_here, inp)
            out = jnp.where(active, out, jnp.zeros_like(out))
            write_idx = jnp.clip(mb_idx, 0, M - 1)
            is_last = d == pp - 1
            outputs = jnp.where(
                active & is_last,
                outputs.at[write_idx].set(out),
                outputs,
            )
            state = jax.lax.ppermute(out, axis_name, shift)
            return state, outputs

        _, outputs = jax.lax.fori_loop(0, M + pp - 1, tick, (state, outputs))
        # replicate the last stage's outputs to all pp members
        outputs = jax.lax.psum(
            jnp.where(d == pp - 1, outputs, jnp.zeros_like(outputs)), axis_name
        )
        return outputs.reshape((b,) + x_full.shape[1:])

    return shard_map(
        per_device,
        mesh=mesh,
        in_specs=(params_spec, P()),
        out_specs=P(),
    )(stage_params, x)


# --------------------------------------------------------------------------- #
# Schedule accounting (asserted by tests/test_parallel.py)
# --------------------------------------------------------------------------- #
def schedule_ticks(schedule: str, pp: int, num_microbatches: int) -> int:
    """Total pipeline ticks for one fwd+bwd step."""
    m = num_microbatches
    if schedule == "gpipe":
        return 2 * (m + pp - 1)
    if schedule == "1f1b":
        return m + 2 * (pp - 1)
    raise ValueError(f"unknown schedule {schedule!r}")


def stash_depth(schedule: str, pp: int, num_microbatches: int) -> int:
    """Activation-stash entries a stage must hold (the 1F1B win)."""
    if schedule == "gpipe":
        return num_microbatches
    if schedule == "1f1b":
        return min(num_microbatches, 2 * pp - 1)
    raise ValueError(f"unknown schedule {schedule!r}")


def bubble_fraction(schedule: str, pp: int, num_microbatches: int) -> float:
    """Idle fraction of the tick x stage grid. Both schedules amortize the
    (pp-1)-tick fill/drain over num_microbatches; 1f1b ticks carry a fwd AND
    a bwd work slot, gpipe ticks carry one."""
    m = num_microbatches
    t = schedule_ticks(schedule, pp, m)
    slots_per_tick = 2 if schedule == "1f1b" else 1
    return 1.0 - (2 * m) / (t * slots_per_tick)


# --------------------------------------------------------------------------- #
# Training step: fwd + bwd under a pipeline schedule, one XLA program
# --------------------------------------------------------------------------- #
def pipeline_train_step(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    loss_fn: Callable[[jax.Array, jax.Array], jax.Array],
    stage_params: Any,
    x: jax.Array,
    targets: jax.Array,
    mesh,
    num_microbatches: int,
    axis_name: str = "pp",
    schedule: str = "1f1b",
):
    """One fwd+bwd pipeline step. Returns ``(loss, grads)``.

    stage_fn(params_for_one_stage, act[mb, ...]) -> act (same shape)
    loss_fn(final_act[mb, ...], target[mb, ...]) -> scalar (mean over mb)
    stage_params: pytree, leaves stage-stacked [pp, ...]
    x, targets: [B, ...] with B % num_microbatches == 0 (replicated in)
    grads: stage-stacked like stage_params ([pp, ...] leaves).

    Backward recomputes each stage forward from the stashed stage INPUT
    (per-stage activation checkpointing — jax.vjp at bwd time), so the stash
    holds inputs only; 1f1b additionally bounds the stash to min(M, 2pp-1)
    entries via circular indexing, the actual 1F1B memory claim.
    """
    from jax.sharding import PartitionSpec as P

    shard_map = functools.partial(jax.shard_map, check_vma=False)

    if schedule not in ("gpipe", "1f1b"):
        raise ValueError(f"unknown schedule {schedule!r}")
    pp = mesh.shape[axis_name]
    b = x.shape[0]
    if b % num_microbatches:
        raise ValueError(f"batch {b} not divisible by num_microbatches={num_microbatches}")
    m_total = num_microbatches
    mb = b // m_total
    w = stash_depth(schedule, pp, m_total)
    ticks = schedule_ticks(schedule, pp, m_total)
    # first tick at which backwards may run: 1f1b interleaves as soon as the
    # cotangent can exist; gpipe waits for every forward to finish
    bwd_base = 2 * (pp - 1) + (m_total if schedule == "gpipe" else 0)

    params_spec = jax.tree.map(lambda _: P(axis_name), stage_params)

    def per_device(params_local, x_full, tgt_full):
        params_here = jax.tree.map(lambda p: p[0], params_local)
        d = jax.lax.axis_index(axis_name)
        mbs = x_full.reshape((m_total, mb) + x_full.shape[1:])
        tgts = tgt_full.reshape((m_total, mb) + tgt_full.shape[1:])
        act_shape = (mb,) + x_full.shape[1:]
        shift_fwd = [(i, i + 1) for i in range(pp - 1)]
        shift_bwd = [(i, i - 1) for i in range(1, pp)]

        zero_act = jnp.zeros(act_shape, x_full.dtype)
        g0 = jax.tree.map(lambda p: jnp.zeros_like(p, dtype=jnp.float32), params_here)

        def tick(t, carry):
            state_f, state_b, stash, g_params, loss_sum = carry
            # ---- forward slot ----
            mf = t - d
            active_f = (mf >= 0) & (mf < m_total)
            idx_f = jnp.clip(mf, 0, m_total - 1)
            inp = jnp.where(d == 0, mbs[idx_f], state_f)
            stash = jnp.where(active_f, stash.at[idx_f % w].set(inp), stash)

            def run_fwd(_):
                return stage_fn(params_here, inp)

            out_f = jax.lax.cond(active_f, run_fwd, lambda _: zero_act, None)
            out_f = jnp.where(active_f, out_f, zero_act)
            # ---- backward slot ----
            # stage d runs bwd of microbatch m at tick bwd_base + m - d:
            # the cotangent hops right-to-left one stage per tick
            m_b = t - bwd_base + d
            active_b = (m_b >= 0) & (m_b < m_total)
            idx_b = jnp.clip(m_b, 0, m_total - 1)
            x_in = stash[idx_b % w]
            tgt_mb = tgts[idx_b]

            def bwd_last(_):
                # combined vjp through loss_fn∘stage_fn: primal gives the
                # microbatch loss, cotangent seed 1/M gives mean-over-batch
                def fwd_loss(p, xin):
                    return loss_fn(stage_fn(p, xin), tgt_mb)

                lm, vjpf = jax.vjp(fwd_loss, params_here, x_in)
                gp, gx = vjpf(jnp.float32(1.0 / m_total))
                return gp, gx, lm / m_total

            def bwd_mid(_):
                _y, vjpf = jax.vjp(stage_fn, params_here, x_in)
                gp, gx = vjpf(state_b)
                return gp, gx, jnp.float32(0.0)

            def bwd_run(_):
                return jax.lax.cond(d == pp - 1, bwd_last, bwd_mid, None)

            def bwd_skip(_):
                return g0, zero_act, jnp.float32(0.0)

            gp, gx, lm = jax.lax.cond(active_b, bwd_run, bwd_skip, None)
            gate = jnp.where(active_b, 1.0, 0.0).astype(jnp.float32)
            g_params = jax.tree.map(
                lambda a, g: a + gate * g.astype(jnp.float32), g_params, gp
            )
            loss_sum = loss_sum + gate * lm
            gx = jnp.where(active_b, gx.astype(x_full.dtype), zero_act)
            # ---- shifts (uniform every tick; extras land as zeros) ----
            state_f = jax.lax.ppermute(out_f, axis_name, shift_fwd)
            state_b = jax.lax.ppermute(gx, axis_name, shift_bwd)
            return state_f, state_b, stash, g_params, loss_sum

        stash0 = jnp.zeros((w,) + act_shape, x_full.dtype)
        carry = (zero_act, zero_act, stash0, g0, jnp.float32(0.0))
        _, _, _, g_params, loss_sum = jax.lax.fori_loop(0, ticks, tick, carry)
        loss = jax.lax.psum(loss_sum, axis_name)  # only last stage nonzero
        grads = jax.tree.map(lambda g: g[None], g_params)  # [1, ...] per stage
        return loss, grads

    return shard_map(
        per_device,
        mesh=mesh,
        in_specs=(params_spec, P(), P()),
        out_specs=(P(), params_spec),
    )(stage_params, x, targets)
