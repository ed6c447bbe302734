"""Training step: mixed-precision loss/grad + optimizer apply.

Paper setup: bf16 compute with fp32 master weights (Sec 4.2). Params live in
fp32; the forward/backward runs on a bf16 cast; gradients and optimizer
state are fp32.

The MuonBP phase ('block' | 'full') is a *static* argument — the launcher
compiles the step once per phase and alternates per ``step % P``
(core/muon.py explains why this beats a lax.cond). Per phase the optimizer
interprets its compiled ``UpdateProgram`` (core/program.py), so each of the
two jitted step functions traces exactly one bucket schedule — the block
step's zero-collective property and the full step's gather bytes are
properties of the compiled artifact, asserted by the HLO audit.

The optimizer apply (update, ZeRO-1 constraint, parameter update and pin)
runs under the ``opt`` name scope, inside which the comm engine's
``muonbp.<phase>...`` stage scopes nest; the model's layers carry their
own scopes (models/transformer.py).
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.combine import apply_updates
from repro.core.muon import Optimizer
from repro.models.model import loss_fn
from repro.models.transformer import ShardCtx


class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    step: jax.Array
    # GuardState when the guarded step is enabled (repro.training.resilience),
    # None otherwise — a None leaf is an empty subtree, so unguarded code
    # paths and checkpoints are unchanged.
    guard: Any = None


def init_train_state(params, optimizer: Optimizer, guard: bool = False) -> TrainState:
    from repro.training import resilience

    return TrainState(
        params=params,
        opt_state=optimizer.init(params),
        step=jnp.zeros((), jnp.int32),
        guard=resilience.init_guard_state() if guard else None,
    )


def cast_tree(tree, dtype):
    return jax.tree.map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x,
        tree,
    )


def _pin(tree, shardings):
    if shardings is None:
        return tree
    return jax.lax.with_sharding_constraint(tree, shardings)


def _constrain_opt(opt_state, opt_shardings):
    if opt_shardings is None:
        return opt_state
    from repro.distributed import zero1 as zero1_lib

    return zero1_lib.constrain(opt_state, opt_shardings)


def train_step(
    state: TrainState,
    batch: dict,
    *,
    cfg: ModelConfig,
    optimizer: Optimizer,
    ctx: ShardCtx = ShardCtx(),
    phase: str = "block",
    compute_dtype=jnp.bfloat16,
    accum_steps: int = 1,
    bf16_grads: bool = False,
    opt_shardings=None,
    param_shardings=None,
    guard=None,
    fault=None,
):
    """One optimization step. Returns (new_state, metrics).

    ``accum_steps > 1`` splits the batch into microbatches and accumulates
    gradients with lax.scan — activation memory drops ~accum_steps x at the
    cost of accum_steps sequential passes (same total FLOPs).

    ``bf16_grads``: differentiate w.r.t. the bf16-cast params so the
    cross-data-replica gradient all-reduce moves bf16 instead of fp32
    (half the bytes; the optimizer still accumulates in fp32). Standard
    mixed-precision trade-off; see EXPERIMENTS.md §Perf.

    ``opt_shardings``: optional pytree of NamedShardings matching the
    optimizer state (``distributed.zero1.opt_shardings``). The fresh state
    is pinned to it with a sharding constraint so ZeRO-1 momentum shards
    survive the compiled step instead of being replicated at the
    partitioner's whim.

    ``param_shardings``: optional pytree of NamedShardings matching the
    params. The new params are pinned to it, so they leave the step in the
    layout they entered with: under ZeRO-1 the update is computed on
    ``data`` shards, and without the pin the partitioner keeps the new
    params sharded that way, so the next step sees another input layout
    (and compiles again) and its forward gathers the params.

    ``guard``: optional :class:`repro.training.resilience.GuardConfig`.
    Wraps the optimizer apply in the in-graph health check + ``lax.cond``
    skip: healthy steps are bitwise-identical to the unguarded step (the
    true branch IS that computation), unhealthy steps leave params and
    momentum untouched and bump ``state.guard.skipped``. Requires
    ``state.guard`` (``init_train_state(..., guard=True)``).

    ``fault``: optional :class:`repro.training.faults.Fault` with an
    in-graph kind — compiled INTO this step variant (the launcher keeps
    clean and faulty variants separate), used only by resilience tests and
    the chaos harness.
    """

    if bf16_grads:
        def lf(p, b):
            return loss_fn(p, b, cfg, ctx=ctx)

        def grad_fn(p, b):
            pc = cast_tree(p, compute_dtype)
            (l, m), g = jax.value_and_grad(lf, has_aux=True)(pc, b)
            return (l, m), g
    else:
        def lf(p, b):
            return loss_fn(cast_tree(p, compute_dtype), b, cfg, ctx=ctx)

        def grad_fn(p, b):
            return jax.value_and_grad(lf, has_aux=True)(p, b)

    if accum_steps > 1:
        def split(x):
            b = x.shape[0]
            return x.reshape(accum_steps, b // accum_steps, *x.shape[1:])

        microbatches = jax.tree.map(split, batch)

        def body(acc, mb):
            (l, m), g = grad_fn(state.params, mb)
            acc = jax.tree.map(lambda a, gi: a + gi.astype(jnp.float32) / accum_steps, acc, g)
            return acc, (l, m)

        zeros = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), state.params
        )
        from repro.models.layers import scan_unroll

        grads, (losses, ms) = jax.lax.scan(
            body, zeros, microbatches, unroll=True if scan_unroll() else 1
        )
        loss = losses.mean()
        metrics = jax.tree.map(lambda x: x.mean(), ms)
    else:
        (loss, metrics), grads = grad_fn(state.params, batch)
    if fault is not None:
        from repro.training import faults as faults_lib

        loss, grads, metrics = faults_lib.inject(fault, loss, grads, metrics)
    if guard is not None:
        from repro.training import resilience

        gstate = state.guard
        if gstate is None:
            gstate = resilience.init_guard_state()
        grad_sq_norm = sum(
            jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(grads)
        )
        with jax.named_scope("opt"):
            new_params, new_opt_state, new_guard, healthy = resilience.guarded_update(
                optimizer, guard, grads, state.opt_state, state.params, gstate,
                loss, grad_sq_norm, phase,
            )
            new_opt_state = _constrain_opt(new_opt_state, opt_shardings)
            new_params = _pin(new_params, param_shardings)
        metrics = dict(metrics)
        metrics["grad_norm"] = jnp.sqrt(grad_sq_norm)
        metrics["healthy"] = healthy.astype(jnp.int32)
        metrics["skipped"] = new_guard.skipped
        metrics["ema_loss"] = resilience.debiased_ema(guard, new_guard)
        metrics["lr_scale"] = new_guard.lr_scale
        return TrainState(new_params, new_opt_state, state.step + 1, new_guard), metrics
    with jax.named_scope("opt"):
        updates, new_opt_state = optimizer.update(
            grads, state.opt_state, state.params, phase
        )
        new_opt_state = _constrain_opt(new_opt_state, opt_shardings)
        new_params = _pin(apply_updates(state.params, updates), param_shardings)
    metrics = dict(metrics)
    metrics["grad_norm"] = jnp.sqrt(
        sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(grads))
    )
    return TrainState(new_params, new_opt_state, state.step + 1, state.guard), metrics


def make_train_step_fns(cfg, optimizer, ctx, donate=True, compute_dtype=jnp.bfloat16,
                        accum_steps: int = 1, opt_shardings=None,
                        param_shardings=None, guard=None, fault=None,
                        phases=("block", "full")):
    """Returns {phase: jitted fn} over (state, batch), one per phase name.

    ``phases`` defaults to the synchronous pair; a staggered launcher passes
    ``StaggerSchedule.phases() + ('full',)`` so each step-residue gets its
    own compiled mixed-phase step (and the forced-full escalation keeps a
    'full' variant).
    """
    fns = {}
    for phase in phases:
        step = functools.partial(
            train_step,
            cfg=cfg,
            optimizer=optimizer,
            ctx=ctx,
            phase=phase,
            compute_dtype=compute_dtype,
            accum_steps=accum_steps,
            opt_shardings=opt_shardings,
            param_shardings=param_shardings,
            guard=guard,
            fault=fault,
        )
        fns[phase] = jax.jit(step, donate_argnums=(0,) if donate else ())
    return fns
