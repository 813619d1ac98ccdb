"""Train step factory: loss -> contributions -> exchange -> update.

The returned step works both single-device (axis_name=None on the
DistributedOptimizer) and inside ``shard_map`` over the data-parallel
mesh axes (the Horovod-faithful mode used by the launcher and the
multi-worker tests).

The step is a BucketSchedule consumer: the exchange is split out of the
optimizer update so the scheduled path (``ExchangeConfig(overlap=True)``)
can launch per-bucket collectives in reverse-layer readiness order,
interleaved with the remaining accumulation/pack compute, before any
bucket unpacks.  ``metrics["exchange_stages"]`` reports how many stages
the active schedule ran.

STATEFUL codecs (``opt.stateful``, e.g. ``codec="int8+ef"``) carry
their ExchangeState in the train-state pytree: the step signature
widens to ``step(params, opt_state, exchange_state, batch) -> (params,
opt_state, exchange_state, metrics)`` so the error-feedback residuals
flow step to step, jit to jit, and into checkpoints.  The factory tags
the returned step with ``step.stateful_exchange`` so Trainer and the
launchers pick the right calling convention.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core.dist_opt import DistributedOptimizer
from repro.optim.base import apply_updates
from repro.telemetry import hooks as scopes
from repro.training.gradients import (grad_contributions,
                                      wait_free_grad_exchange)


def make_train_step(model, opt: DistributedOptimizer,
                    sparse_embedding: bool = False,
                    **loss_kw) -> Callable:
    """Returns step(params, opt_state, batch) -> (params, opt_state,
    metrics) — or, when the optimizer's codec is stateful,
    step(params, opt_state, exchange_state, batch) -> (params,
    opt_state, exchange_state, metrics).

    With ``ExchangeConfig(zero1=True)`` the signatures are unchanged
    but ``opt_state`` is the sharded ``Zero1State`` (from
    ``opt.init_zero1_state``) and the step runs the fused ZeRO-1
    schedule instead of exchange-then-update."""
    cfg = getattr(opt, "exchange_config", None)
    overlap = cfg is not None and cfg.overlap
    wait_free = cfg is not None and cfg.overlap_backward
    stateful = cfg is not None and cfg.codec_obj.stateful
    zero1 = cfg is not None and cfg.zero1

    def _core(params, opt_state, batch, ex_state):
        if zero1:
            # ZeRO-1: the exchange IS the update — grad reduce-scatter,
            # flat-shard optimizer math on this worker's 1/P slice, and
            # the updated-param allgather run as ONE fused schedule.
            # ``opt_state`` is the Zero1State (sharded over the mesh).
            grads, loss, metrics = grad_contributions(
                model, params, batch, sparse_embedding=sparse_embedding,
                **loss_kw)
            with jax.named_scope(scopes.OPTIM):
                params, opt_state, ex_state = opt.zero1_step(
                    grads, params, opt_state, exchange_state=ex_state)
            n_stages = opt.plan(grads).schedule.n_stages
            metrics = dict(metrics, loss=loss,
                           exchange_stages=jnp.int32(n_stages))
            return params, opt_state, ex_state, metrics
        if wait_free:
            # overlap="backward": collectives launch from inside the
            # backward pass, per block, via custom_vjp taps
            dense, ex_state, loss, metrics = wait_free_grad_exchange(
                model, opt, params, batch, state=ex_state,
                sparse_embedding=sparse_embedding, **loss_kw)
            metrics = dict(metrics, loss=loss)
        else:
            grads, loss, metrics = grad_contributions(
                model, params, batch, sparse_embedding=sparse_embedding,
                **loss_kw)
            do_exchange = (opt.exchange_scheduled if overlap
                           else opt.exchange)
            if ex_state is None:
                dense = do_exchange(grads)
            else:
                dense, ex_state = do_exchange(grads, state=ex_state)
            n_stages = opt.plan(grads).schedule.n_stages
            metrics = dict(metrics, loss=loss,
                           exchange_stages=jnp.int32(n_stages))
        with jax.named_scope(scopes.OPTIM):
            updates, opt_state = opt.base.update(dense, opt_state, params)
            params = apply_updates(params, updates)
        return params, opt_state, ex_state, metrics

    if cfg is None:
        def step(params, opt_state, batch):   # plain Optimizer fallback
            grads, loss, metrics = grad_contributions(
                model, params, batch, sparse_embedding=sparse_embedding,
                **loss_kw)
            with jax.named_scope(scopes.OPTIM):
                updates, opt_state = opt.update(grads, opt_state, params)
                params = apply_updates(params, updates)
            return params, opt_state, dict(metrics, loss=loss)
    elif stateful:
        def step(params, opt_state, ex_state, batch):
            params, opt_state, ex_state, metrics = _core(
                params, opt_state, batch, ex_state)
            return params, opt_state, ex_state, metrics
    else:
        def step(params, opt_state, batch):
            params, opt_state, _, metrics = _core(params, opt_state,
                                                  batch, None)
            return params, opt_state, metrics

    step.stateful_exchange = stateful
    return step
