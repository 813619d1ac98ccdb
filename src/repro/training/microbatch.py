"""Microbatch gradient accumulation + dynamic loss scaling.

The paper trains with global batches up to 1.5M tokens by adding workers;
when HBM, not worker count, is the limit, the same global batch comes
from ACCUMULATING microbatch gradients locally before the (single)
cross-worker exchange — which also amortises the paper's collective cost
over more tokens.  ``accumulate_microbatches`` folds a (M, ...) stacked
batch through the loss with a lax.scan, summing LOCAL gradients; the
DistributedOptimizer then exchanges once.

``LossScaler`` implements standard dynamic loss scaling for bf16/f16
training (Ott et al. 2018, the paper's ref [12]): scale up every
``growth_interval`` good steps, halve and SKIP the step on non-finite
gradients.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.training.gradients import (grad_contributions,
                                      wait_free_grad_exchange)
from repro.core.indexed_slices import IndexedSlices
from repro.telemetry import hooks as scopes


def split_microbatches(batch: Dict[str, jax.Array], n: int
                       ) -> Dict[str, jax.Array]:
    """(B, ...) -> (n, B/n, ...) per leaf."""
    def split(x):
        b = x.shape[0]
        assert b % n == 0, (b, n)
        return x.reshape((n, b // n) + x.shape[1:])
    return jax.tree_util.tree_map(split, batch)


def _is_contrib_leaf(x) -> bool:
    return isinstance(x, (list, IndexedSlices))


def _make_combine(denom: int):
    """Per-leaf combiner: dense leaves summed, IndexedSlices
    concatenated, everything scaled by ``1/denom``."""
    def combine(*leaves):
        if isinstance(leaves[0], list):          # contribution lists
            out = []
            for contribs in zip(*leaves):
                if isinstance(contribs[0], IndexedSlices):
                    idx = jnp.concatenate([c.indices for c in contribs])
                    vals = jnp.concatenate([c.values
                                            for c in contribs]) / denom
                    out.append(IndexedSlices(idx, vals,
                                             contribs[0].dense_shape))
                else:
                    out.append(sum(contribs) / denom)
            return out
        return sum(leaves) / denom
    return combine


def _scale_contribs(grads, denom: int):
    """Scale every contribution (dense, IndexedSlices, or list) by
    ``1/denom`` without merging anything."""
    def scale(leaf):
        if isinstance(leaf, list):
            return [scale(c) for c in leaf]
        if isinstance(leaf, IndexedSlices):
            return IndexedSlices(leaf.indices, leaf.values / denom,
                                 leaf.dense_shape)
        return leaf / denom
    return jax.tree_util.tree_map(scale, grads, is_leaf=_is_contrib_leaf)


def _as_contrib_list(leaf) -> list:
    return list(leaf) if isinstance(leaf, list) else [leaf]


def accumulate_microbatches(model, params, stacked_batch,
                            sparse_embedding: bool = False,
                            defer_final: bool = False,
                            **loss_kw) -> Tuple[Any, jax.Array, Dict]:
    """Mean of per-microbatch gradients via lax.scan (O(1) live memory
    in the microbatch count).  Sparse embedding contributions are
    accumulated by CONCATENATION (the faithful representation: each
    microbatch contributes its own token rows) — so the paper's
    gather-vs-reduce choice applies to microbatching too.

    With ``defer_final=True`` (the overlap-scheduling hook) the FINAL
    microbatch's contribution is NOT folded into the running sum:
    every leaf comes back as a contribution list
    ``[partial_over_first_n-1, final]`` so a scheduled exchange
    (``ExchangeConfig(overlap=True)``) performs the remaining
    accumulation per stage, interleaved with earlier stages'
    already-launched collectives."""
    n = jax.tree_util.tree_leaves(stacked_batch)[0].shape[0]

    def one(mb):
        return grad_contributions(model, params, mb,
                                  sparse_embedding=sparse_embedding,
                                  **loss_kw)

    if not sparse_embedding:
        def body(carry, mb):
            acc, loss_sum = carry
            g, loss, _ = one(mb)
            acc = jax.tree_util.tree_map(jnp.add, acc, g)
            return (acc, loss_sum + loss), None

        mb0 = jax.tree_util.tree_map(lambda x: x[0], stacked_batch)
        g0, loss0, metrics0 = one(mb0)
        if defer_final and n > 1:
            # scan all but the last microbatch; the final one stays a
            # separate list entry for the scheduled exchange
            rest = jax.tree_util.tree_map(lambda x: x[1:-1],
                                          stacked_batch)
            (acc, loss_sum), _ = jax.lax.scan(body, (g0, loss0), rest)
            mb_last = jax.tree_util.tree_map(lambda x: x[-1],
                                             stacked_batch)
            g_last, loss_last, _ = one(mb_last)
            grads = jax.tree_util.tree_map(
                lambda a, b: [a / n, b / n], acc, g_last)
            return grads, (loss_sum + loss_last) / n, metrics0
        rest = jax.tree_util.tree_map(lambda x: x[1:], stacked_batch)
        (acc, loss_sum), _ = jax.lax.scan(body, (g0, loss0), rest)
        grads = jax.tree_util.tree_map(lambda g: g / n, acc)
        return grads, loss_sum / n, metrics0

    # sparse path: dense leaves summed, IndexedSlices concatenated —
    # python loop (contribution lists are not scan-able pytrees)
    grads_list, losses = [], []
    for i in range(n):
        mb = jax.tree_util.tree_map(lambda x: x[i], stacked_batch)
        g, loss, m = one(mb)
        grads_list.append(g)
        losses.append(loss)

    if defer_final and n > 1:
        partial = (grads_list[0] if n == 2 else jax.tree_util.tree_map(
            _make_combine(1), *grads_list[:-1], is_leaf=_is_contrib_leaf))
        partial = _scale_contribs(partial, n)
        final = _scale_contribs(grads_list[-1], n)
        grads = jax.tree_util.tree_map(
            lambda a, b: _as_contrib_list(a) + _as_contrib_list(b),
            partial, final, is_leaf=_is_contrib_leaf)
        return grads, sum(losses) / n, {}

    grads = jax.tree_util.tree_map(
        _make_combine(n), *grads_list,
        is_leaf=_is_contrib_leaf)
    return grads, sum(losses) / n, {}


def accumulate_partial_microbatches(model, params, stacked_batch,
                                    sparse_embedding: bool = False,
                                    **loss_kw):
    """First n-1 microbatches folded into the deferred ``partial``
    contribution — op for op the same computation as
    ``accumulate_microbatches(defer_final=True)``'s partial entry, so
    the two representations are bitwise interchangeable.  Returns
    ``(partial, final_microbatch, partial_loss_sum, n)``; the wait-free
    step (``overlap="backward"``) differentiates only the FINAL
    microbatch and folds ``partial`` in per block inside the backward
    pass.  ``partial`` is ``None`` when there is only one microbatch."""
    n = jax.tree_util.tree_leaves(stacked_batch)[0].shape[0]
    mb_last = jax.tree_util.tree_map(lambda x: x[-1], stacked_batch)
    if n == 1:
        return None, mb_last, jnp.float32(0.0), n

    def one(mb):
        return grad_contributions(model, params, mb,
                                  sparse_embedding=sparse_embedding,
                                  **loss_kw)

    if not sparse_embedding:
        def body(carry, mb):
            acc, loss_sum = carry
            g, loss, _ = one(mb)
            acc = jax.tree_util.tree_map(jnp.add, acc, g)
            return (acc, loss_sum + loss), None

        mb0 = jax.tree_util.tree_map(lambda x: x[0], stacked_batch)
        g0, loss0, _ = one(mb0)
        rest = jax.tree_util.tree_map(lambda x: x[1:-1], stacked_batch)
        (acc, loss_sum), _ = jax.lax.scan(body, (g0, loss0), rest)
        partial = jax.tree_util.tree_map(lambda a: a / n, acc)
        return partial, mb_last, loss_sum, n

    grads_list, losses = [], []
    for i in range(n - 1):
        mb = jax.tree_util.tree_map(lambda x: x[i], stacked_batch)
        g, loss, _ = one(mb)
        grads_list.append(g)
        losses.append(loss)
    partial = (grads_list[0] if n == 2 else jax.tree_util.tree_map(
        _make_combine(1), *grads_list, is_leaf=_is_contrib_leaf))
    partial = _scale_contribs(partial, n)
    return partial, mb_last, sum(losses), n


def _scale_grad_tree(grads, scale):
    """Multiply every contribution (dense, list, IndexedSlices) by the
    loss scale — the post-hoc grad scaling the fused path applies."""
    return jax.tree_util.tree_map(
        lambda g: g * scale if not isinstance(g, list)
        else [c * scale if not isinstance(c, IndexedSlices)
              else IndexedSlices(c.indices, c.values * scale,
                                 c.dense_shape) for c in g],
        grads, is_leaf=lambda x: isinstance(x, list))


class ScalerState(NamedTuple):
    scale: jax.Array           # current loss scale
    good_steps: jax.Array      # consecutive finite-grad steps


@dataclasses.dataclass(frozen=True)
class LossScaler:
    init_scale: float = 2.0 ** 15
    growth_factor: float = 2.0
    backoff_factor: float = 0.5
    growth_interval: int = 200

    def init(self) -> ScalerState:
        return ScalerState(scale=jnp.float32(self.init_scale),
                           good_steps=jnp.int32(0))

    def scale_loss(self, loss: jax.Array, state: ScalerState) -> jax.Array:
        return loss * state.scale

    def unscale_and_check(self, grads, state: ScalerState):
        """Returns (unscaled grads, finite flag, new state).  On overflow
        the caller must SKIP the update (see make_scaled_train_step)."""
        finite = jnp.array(True)
        for g in jax.tree_util.tree_leaves(grads):
            finite &= jnp.all(jnp.isfinite(g))
        grads = jax.tree_util.tree_map(
            lambda g: (g / state.scale).astype(g.dtype), grads)
        new_scale = jnp.where(
            finite,
            jnp.where(state.good_steps + 1 >= self.growth_interval,
                      state.scale * self.growth_factor, state.scale),
            jnp.maximum(state.scale * self.backoff_factor, 1.0))
        new_good = jnp.where(
            finite,
            jnp.where(state.good_steps + 1 >= self.growth_interval,
                      0, state.good_steps + 1),
            0)
        return grads, finite, ScalerState(new_scale, new_good)


def make_scaled_train_step(model, opt, scaler: LossScaler,
                           n_microbatches: int = 1,
                           sparse_embedding: bool = False,
                           **loss_kw) -> Callable:
    """Train step with loss scaling + optional microbatch accumulation.
    Overflow steps leave params/opt_state untouched (scale backs off).

    When the optimizer's ``ExchangeConfig`` has ``overlap=True`` the
    final microbatch's gradient is handed to the exchange UNSUMMED
    (``defer_final``): the staged BucketSchedule folds it in per
    bucket, so each stage's remaining accumulation compute runs after
    the previous stage's collective has already launched.

    Stateful codecs widen the signature to ``step(params, opt_state,
    scaler_state, exchange_state, batch)`` (returning the new
    ExchangeState second-from-last, before metrics); on
    overflow-skipped steps the
    residuals roll back with params/opt_state — a non-finite encode
    would bank inf-inf = NaN residuals and poison every later wire.
    Like the gradients themselves, residuals live in scaled units, so
    whenever the scaler moves (growth or backoff) they are multiplied
    by ``new_scale / old_scale`` to match the next step's grads."""
    from repro.optim.base import apply_updates

    cfg = getattr(opt, "exchange_config", None)
    wait_free = cfg is not None and cfg.overlap_backward
    defer_final = (cfg is not None and cfg.overlap and not wait_free
                   and n_microbatches > 1)
    stateful = cfg is not None and cfg.codec_obj.stateful

    def _core(params, opt_state, scaler_state, batch, ex_state):
        old_scale = scaler_state.scale
        prev_ex_state = ex_state
        if wait_free:
            # overlap="backward": differentiate only the FINAL
            # microbatch; its block cotangents trigger the collectives
            # mid-backward, each stage folding in the (already-scaled)
            # partial sum of the first n-1 microbatches.  Loss scaling
            # multiplies the LOSS pre-differentiation — power-of-2
            # scales commute bitwise with post-hoc grad scaling.
            if n_microbatches > 1:
                stacked = split_microbatches(batch, n_microbatches)
                partial, mb_last, loss_sum, _n = \
                    accumulate_partial_microbatches(
                        model, params, stacked,
                        sparse_embedding=sparse_embedding, **loss_kw)
                partial = _scale_grad_tree(partial, old_scale)
            else:
                partial, mb_last, loss_sum = None, batch, None
            dense, ex_state, loss_last, metrics = wait_free_grad_exchange(
                model, opt, params, mb_last, state=ex_state,
                sparse_embedding=sparse_embedding, partial=partial,
                loss_scale=old_scale, loss_denom=n_microbatches,
                **loss_kw)
            loss = (loss_last if loss_sum is None
                    else (loss_sum + loss_last) / n_microbatches)
        else:
            def loss_fn(p, b):
                if n_microbatches > 1:
                    stacked = split_microbatches(b, n_microbatches)
                    g, loss, metrics = accumulate_microbatches(
                        model, p, stacked,
                        sparse_embedding=sparse_embedding,
                        defer_final=defer_final, **loss_kw)
                else:
                    g, loss, metrics = grad_contributions(
                        model, p, b, sparse_embedding=sparse_embedding,
                        **loss_kw)
                return g, loss, metrics

            # scale by differentiating the SCALED loss: equivalent to
            # grad*scale
            grads, loss, metrics = loss_fn(params, batch)
            grads = _scale_grad_tree(grads, scaler_state.scale)
            if ex_state is None:
                dense = opt.exchange(grads)
            else:
                dense, ex_state = opt.exchange(grads, state=ex_state)
        dense, finite, scaler_state = scaler.unscale_and_check(
            dense, scaler_state)
        with jax.named_scope(scopes.OPTIM):
            updates, new_opt_state = opt.base.update(dense, opt_state,
                                                     params)
            new_params = apply_updates(params, updates)
            params = jax.tree_util.tree_map(
                lambda new, old: jnp.where(finite, new, old),
                new_params, params)
            opt_state = jax.tree_util.tree_map(
                lambda new, old: jnp.where(finite, new, old),
                new_opt_state, opt_state)
        if ex_state is not None:
            # an overflowed encode banks inf-inf = NaN residuals that
            # would poison every later step's wire
            ex_state = jax.tree_util.tree_map(
                lambda new, old: jnp.where(finite, new, old),
                ex_state, prev_ex_state)
            # residuals live in loss-scaled units: when the scaler moves
            # (growth or backoff) convert them to the units the next
            # step's grads will carry, or EF compensates at the wrong
            # magnitude across every scale transition
            rescale = jnp.where(scaler_state.scale == old_scale,
                                jnp.float32(1.0),
                                scaler_state.scale / old_scale)
            ex_state = jax.tree_util.tree_map(
                lambda r: r * rescale, ex_state)
        metrics = dict(metrics, loss=loss,
                       loss_scale=scaler_state.scale,
                       overflow=~finite)
        return params, opt_state, scaler_state, ex_state, metrics

    if stateful:
        def step(params, opt_state, scaler_state, ex_state, batch):
            return _core(params, opt_state, scaler_state, batch, ex_state)
    else:
        def step(params, opt_state, scaler_state, batch):
            params, opt_state, scaler_state, _, metrics = _core(
                params, opt_state, scaler_state, batch, None)
            return params, opt_state, scaler_state, metrics

    step.stateful_exchange = stateful
    return step
