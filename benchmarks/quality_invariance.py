"""Paper Fig. 12 (mechanism): translation quality is unchanged by the
accumulation strategy and robust across (scaled-down) batch sizes.

BLEU on WMT17 is unavailable offline; the paper's Fig. 12 claim rests on
the fix being MATHEMATICALLY NEUTRAL (same gradients -> same model) plus
large-batch training remaining stable.  We verify both at CPU scale on
the synthetic translation task: (a) gather vs reduce training runs are
bit-compatible within tolerance, (b) final loss is comparable across a
4x batch-size range (the paper's 402k -> 1M token range, scaled).

(c) extends the quality story to QUANTISED wires: an int8 wire is NOT
mathematically neutral (per-bucket absmax rounding discards gradient
mass every step), so fixed-step final loss opens a gap against the fp32
wire; the stateful error-feedback codec ("int8+ef") banks each step's
rounding error and folds it into the next encode, and must close at
least half of that gap — the convergence contract the stateful codec
API exists to deliver."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import DistributedOptimizer, ExchangeConfig
from repro.data import make_pipeline
from repro.models import build_model
from repro.optim import adamw
from repro.training import Trainer, TrainerConfig, make_train_step
from repro.training.gradients import abstract_grad_contributions

STEPS = 120


def _train(cfg, model, params, sad: bool, batch: int, steps=STEPS,
           lr=1e-2, codec: str = "identity", error_feedback: bool = False,
           fusion_threshold=None, state_dtype: str = "float32"):
    opt = DistributedOptimizer(
        adamw(lr, state_dtype=state_dtype), exchange=ExchangeConfig(
            sparse_as_dense=sad, codec=codec,
            error_feedback=error_feedback,
            fusion_threshold=fusion_threshold))
    step = make_train_step(model, opt, sparse_embedding=True)
    pipe = make_pipeline(cfg, batch_per_host=batch, seq_len=32,
                         task="copy")
    ex_state = None
    if opt.stateful:
        b0 = {k: jnp.asarray(v) for k, v in pipe.batch_at(0).items()}
        g = abstract_grad_contributions(model, params, b0,
                                        sparse_embedding=True)
        ex_state = opt.init_exchange_state(g)
    tr = Trainer(model, step, pipe, TrainerConfig(total_steps=steps,
                                                  log_every=steps))
    # ``run`` consumes the state it is given; callers reuse ``params``
    start = jax.tree_util.tree_map(jnp.copy, params)
    res = tr.run(start, opt.init(start), log=lambda s: None,
                 exchange_state=ex_state)
    return res["history"][-1]["loss"], res["params"]


def run(emit):
    cfg = get_config("transformer-big").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    # (a) strategy invariance
    loss_g, pg = _train(cfg, model, params, sad=False, batch=8)
    loss_r, pr = _train(cfg, model, params, sad=True, batch=8)
    diff = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32))))
               for a, b in zip(jax.tree_util.tree_leaves(pg),
                               jax.tree_util.tree_leaves(pr)))
    emit("fig12_strategy_invariance", 0.0,
         f"param_maxdiff{diff:.2e}_lossg{loss_g:.3f}_lossr{loss_r:.3f}")

    # (b) batch-size robustness (scaled stand-in for 402k/630k/1M)
    losses = {}
    for batch in (4, 8, 16):
        # keep tokens-seen constant: fewer steps at larger batch
        steps = STEPS * 8 // batch
        losses[batch], _ = _train(cfg, model, params, sad=True,
                                  batch=batch, steps=steps)
        emit(f"fig12_loss_gbz{batch * 32}tok", 0.0,
             f"{losses[batch]:.4f}")
    spread = max(losses.values()) - min(losses.values())
    emit("fig12_batch_robustness", 0.0,
         f"loss_spread{spread:.3f}_"
         f"{'PASS' if spread < 1.0 else 'WIDE'}")

    # (c) quantised-wire convergence + error feedback.  One Horovod-size
    # fusion bucket (single absmax per ~1 MiB buffer) is the realistic
    # worst case for per-bucket int8; the three runs share init, data
    # and step count, so any final-loss delta is wire-induced.
    wire_kw = dict(sad=True, batch=8, fusion_threshold=1 << 20)
    loss_f32, _ = _train(cfg, model, params, **wire_kw)
    loss_q8, _ = _train(cfg, model, params, codec="int8", **wire_kw)
    loss_ef, _ = _train(cfg, model, params, codec="int8",
                        error_feedback=True, **wire_kw)
    emit("wire_fp32_final_loss", 0.0, f"{loss_f32:.4f}")
    emit("wire_int8_final_loss", 0.0, f"{loss_q8:.4f}")
    emit("wire_int8_ef_final_loss", 0.0, f"{loss_ef:.4f}")
    gap = loss_q8 - loss_f32
    # a gap at or below the run-to-run noise floor leaves EF nothing to
    # close — dividing by it would flip sign or explode, so declare the
    # contract met outright instead
    noise_floor = 0.02
    if gap <= noise_floor:
        closure = 1.0
    else:
        closure = (loss_q8 - loss_ef) / gap
    emit("ef_gap_closure", 0.0,
         f"gap{gap:.4f}_closure{closure:.2f}_"
         f"{'PASS' if closure >= 0.5 else 'FAIL'}")

    # (d) quantised OPTIMIZER STATE: adamw(state_dtype="bfloat16")
    # halves the mu/nu storage (the ZeRO-1 memory row's bf16 variant);
    # the update math still runs in f32 after upcasting, so the final
    # loss must stay within the run-to-run noise floor of fp32 state
    loss_bf16, _ = _train(cfg, model, params, sad=True, batch=8,
                          state_dtype="bfloat16")
    state_gap = abs(loss_bf16 - loss_r)
    emit("optstate_bf16_final_loss", 0.0, f"{loss_bf16:.4f}")
    emit("optstate_bf16_invariance", 0.0,
         f"gap{state_gap:.4f}_vs_floor{noise_floor}_"
         f"{'PASS' if state_gap <= noise_floor else 'FAIL'}")
