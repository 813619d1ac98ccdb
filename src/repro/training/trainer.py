"""Trainer: the end-to-end loop (data -> step -> metrics -> checkpoint)."""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from repro.checkpoint import save_checkpoint, restore_checkpoint, latest_step
from repro.telemetry import hooks
from repro.telemetry.trace import write_step_hlo


@dataclasses.dataclass
class TrainerConfig:
    total_steps: int = 100
    log_every: int = 10
    checkpoint_every: int = 0          # 0 disables
    checkpoint_dir: Optional[str] = None
    resume: bool = False
    # profile the last ``profile_steps`` steps of ``run`` with
    # ``jax.profiler`` into ``profile_dir`` (an .xplane.pb and a
    # Perfetto trace under plugins/profile/, and the compiled step's
    # HLO text, whose metadata names each device op's scope; read them
    # with telemetry.trace.load_profile)
    profile_dir: Optional[str] = None
    profile_steps: int = 0


@dataclasses.dataclass
class Trainer:
    model: Any
    step_fn: Callable                   # (params, opt_state, batch) -> ...
    pipeline: Any                       # iterable of host batches
    config: TrainerConfig
    recorder: Any = None                # telemetry.metrics.StepRecorder
    # where each global batch is placed (data-parallel runs shard it
    # over the workers); None leaves it on the default device
    batch_sharding: Any = None

    def run(self, params, opt_state, log: Callable[[str], None] = print,
            exchange_state: Any = None) -> Dict[str, Any]:
        """Run the loop.  The step is jitted with the train state donated:
        ``run`` owns the ``params``, ``opt_state`` and ``exchange_state``
        it is given, their buffers are reused for the step's outputs
        (the passed-in arrays are deleted after the first step), and
        the caller continues from the state ``run`` returns.  A caller
        that needs the starting state afterwards passes a copy.  The
        batch is never donated.

        ``exchange_state`` (an ``ExchangeState`` from
        ``opt.init_exchange_state``) switches the step to the stateful
        calling convention — the codec residuals then ride the train
        state: threaded through every jit_step, saved in every
        checkpoint, and restored on resume so a mid-run restart picks
        up with identical residuals.

        With a ``recorder`` (``telemetry.metrics.StepRecorder``) every
        step additionally records ``step_ms`` split into ``data_ms``
        (host batch fetch) vs ``compute_ms``, per-step loss/overflow,
        and streams the rows to the recorder's JSONL sink at each log
        boundary."""
        cfg = self.config
        rec = self.recorder
        stateful = exchange_state is not None
        start_step = 0
        if cfg.resume and cfg.checkpoint_dir:
            s = latest_step(cfg.checkpoint_dir)
            if s is not None:
                if stateful:
                    (params, opt_state, exchange_state), start_step = \
                        restore_checkpoint(
                            cfg.checkpoint_dir,
                            (params, opt_state, exchange_state), step=s)
                else:
                    (params, opt_state), start_step = restore_checkpoint(
                        cfg.checkpoint_dir, (params, opt_state), step=s)
                log(f"resumed from step {start_step}")

        # one copy of the train state on the device: each step's outputs
        # take the buffers of its inputs
        jit_step = jax.jit(self.step_fn,
                           donate_argnums=(0, 1, 2) if stateful else (0, 1))
        history: List[Dict[str, float]] = []
        tokens_seen = 0
        overflow_pending: List[Any] = []  # un-synced device bools
        overflow_skipped = 0
        t0 = time.perf_counter()
        window_t0, window_steps = t0, 0
        window_data_ms = 0.0
        profile_from = (max(start_step, cfg.total_steps - cfg.profile_steps)
                        if cfg.profile_dir and cfg.profile_steps > 0
                        else None)
        batch = None
        with contextlib.ExitStack() as profiling:
            for step in range(start_step, cfg.total_steps):
                if step == profile_from:
                    # start on an idle device, so that the capture holds
                    # the device work of the profiled steps alone
                    jax.block_until_ready(params)
                    profiling.enter_context(jax.profiler.trace(
                        cfg.profile_dir, create_perfetto_trace=True))
                step_span = jax.profiler.StepTraceAnnotation(
                    hooks.STEP, step_num=step)
                with step_span:
                    if rec is not None:
                        rec.step_start()
                    t_fetch = time.perf_counter()
                    with jax.profiler.TraceAnnotation(hooks.FETCH):
                        host = self.pipeline.batch_at(step)
                    with jax.profiler.TraceAnnotation(hooks.DEVICE_PUT):
                        batch = {k: jax.device_put(v, self.batch_sharding)
                                 for k, v in host.items()}
                    data_ms = (time.perf_counter() - t_fetch) * 1e3
                    window_data_ms += data_ms
                    if rec is not None:
                        rec.data_loaded()
                    with jax.profiler.TraceAnnotation(hooks.DISPATCH):
                        if stateful:
                            params, opt_state, exchange_state, metrics = \
                                jit_step(params, opt_state, exchange_state,
                                         batch)
                        else:
                            params, opt_state, metrics = jit_step(
                                params, opt_state, batch)
                    # defer the device->host read of the loss-scaler
                    # overflow flag to the log boundary (no per-step sync
                    # on the default path); overflow steps are skipped
                    # updates (the scaler rolls the state back)
                    if "overflow" in metrics:
                        overflow_pending.append(metrics["overflow"])
                    if rec is not None:
                        rec.step_end(metrics)
                    tokens_seen += int(np.prod(batch["tokens"].shape))
                    window_steps += 1
                    if ((step + 1) % cfg.log_every == 0
                            or step == cfg.total_steps - 1):
                        with jax.profiler.TraceAnnotation(hooks.LOG):
                            m = {k: float(v) for k, v in metrics.items()
                                 if np.ndim(v) == 0}
                            now = time.perf_counter()
                            dt = now - t0
                            if overflow_pending:
                                overflow_skipped += int(sum(
                                    int(np.asarray(o))
                                    for o in overflow_pending))
                                overflow_pending.clear()
                            # mean wall-time per step since the last log
                            # line (the number the overlap benchmark
                            # compares on/off), with the host data fetch
                            # split out
                            m.update(step=step + 1, tokens=tokens_seen,
                                     tok_per_s=tokens_seen / max(dt, 1e-9),
                                     step_ms=(now - window_t0) * 1e3
                                     / max(window_steps, 1),
                                     data_ms=window_data_ms
                                     / max(window_steps, 1),
                                     overflow_skipped=overflow_skipped)
                            window_t0, window_steps = now, 0
                            window_data_ms = 0.0
                            history.append(m)
                            skipped = (f" overflow_skipped={overflow_skipped}"
                                       if overflow_skipped else "")
                            log(f"step {step+1}: "
                                f"loss={m.get('loss', float('nan')):.4f} "
                                f"ce={m.get('ce', float('nan')):.4f} "
                                f"tok/s={m['tok_per_s']:.0f} "
                                f"step_ms={m['step_ms']:.1f} "
                                f"data_ms={m['data_ms']:.2f}{skipped}")
                            if rec is not None:
                                rec.flush()
                    if (cfg.checkpoint_every and cfg.checkpoint_dir
                            and (step + 1) % cfg.checkpoint_every == 0):
                        with jax.profiler.TraceAnnotation(hooks.CHECKPOINT):
                            tree = ((params, opt_state, exchange_state)
                                    if stateful else (params, opt_state))
                            save_checkpoint(cfg.checkpoint_dir, step + 1,
                                            tree)
            if profile_from is not None:
                # the last step's device work belongs in the profile
                jax.block_until_ready(params)
        if profile_from is not None and batch is not None:
            args = ((params, opt_state, exchange_state, batch) if stateful
                    else (params, opt_state, batch))
            write_step_hlo(cfg.profile_dir, jit_step, args, len(args) - 1)
        if rec is not None:
            rec.flush()
        return {"params": params, "opt_state": opt_state,
                "exchange_state": exchange_state, "history": history}
