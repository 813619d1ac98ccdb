"""Training launcher CLI.

Two distribution modes:

  * ``--dist local``   — single process/device (CPU dev loop, examples).
  * ``--dist horovod`` — Horovod-faithful: ``shard_map`` over the data
    axes with EXPLICIT gradient collectives chosen by the accumulation
    strategy (the paper's mechanism, end to end).  Uses however many
    devices the current backend exposes (use
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` to emulate N
    MPI processes on CPU, exactly like the paper's `mpirun -np N`).

Strategy flags map 1:1 to the paper:
  --grad-accum sparse_gather   TF Algorithm 1 (gather; the pathology)
  --grad-accum dense_reduce    sparse_as_dense=True (the paper's fix)

Example:
  PYTHONPATH=src XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python -m repro.launch.train --arch transformer-big --reduced \
    --dist horovod --grad-accum dense_reduce --steps 50
"""
from __future__ import annotations

import argparse
import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from repro.configs import get_config
from repro.core import (DistributedOptimizer, ExchangeConfig,
                        available_backends, available_codecs)
from repro.data import make_pipeline
from repro.launch.cache import enable_compile_cache
from repro.models import build_model
from repro.optim import adamw, noam_schedule
from repro.training import Trainer, TrainerConfig, make_train_step


TRACE_STEPS = 2     # steps at the end of a run that --trace-dir profiles


def dist_axes(args, backend=None):
    """Mesh axis names for --dist horovod (the hierarchical backend
    spans two axes: within-pod + cross-pod).  ``backend`` overrides
    ``args.backend`` — a ``--tuned`` config decides the mesh shape."""
    if args.dist != "horovod":
        return None
    b = backend if backend is not None else args.backend
    return ("pod", "data") if b == "hierarchical" else ("data",)


def build_optimizer(args, cfg,
                    exchange: ExchangeConfig = None) -> DistributedOptimizer:
    base = adamw(noam_schedule(cfg.d_model, warmup_steps=args.warmup))
    if exchange is None:
        exchange = ExchangeConfig(
            sparse_as_dense=args.grad_accum == "dense_reduce",
            algorithm=args.algorithm,
            fusion_threshold=args.fusion_threshold,
            reduce_scatter=args.reduce_scatter,
            wire_dtype=args.wire_dtype,
            codec=args.codec,
            backend=args.backend,
            overlap=args.overlap or False,
            error_feedback=args.error_feedback,
            zero1=getattr(args, "zero1", False),
            param_codec=getattr(args, "param_codec", "identity"),
        )
    axis = dist_axes(args, backend=exchange.backend)
    return DistributedOptimizer(base, exchange=exchange, axis_name=axis)


def resolve_tuned_exchange(args, cfg, model, params,
                           sparse_embedding: bool,
                           n_dev: int) -> ExchangeConfig:
    """--tuned: resolve the cached tuning artifact for this (model,
    workers, profile) key and return its winning ExchangeConfig.  On a
    cache miss, warn and fall back to an analytic-only search (saved,
    so the next launch hits the cache)."""
    from repro.training.gradients import abstract_grad_contributions
    from repro.tuning import load_tuned_config, save_artifact
    from repro.tuning import search as run_search

    pipe = make_pipeline(cfg, batch_per_host=args.batch_per_worker,
                         seq_len=args.seq_len, seed=args.seed,
                         task=args.task)
    b0 = {k: jnp.asarray(v) for k, v in pipe.batch_at(0).items()}
    g = abstract_grad_contributions(model, params, b0,
                                    sparse_embedding=sparse_embedding)
    workers = n_dev if args.dist == "horovod" else 1
    doc = load_tuned_config(g, workers, args.profile, args.tune_cache)
    if doc is not None:
        print(f"tuned exchange: {doc['winner_label']} "
              f"(artifact {doc['path']})")
        return doc["exchange_config"]
    print(f"warning: no tuning artifact for (arch={args.arch}, "
          f"P={workers}, profile={args.profile}) under {args.tune_cache} "
          f"— run dryrun --tune; falling back to analytic search",
          file=sys.stderr)
    res = run_search(g, workers, profile=args.profile, trials=0)
    path = save_artifact(res, args.tune_cache)
    print(f"tuned exchange (analytic, cached -> {path}): "
          f"{res.winner.label}")
    return res.winner.config


def abstract_worker_grads(args, model, params, pipe,
                          sparse_embedding: bool):
    """One per-worker gradient-contribution tree, traced abstractly
    (eval_shape, no compute) — the structure the ExchangePlan and its
    ExchangeState are keyed on."""
    from repro.training.gradients import abstract_grad_contributions
    b0 = {k: jnp.asarray(v)[:args.batch_per_worker]
          for k, v in pipe.batch_at(0).items()}
    return abstract_grad_contributions(model, params, b0,
                                       sparse_embedding=sparse_embedding)


def print_exchange_schedule(args, model, params, opt, pipe,
                            sparse_embedding: bool, n_dev: int):
    """Print the plan's BucketSchedule — what the step will actually
    run, stage by stage, including codec-state (residual) memory and
    the per-hop wire split on hierarchical runs.  Returns the abstract
    gradient tree (one ``jax.eval_shape`` trace of the full model —
    callers reuse it for ``init_exchange_state``), or ``None`` if the
    trace failed."""
    g = None
    try:
        g = abstract_worker_grads(args, model, params, pipe,
                                  sparse_embedding)
        if args.dist != "horovod":
            workers = 1
        elif opt.exchange_config.backend == "hierarchical":
            workers = (2, n_dev // 2)
        else:
            workers = n_dev
        print(opt.exchange_stats(
            g, n_workers=workers,
            profile=getattr(args, "profile", "ib")).describe())
    except Exception as e:                       # informational only
        print(f"(exchange schedule unavailable: {e})")
    return g


def place_on_mesh(tree, mesh, spec):
    """``device_put`` a train-state tree where the ``shard_map``'d step
    returns it (``spec``: one PartitionSpec or a tree of them), so the
    first step compiles the program every later step reuses."""
    return jax.device_put(tree, jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec,
        is_leaf=lambda s: isinstance(s, P)))


def write_exchange_meta(args, opt, model, params, pipe, g, step_fn,
                        result, n_dev, sparse_embedding) -> None:
    """--trace-dir: beside the profile that ``Trainer.run`` captured,
    write the plan's ``exchange.json`` (stage names, wire accounting,
    the tuner's prediction, and the wire bytes one abstract evaluation
    of the step bills per stage) and print the report."""
    from repro.telemetry import report as report_lib
    from repro.telemetry import trace as trace_lib

    if g is None:
        g = abstract_worker_grads(args, model, params, pipe,
                                  sparse_embedding)
    plan = opt.plan(g)
    batch = {k: jnp.asarray(v) for k, v in pipe.batch_at(0).items()}
    fn_args = (result["params"], result["opt_state"])
    if result["exchange_state"] is not None:
        fn_args += (result["exchange_state"],)
    if args.dist == "horovod":
        n_workers = ((2, n_dev // 2)
                     if opt.exchange_config.backend == "hierarchical"
                     else n_dev)
    else:
        n_workers = 1
    wire = trace_lib.measure_wire(step_fn, *fn_args, batch)
    meta = trace_lib.plan_trace_meta(plan, n_workers, profile=args.profile,
                                     measured=wire)
    meta.update(arch=args.arch, dist=args.dist, steps=args.steps,
                trace_steps=TRACE_STEPS)
    trace_lib.write_meta(meta, args.trace_dir)
    print(f"trace written: {args.trace_dir}")
    summary = report_lib.summarize_profile(args.trace_dir)
    print("per step, ms: " + "  ".join(
        f"{k}={v:.3f}" for k, v in summary["layers_ms"].items()))
    print(report_lib.render_table(summary["rows"]))


def train(argv=None) -> dict:
    """Parse the CLI, build model, optimizer and mesh, and train.

    Returns the ``Trainer.run`` result (final ``params``, ``opt_state``,
    ``exchange_state`` and the logged ``history``) plus the resolved
    ``config`` and worker count ``n_workers``; ``main`` is the CLI shell
    around it."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="transformer-big")
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-sized variant of the arch")
    ap.add_argument("--dist", default="local",
                    choices=["local", "horovod"])
    ap.add_argument("--grad-accum", default="dense_reduce",
                    choices=["sparse_gather", "dense_reduce"])
    ap.add_argument("--algorithm", default="tf_algorithm1",
                    choices=["tf_algorithm1", "proposed_algorithm2"])
    ap.add_argument("--fusion-threshold", type=int, default=None)
    ap.add_argument("--reduce-scatter", action="store_true",
                    help="exchange dense buckets via reduce-scatter + "
                         "allgather (ZeRO-style) instead of allreduce")
    ap.add_argument("--wire-dtype", default=None,
                    choices=[None, "bf16", "bfloat16", "f16", "float16"],
                    help="deprecated spelling of --codec: downcast "
                         "fusion buffers to this dtype on the wire")
    # choices/help enumerate the LIVE registries so the text can never
    # drift from what is actually registered (e.g. fp8 availability
    # depends on the installed jax exposing native float8 dtypes)
    ap.add_argument("--codec", default="identity",
                    help="WireCodec registry name for the gradient wire "
                         f"(registered: {', '.join(available_codecs())}; "
                         "append '+ef' to any name — or pass "
                         "--error-feedback — for quantisation-residual "
                         "error feedback)")
    ap.add_argument("--backend", default="jax",
                    help="CollectiveBackend registry name (registered: "
                         f"{', '.join(available_backends())})")
    ap.add_argument("--error-feedback", action="store_true",
                    help="wrap the codec in ErrorFeedbackCodec: keep a "
                         "per-bucket f32 residual of the wire's "
                         "quantisation error and fold it into the next "
                         "step's encode (threads an ExchangeState "
                         "through the train state and checkpoints)")
    ap.add_argument("--overlap", nargs="?", const="staged", default=None,
                    choices=["staged", "backward"],
                    help="comm/compute overlap mode. 'staged' (also the "
                         "bare-flag default): launch per-bucket "
                         "collectives in reverse-layer readiness order, "
                         "interleaved with the remaining accumulation "
                         "compute, before any bucket unpacks. "
                         "'backward': wait-free backprop — buckets are "
                         "block-aligned and each block's collective "
                         "launches from inside the backward pass, the "
                         "moment its cotangents are emitted")
    ap.add_argument("--zero1", action="store_true",
                    help="ZeRO-1: reduce-scatter each dense bucket's "
                         "gradient, run the optimizer on this worker's "
                         "1/P flat shard of (f32 master params + EMA "
                         "state), and allgather the UPDATED params back "
                         "through the same bucket schedule — P-fold "
                         "optimizer-state memory cut at allreduce-equal "
                         "wire cost (see docs/zero.md)")
    ap.add_argument("--param-codec", default="identity",
                    help="WireCodec for the zero1 updated-param "
                         "allgather (stateless codecs only; default "
                         "identity keeps the step bitwise-identical to "
                         "the replicated path)")
    ap.add_argument("--batch-per-worker", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--warmup", type=int, default=400)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--task", default="lm", choices=["lm", "translation"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tuned", action="store_true",
                    help="configure the exchange from the cached "
                         "autotuner artifact for this (model, workers, "
                         "--profile) instead of the exchange flags "
                         "(produce one with dryrun --tune); a cache "
                         "miss warns and falls back to an analytic "
                         "search")
    ap.add_argument("--profile", default="ethernet",
                    help="BandwidthProfile preset name or JSON path "
                         "(tuning key + predicted_comm_us estimates)")
    ap.add_argument("--tune-cache", default=None,
                    help="tuning artifact directory (default: the "
                         "repo-wide experiments/tuning)")
    ap.add_argument("--metrics-jsonl", default=None,
                    help="stream per-step metrics (loss, step_ms split "
                         "into data_ms/compute_ms, tok/s, overflow-"
                         "skipped steps) and the run history to this "
                         "JSONL file (see docs/observability.md)")
    ap.add_argument("--trace-dir", default=None,
                    help=f"profile the loop's last {TRACE_STEPS} steps "
                         "with jax.profiler into this directory (a "
                         "Perfetto trace, the compiled step's HLO text "
                         "and the plan's exchange.json with runtime "
                         "wire-byte counters); summarize with "
                         "scripts/trace_report.py")
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.tune_cache is None:
        from repro.tuning.search import DEFAULT_CACHE_DIR
        args.tune_cache = DEFAULT_CACHE_DIR

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    # the instrumented sparse path is the whole point in horovod mode
    sparse_embedding = args.dist == "horovod" or \
        args.grad_accum == "sparse_gather"
    n_dev = len(jax.devices())
    tuned_exchange = None
    if args.tuned:
        tuned_exchange = resolve_tuned_exchange(
            args, cfg, model, params, sparse_embedding, n_dev)
    opt = build_optimizer(args, cfg, exchange=tuned_exchange)
    step = make_train_step(model, opt, sparse_embedding=sparse_embedding)

    stateful = step.stateful_exchange
    zero1 = opt.zero1
    mesh = axes = pspec_batch = None
    if args.dist == "horovod":
        axes = dist_axes(args, backend=opt.exchange_config.backend)
        if len(axes) == 2:
            if n_dev % 2:
                raise SystemExit("hierarchical backend needs an even "
                                 "worker count (2 emulated pods)")
            shape = (2, n_dev // 2)
        else:
            shape = (n_dev,)
        mesh = Mesh(np.array(jax.devices()).reshape(shape), axes)
        pspec_batch = P(axes)
        batch_sharding = NamedSharding(mesh, pspec_batch)
        batch_per_host = args.batch_per_worker * n_dev
        print(f"horovod mode: {n_dev} workers ({'x'.join(map(str, shape))}"
              f" {'/'.join(axes)}), global batch "
              f"{batch_per_host}x{args.seq_len} tokens")
    else:
        batch_per_host = args.batch_per_worker
        batch_sharding = None

    pipe = make_pipeline(cfg, batch_per_host=batch_per_host,
                         seq_len=args.seq_len, seed=args.seed,
                         task=args.task)
    g = None
    ex_cfg = opt.exchange_config
    if ex_cfg.overlap or stateful or args.tuned or zero1 \
            or ex_cfg.backend == "hierarchical":
        g = print_exchange_schedule(args, model, params, opt, pipe,
                                    sparse_embedding, n_dev)
    workers = n_dev if args.dist == "horovod" else 1
    if zero1:
        # optimizer state is the sharded Zero1State, laid out along the
        # plan's bucket partition (the GLOBAL view; shard_map splits it)
        if g is None:
            g = abstract_worker_grads(args, model, params, pipe,
                                      sparse_embedding)
        opt_state = opt.init_zero1_state(g, params, n_workers=workers)
    else:
        opt_state = opt.init(params)
    ex_state = None
    if stateful:
        if g is None:
            g = abstract_worker_grads(args, model, params, pipe,
                                      sparse_embedding)
        ex_state = opt.init_exchange_state(g, n_workers=workers)

    if args.dist == "horovod":
        if zero1:
            from repro.optim import zero1 as zero1_lib
            ostate_spec = zero1_lib.state_specs(opt.plan(g), opt_state,
                                                axes)
        else:
            ostate_spec = P()
        if stateful:
            # ExchangeState leaves are flat per-worker residuals stacked
            # on dim 0: shard them over the data axes so each worker
            # reads and writes only its own slice
            step = shard_map(step, mesh=mesh,
                             in_specs=(P(), ostate_spec, P(axes),
                                       pspec_batch),
                             out_specs=(P(), ostate_spec, P(axes), P()),
                             check_vma=False)
        else:
            step = shard_map(step, mesh=mesh,
                             in_specs=(P(), ostate_spec, pspec_batch),
                             out_specs=(P(), ostate_spec, P()),
                             check_vma=False)
        params = place_on_mesh(params, mesh, P())
        opt_state = place_on_mesh(opt_state, mesh, ostate_spec)
        if stateful:
            ex_state = place_on_mesh(ex_state, mesh, P(axes))
    recorder = None
    if args.metrics_jsonl:
        from repro.telemetry.metrics import MetricsLogger, StepRecorder
        recorder = StepRecorder(
            MetricsLogger(args.metrics_jsonl),
            tokens_per_step=batch_per_host * args.seq_len)
    trainer = Trainer(model, step, pipe, TrainerConfig(
        total_steps=args.steps, log_every=args.log_every,
        checkpoint_every=args.checkpoint_every,
        checkpoint_dir=args.checkpoint_dir, resume=args.resume,
        profile_dir=args.trace_dir, profile_steps=TRACE_STEPS),
        recorder=recorder, batch_sharding=batch_sharding)
    result = trainer.run(params, opt_state, exchange_state=ex_state)
    if recorder is not None:
        # persist the Trainer's windowed history (previously dropped
        # here) next to the per-step rows
        for h in result["history"]:
            recorder.logger.emit("history", **h)
        recorder.close()
        print(f"metrics written: {args.metrics_jsonl}")
    if args.trace_dir:
        # ``run`` consumed ``params``: read shapes from its result
        write_exchange_meta(args, opt, model, result["params"], pipe, g,
                            step, result, n_dev, sparse_embedding)
    return dict(result, config=cfg, n_workers=workers)


def main(argv=None) -> int:
    result = train(argv)
    final = result["history"][-1] if result["history"] else {}
    print(f"done: {final}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
