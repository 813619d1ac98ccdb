"""Reproduce the paper's core experiment at laptop scale: per-worker-count
comparison of the accumulation/exchange strategies (buffer size, planned
wire bytes, measured step time, model equality).

All static numbers come from the ExchangePlan — the same schedule the
runtime collectives execute.  Beyond the paper's two strategies, any
codec/backend combination from the registries can be compared with
``--codec`` / ``--backend`` / ``--reduce-scatter`` (adds a third row):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
    PYTHONPATH=src python examples/scaling_comparison.py \\
        [--reduce-scatter] [--codec bf16|int8] [--backend jax|ringsim]
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from repro.configs import get_config
from repro.core import DistributedOptimizer, ExchangeConfig
from repro.data import make_pipeline
from repro.models import build_model
from repro.optim import adamw
from repro.training import make_train_step
from repro.training.gradients import grad_contributions


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--reduce-scatter", action="store_true",
                    help="add a dense_reduce row exchanged via "
                         "reduce-scatter + allgather")
    ap.add_argument("--wire-dtype", default=None,
                    choices=[None, "bf16", "bfloat16"],
                    help="deprecated spelling of --codec")
    ap.add_argument("--codec", default=None,
                    help="WireCodec for the extra row (bf16, f16, int8)")
    ap.add_argument("--backend", default=None,
                    help="CollectiveBackend for the extra row (jax, "
                         "ringsim)")
    args = ap.parse_args(argv)
    if args.wire_dtype and not args.codec:
        args.codec = args.wire_dtype

    n_dev = len(jax.devices())
    cfg = get_config("transformer-big").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    pipe = make_pipeline(cfg, batch_per_host=2 * n_dev, seq_len=32)
    batch = {k: jnp.asarray(v) for k, v in pipe.batch_at(0).items()}
    mesh = Mesh(np.array(jax.devices()), ("data",))

    grads, _, _ = grad_contributions(
        model, params, {k: v[:2] for k, v in batch.items()},
        sparse_embedding=True)

    strategies = [("sparse_gather", ExchangeConfig(sparse_as_dense=False)),
                  ("dense_reduce", ExchangeConfig(sparse_as_dense=True))]
    if args.reduce_scatter or args.codec or args.backend:
        extra = ExchangeConfig(sparse_as_dense=True,
                               reduce_scatter=args.reduce_scatter,
                               codec=args.codec or "identity",
                               backend=args.backend or "jax")
        name = "dense" + ("_rs" if args.reduce_scatter else "") + \
            (f"_{extra.codec}" if extra.codec != "identity" else "") + \
            (f"_{extra.backend}" if extra.backend != "jax" else "")
        strategies.append((name, extra))

    print(f"{n_dev} emulated workers — {cfg.name}  "
          f"(run with XLA_FLAGS=--xla_force_host_platform_device_count=N "
          f"to change)")
    print(f"{'strategy':15s} {'buffer@N':>12s} {'wire/worker':>12s} "
          f"{'n_coll':>7s} {'ms/step':>9s} {'final loss':>10s}")

    final_params = {}
    for name, cfg in strategies:
        opt = DistributedOptimizer(adamw(3e-3), exchange=cfg,
                                   axis_name=("data",))
        stats = opt.exchange_stats(grads, n_workers=n_dev)
        step = shard_map(
            make_train_step(model, opt, sparse_embedding=True),
            mesh=mesh, in_specs=(P(), P(), P("data")),
            out_specs=(P(), P(), P()), check_vma=False)
        step = jax.jit(step)
        p, s = params, opt.init(params)
        p, s, m = step(p, s, batch)               # compile
        jax.block_until_ready(p)
        t0 = time.perf_counter()
        for i in range(1, 6):
            b = {k: jnp.asarray(v) for k, v in pipe.batch_at(i).items()}
            p, s, m = step(p, s, b)
        jax.block_until_ready(p)
        dt = (time.perf_counter() - t0) / 5
        final_params[name] = p
        print(f"{name:15s} {stats.accumulated_bytes/1e6:10.1f}MB "
              f"{stats.wire_bytes/1e6:10.1f}MB {stats.n_collectives:7d} "
              f"{dt*1e3:9.1f} {float(m['loss']):10.4f}")

    diff = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
        jax.tree_util.tree_leaves(final_params["sparse_gather"]),
        jax.tree_util.tree_leaves(final_params["dense_reduce"])))
    print(f"\nmax param difference: {diff:.2e} — same model, "
          f"{'(paper Fig. 12 invariance holds)' if diff < 1e-4 else 'BUG'}")
    extras = [n for n in final_params
              if n not in ("sparse_gather", "dense_reduce")]
    for name in extras:
        d = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
            jax.tree_util.tree_leaves(final_params[name]),
            jax.tree_util.tree_leaves(final_params["dense_reduce"])))
        tol = 5e-2 if ("bf" in name or "f16" in name
                       or "int8" in name) else 1e-4
        print(f"{name} vs dense_reduce: {d:.2e} "
              f"({'within wire tolerance' if d < tol else 'BUG'})")


if __name__ == "__main__":
    main()
