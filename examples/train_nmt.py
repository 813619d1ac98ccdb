"""End-to-end NMT training driver (deliverable b).

Trains a ~100M-parameter variant of the paper's transformer on the
synthetic translation corpus with the paper's dense-reduce accumulation,
the Noam schedule, checkpointing, and (optionally) multi-worker
emulation.  A few hundred steps on CPU:

    PYTHONPATH=src python examples/train_nmt.py --steps 300

Multi-worker (the paper's `mpirun -np 8` equivalent):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
    PYTHONPATH=src python examples/train_nmt.py --steps 300 --horovod

Quick sanity run: --steps 20 --small
"""
import argparse

import jax
import numpy as np

from repro.configs import get_config
from repro.core import DistributedOptimizer, ExchangeConfig
from repro.data import make_pipeline
from repro.models import build_model
from repro.optim import adamw, noam_schedule
from repro.serving import ServeEngine
from repro.training import Trainer, TrainerConfig, make_train_step


def nmt_100m():
    """~100M-param transformer: the paper's architecture, one size down
    (between 'base' 65M and 'big' 210M)."""
    return get_config("transformer-big").with_(
        name="transformer-100m", d_model=768, n_heads=12, n_kv_heads=12,
        d_ff=3072, head_dim=64, dtype="float32")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch-per-worker", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--small", action="store_true",
                    help="reduced config (CI / smoke)")
    ap.add_argument("--horovod", action="store_true",
                    help="shard over all visible devices")
    ap.add_argument("--sparse-gather", action="store_true",
                    help="use the pathological strategy instead of the fix")
    ap.add_argument("--checkpoint-dir", default=None)
    args = ap.parse_args()

    cfg = get_config("transformer-big").reduced() if args.small else \
        nmt_100m()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(params))
    print(f"{cfg.name}: {n_params/1e6:.1f}M params, "
          f"strategy={'gather' if args.sparse_gather else 'dense_reduce'}")

    n_dev = len(jax.devices())
    axis = ("data",) if args.horovod and n_dev > 1 else None
    opt = DistributedOptimizer(
        adamw(noam_schedule(cfg.d_model, warmup_steps=max(args.steps // 4,
                                                          50))),
        exchange=ExchangeConfig(
            sparse_as_dense=not args.sparse_gather,
            fusion_threshold=128 * 1024 * 1024),  # HOROVOD_FUSION_THRESHOLD
        axis_name=axis)
    step = make_train_step(model, opt, sparse_embedding=True)

    batch_per_host = args.batch_per_worker
    if axis is not None:
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        mesh = Mesh(np.array(jax.devices()), ("data",))
        step = shard_map(step, mesh=mesh, in_specs=(P(), P(), P("data")),
                         out_specs=(P(), P(), P()), check_vma=False)
        batch_per_host *= n_dev
        print(f"horovod mode: {n_dev} workers")

    pipe = make_pipeline(cfg, batch_per_host=batch_per_host,
                         seq_len=args.seq_len, task="translation")
    trainer = Trainer(model, step, pipe, TrainerConfig(
        total_steps=args.steps, log_every=max(args.steps // 20, 1),
        checkpoint_every=args.steps // 3 if args.checkpoint_dir else 0,
        checkpoint_dir=args.checkpoint_dir))
    res = trainer.run(params, opt.init(params))

    # quick greedy decode demo on the trained model
    eng = ServeEngine(model, res["params"], cache_len=args.seq_len + 8)
    prompts = pipe.batch_at(10_000)["tokens"][:2, :args.seq_len // 2]
    out = eng.generate(prompts, max_new=8)
    print("sample generations (token ids):")
    for row in out:
        print("  ", row.tolist())


if __name__ == "__main__":
    main()
