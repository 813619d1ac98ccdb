"""Quickstart: the paper's fix in 60 lines.

Builds the paper's transformer (reduced to CPU size), trains it twice —
once with TensorFlow-style assumed-sparse accumulation (gather), once
with the paper's sparse_as_dense fix (reduce) — and shows that the
models are identical while the accumulated-tensor sizes are wildly
different.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core import DistributedOptimizer, ExchangeConfig
from repro.data import make_pipeline
from repro.models import build_model
from repro.optim import adamw
from repro.training import Trainer, TrainerConfig, make_train_step
from repro.training.gradients import grad_contributions


def main():
    cfg = get_config("transformer-big").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    pipe = make_pipeline(cfg, batch_per_host=8, seq_len=32, task="copy")

    print(f"model: {cfg.name} ({cfg.n_layers}L d={cfg.d_model} "
          f"vocab={cfg.vocab}, tied embeddings)")

    # --- what does each strategy accumulate? -----------------------------
    batch = {k: jnp.asarray(v) for k, v in pipe.batch_at(0).items()}
    grads, _, _ = grad_contributions(model, params, batch,
                                     sparse_embedding=True)
    for name, cfg in [
            ("sparse gather (TF default)", ExchangeConfig()),
            ("dense reduce (the paper's fix)",
             ExchangeConfig(sparse_as_dense=True)),
            ("dense reduce + int8 wire",
             ExchangeConfig(sparse_as_dense=True, codec="int8"))]:
        opt = DistributedOptimizer(adamw(3e-3), exchange=cfg)
        stats = opt.exchange_stats(grads, n_workers=64)
        print(f"  {name:33s}: accumulated buffer at 64 workers = "
              f"{stats.accumulated_bytes/1e6:8.1f} MB, "
              f"wire = {stats.wire_bytes/1e6:8.1f} MB/worker  "
              f"[{stats.strategy}]")

    # --- and does the choice change the model? NO. -----------------------
    results = {}
    for name, sad in [("gather", False), ("reduce", True)]:
        opt = DistributedOptimizer(
            adamw(3e-3), exchange=ExchangeConfig(sparse_as_dense=sad))
        step = make_train_step(model, opt, sparse_embedding=True)
        tr = Trainer(model, step, pipe,
                     TrainerConfig(total_steps=30, log_every=10))
        print(f"training with {name} accumulation:")
        # ``run`` consumes the state it is given: train from a copy
        start = jax.tree_util.tree_map(jnp.copy, params)
        res = tr.run(start, opt.init(start),
                     log=lambda s: print("   ", s))
        results[name] = res["params"]
    diff = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
        jax.tree_util.tree_leaves(results["gather"]),
        jax.tree_util.tree_leaves(results["reduce"])))
    print(f"max param difference between strategies: {diff:.2e}  "
          f"(identical models, {'OK' if diff < 1e-4 else 'MISMATCH'})")


if __name__ == "__main__":
    main()
