"""CI smoke: int8 + error-feedback wire must track the fp32 wire.

Trains the reduced transformer-big three times on 8 emulated workers
(shard_map, Horovod-faithful) from the same init/data — fp32 wire,
int8 wire, int8+ef wire — and asserts the error-feedback run lands
within tolerance of fp32 (and no further than plain int8).  This is
the convergence contract the stateful codec API exists to deliver,
runnable in a couple of minutes on a CI core.

  python scripts/ef_smoke.py [--steps 40] [--workers 8]
"""
import argparse
import os
import sys

ap = argparse.ArgumentParser()
ap.add_argument("--steps", type=int, default=60)
ap.add_argument("--workers", type=int, default=8)
ap.add_argument("--tolerance", type=float, default=0.15,
                help="max |loss_ef - loss_fp32| in nats")
args = ap.parse_args()

# workers are emulated on host devices: stay off any accelerator
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           f" --xla_force_host_platform_device_count="
                           f"{args.workers}")

import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402
import numpy as np                                          # noqa: E402
from jax import shard_map                                   # noqa: E402
from jax.sharding import Mesh, PartitionSpec as P           # noqa: E402

from repro.configs import get_config                        # noqa: E402
from repro.core import DistributedOptimizer, ExchangeConfig  # noqa: E402
from repro.data import make_pipeline                        # noqa: E402
from repro.models import build_model                        # noqa: E402
from repro.optim import adamw                               # noqa: E402
from repro.training import (Trainer, TrainerConfig,         # noqa: E402
                            make_train_step)
from repro.training.gradients import abstract_grad_contributions  # noqa: E402


def final_loss(codec: str, error_feedback: bool) -> float:
    cfg = get_config("transformer-big").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = DistributedOptimizer(
        adamw(1e-2),
        exchange=ExchangeConfig(sparse_as_dense=True, codec=codec,
                                error_feedback=error_feedback,
                                fusion_threshold=1 << 20),
        axis_name=("data",))
    step = make_train_step(model, opt, sparse_embedding=True)
    n_dev = len(jax.devices())
    mesh = Mesh(np.array(jax.devices()), ("data",))
    if step.stateful_exchange:
        step = shard_map(step, mesh=mesh,
                         in_specs=(P(), P(), P("data"), P("data")),
                         out_specs=(P(), P(), P("data"), P()),
                         check_vma=False)
    else:
        step = shard_map(step, mesh=mesh,
                         in_specs=(P(), P(), P("data")),
                         out_specs=(P(), P(), P()),
                         check_vma=False)
    pipe = make_pipeline(cfg, batch_per_host=2 * n_dev, seq_len=16,
                         task="copy")
    ex_state = None
    if opt.stateful:
        b0 = {k: jnp.asarray(v)[:2] for k, v in pipe.batch_at(0).items()}
        g = abstract_grad_contributions(model, params, b0,
                                        sparse_embedding=True)
        ex_state = opt.init_exchange_state(g, n_workers=n_dev)
    trainer = Trainer(model, step, pipe, TrainerConfig(
        total_steps=args.steps, log_every=max(1, args.steps // 15)))
    res = trainer.run(params, opt.init(params), log=lambda s: None,
                      exchange_state=ex_state)
    # single-step losses are noisy this early in training: compare the
    # mean over the last third of the run
    tail = [h["loss"] for h in res["history"]][-5:]
    return float(np.mean(tail))


f32 = final_loss("identity", False)
q8 = final_loss("int8", False)
ef = final_loss("int8", True)
gap, ef_gap = q8 - f32, ef - f32
print(f"fp32 wire      final loss: {f32:.4f}")
print(f"int8 wire      final loss: {q8:.4f}  (gap {gap:+.4f})")
print(f"int8+ef wire   final loss: {ef:.4f}  (gap {ef_gap:+.4f})")

# the relative check ("ef no further from fp32 than raw int8") needs
# noise-scale slack: tail-of-5 losses this early jitter by a few
# hundredths, and a lucky raw-int8 run must not red the CI leg
NOISE = 0.05
ok = abs(ef_gap) <= args.tolerance and abs(ef_gap) <= abs(gap) + NOISE
print(f"{'PASS' if ok else 'FAIL'}: |ef-fp32|={abs(ef_gap):.4f} "
      f"tolerance={args.tolerance} |int8-fp32|={abs(gap):.4f} "
      f"noise_slack={NOISE}")
sys.exit(0 if ok else 1)
