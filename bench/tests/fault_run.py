"""Run a test cell on the CPU, with the program's timed path broken.

    python bench/tests/fault_run.py <fault> --workload tiny-1 --seed 5 \
        --seconds 1 --trace 0

Faults: ``none``; ``state_unchanged`` (the step returns the parameters
it was given); ``half_batch`` (the loss, hence the gradient, takes half
of each chip's rows, the mean over the rest); ``no_exchange`` (every
chip keeps its own gradient: the exchange runs without the mesh axis);
``dup_overwrite`` (the densify of the embedding's ``IndexedSlices``
gradient overwrites repeated rows instead of adding them).
Everything but the look for a chip runs as in a benchmark run.
"""
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
DATA = ROOT / "bench" / "tests" / "data"


def plant(fault: str) -> None:
    if fault == "state_unchanged":
        from repro.training import train_step
        train_step.apply_updates = lambda params, updates: params
    elif fault == "half_batch":
        from repro.models import model
        loss = model.Model.loss

        def half_loss(self, params, batch, taps=None, **kw):
            n = batch["tokens"].shape[0] // 2
            batch = {k: v[:n] for k, v in batch.items()}
            return loss(self, params, batch,
                        taps=None if taps is None else taps[:n], **kw)

        model.Model.loss = half_loss
    elif fault == "no_exchange":
        from repro.core.dist_opt import DistributedOptimizer

        def local_exchange(self, grads, state=None):
            return self.plan(grads).execute(grads, None,
                                            average=self.average,
                                            state=state)

        DistributedOptimizer.exchange = local_exchange
    elif fault == "dup_overwrite":
        import jax.numpy as jnp
        from repro.core.indexed_slices import IndexedSlices

        def overwrite(self):
            zeros = jnp.zeros(self.dense_shape, dtype=self.values.dtype)
            return zeros.at[self.indices].set(self.values)

        IndexedSlices.to_dense = overwrite
    elif fault != "none":
        raise SystemExit(f"unknown fault {fault!r}")


if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    plant(sys.argv[1])
    from bench import run
    spec = json.loads((DATA / "spec.json").read_text())
    sys.exit(run.main(sys.argv[2:], allow_cpu=True, spec=spec,
                      data_dir=DATA))
