"""The one generator of training batches, driven by a traffic file.

A traffic file (``bench/traffic/<name>.json``) states the job: rows per
chip, sequence length, how many distinct batches the pool holds, the
token distribution, and the launcher options the step is built with.
From ``--seed`` the generator draws a pool of host batches, the same
sizes for every seed:

* ``tokens``: ids drawn Zipf(a), minus one, clipped to the vocabulary
  (the skewed id distribution that makes the embedding gradient sparse);
* ``labels``: the next token of the same stream;
* ``loss_mask``: ones (every position is a target);
* ``frontend``: where the config has ``frontend_frames``, the
  encoder-state stub, float32 normal, ``frames`` x ``d_model`` per row
  (drawn after each batch's ids, from the same stream).

``PoolFeed`` hands them to the program's ``Trainer`` through its
``batch_at(step)`` interface, cycling the pool from an offset that the
harness moves between calls, so the first steps all see distinct rows.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, List

import numpy as np

DATA_DIR = Path(__file__).resolve().parent


def load(name: str, data_dir: Path = DATA_DIR) -> Dict:
    return json.loads((Path(data_dir) / "traffic" / f"{name}.json").read_text())


def make_pool(traffic: Dict, cfg: Dict, seed: int, chips: int
              ) -> List[Dict[str, np.ndarray]]:
    rows = traffic["batch_per_chip"] * chips
    seq = traffic["seq_len"]
    frames, d = cfg.get("frontend_frames"), cfg["d_model"]
    vocab = cfg["vocab"]
    dist = traffic["tokens"]
    if dist["kind"] != "zipf":
        raise ValueError(f"unknown token distribution {dist['kind']!r}")
    rng = np.random.default_rng([int(seed), 0x7EA7])
    pool = []
    for _ in range(traffic["pool_batches"]):
        raw = rng.zipf(dist["a"], size=(rows, seq + 1))
        toks = np.minimum(raw - 1, vocab - 1).astype(np.int32)
        batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:],
                 "loss_mask": np.ones((rows, seq), np.float32)}
        if frames is not None:
            batch["frontend"] = rng.standard_normal((rows, frames, d),
                                                    dtype=np.float32)
        pool.append(batch)
    return pool


class PoolFeed:
    """``batch_at(step)`` over a fixed pool, from a movable offset."""

    def __init__(self, pool: List[Dict[str, np.ndarray]], annotate=None):
        self.pool = pool
        self.offset = 0
        self._annotate = annotate        # context factory for host spans

    def batch_at(self, step: int) -> Dict[str, np.ndarray]:
        if self._annotate is None:
            return self.pool[(self.offset + step) % len(self.pool)]
        with self._annotate("bench/fetch"):
            return self.pool[(self.offset + step) % len(self.pool)]
