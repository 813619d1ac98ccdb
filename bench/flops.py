"""Bytes that the densify of the embedding's gradient needs, counted from
the config's shapes and the step's ids, whatever the program does to
compute it.  The model's operations and parameters are counted by the
config's reference module (``bench/references/<reference>.py``:
``train_step_flops``, ``param_count``), one place per architecture.

``densify_bytes`` is the least traffic of turning one step's embedding
rows (IndexedSlices) into the dense vocab x d_model gradient.  Read
every id and row, and then:

* tied head: its matmul has already written the dense gradient, so
  read and write each distinct row of the table once;
* untied: no matmul has written the table, so write all of it once.
"""
from __future__ import annotations

from typing import Dict

import numpy as np


def densify_bytes(cfg: Dict, tokens: int, unique_rows: float,
                  itemsize: int) -> float:
    d = cfg["d_model"]
    rows = tokens * 4 + tokens * d * itemsize
    if cfg["tied_embeddings"]:
        return float(rows + 2 * unique_rows * d * itemsize)
    return float(rows + cfg["vocab"] * d * itemsize)


def unique_rows(token_batches, chips: int) -> float:
    """Mean number of distinct ids per chip and step over the batches."""
    counts = [len(np.unique(shard))
              for t in token_batches for shard in np.split(t, chips)]
    return float(sum(counts)) / len(counts)
