"""Operations and bytes that the work needs, counted from the config's
shapes, whatever the program does to compute it.

``train_step_flops`` is the model FLOPs of one training step: forward
plus backward matmuls, nothing recomputed, attention and the tied head
included.  The backward pass of a matmul costs twice its forward (the
gradients of both operands), except where one operand is an input that
takes no gradient: the projections of the encoder states (keys and
values of cross-attention) only need the weights' gradient.  Causal
self-attention counts the lower triangle the model needs
(``causal_pairs``), not the full square a kernel may compute.

``densify_bytes`` is the least traffic of adding one step's embedding
rows (IndexedSlices) into the dense vocab x d_model gradient that the
tied head's matmul has already written: read every id and row, and read
and write each distinct row of the table once.
"""
from __future__ import annotations

from typing import Dict

import numpy as np


def causal_pairs(seq: int) -> int:
    return seq * (seq + 1) // 2


def forward_flops(cfg: Dict, rows: int, seq: int,
                  causal_full: bool = False) -> Dict[str, float]:
    """Forward matmul FLOPs of ``rows`` x ``seq`` tokens, by part."""
    d, f, v, n = cfg["d_model"], cfg["d_ff"], cfg["vocab"], cfg["n_layers"]
    hd = d // cfg["n_heads"]
    h, kv = cfg["n_heads"] * hd, cfg["n_kv_heads"] * hd
    fr = cfg["frontend_frames"]
    tok = rows * seq
    pairs = rows * (seq * seq if causal_full else causal_pairs(seq))
    return {
        # q, k, v, o of self-attention; q and o of cross-attention;
        # the SwiGLU gate, up and down projections
        "token_matmuls": 2.0 * tok * n * (d * h + 2 * d * kv + h * d
                                          + d * h + h * d + 3 * d * f),
        # keys and values of the encoder states
        "frame_matmuls": 2.0 * rows * fr * n * 2 * d * h,
        # scores and weighted values
        "self_attention": 2.0 * 2 * pairs * h * n,
        "cross_attention": 2.0 * 2 * tok * fr * h * n,
        "head": 2.0 * tok * d * v,
    }


def train_step_flops(cfg: Dict, rows: int, seq: int,
                     causal_full: bool = False) -> float:
    """Forward + backward model FLOPs of one step over rows x seq."""
    fwd = forward_flops(cfg, rows, seq, causal_full)
    total = 0.0
    for part, x in fwd.items():
        total += x * (2.0 if part == "frame_matmuls" else 3.0)
    return total


def param_count(cfg: Dict) -> int:
    d, f, v, n = cfg["d_model"], cfg["d_ff"], cfg["vocab"], cfg["n_layers"]
    hd = d // cfg["n_heads"]
    h, kv = cfg["n_heads"] * hd, cfg["n_kv_heads"] * hd
    per_layer = (d * h + 2 * d * kv + h * d) + 4 * d * h + 3 * d * f + 3 * d
    return v * d + d + n * per_layer


def densify_bytes(cfg: Dict, tokens: int, unique_rows: float,
                  itemsize: int) -> float:
    d = cfg["d_model"]
    return float(tokens * 4 + tokens * d * itemsize
                 + 2 * unique_rows * d * itemsize)


def unique_rows(token_batches, chips: int) -> float:
    """Mean number of distinct ids per chip and step over the batches."""
    counts = [len(np.unique(shard))
              for t in token_batches for shard in np.split(t, chips)]
    return float(sum(counts)) / len(counts)
