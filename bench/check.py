"""The comparison that decides ``correct``.

Five numbers, each against the plain reference's run of the same steps
from the same weights on the same rows:

* ``loss_gap``: the relative gap between the program's first-step loss
  and the reference's (the first shard's rows, whose loss the program
  reports).  The later steps' losses are read too (``loss_gaps``) but
  not limited: after two large Adam steps they swing from seed to seed
  by more than the control moves them (see PERF.md);
* ``grad_gap``: the first step's gradient as the optimizer got it, read
  back from AdamW's first moment, per leaf and per chip: the largest gap
  between the program's norm and the reference's, over the larger of
  that leaf's reference norm and the median leaf's;
* ``grad_err``: the first step's gradient against the reference's,
  element by element: the median over leaves of the norm of their
  difference over the reference's norm.  Norms alone cannot see
  rounding that is unbiased, such as float8 matmuls; this can;
* ``grad_err_max``: the same for the worst leaf, so that a fault
  confined to one leaf, such as the densify of the embedding's
  gradient, shows although it leaves the median where it was;
* ``delta_gap``: the same as ``grad_gap`` for the parameters' change
  over the first steps, leaving out leaves whose reference gradient is
  under a thousandth of the median leaf's (they move by round-off
  alone).

The limits live in ``bench/limits/<workload>.json``, each set between
the program's readings on a dozen seeds and those of the control and the
planted faults (see PERF.md).
"""
from __future__ import annotations

import json
import math
from pathlib import Path
from typing import Dict

import numpy as np

DATA_DIR = Path(__file__).resolve().parent
NAMES = ("loss_gap", "grad_gap", "grad_err", "grad_err_max", "delta_gap")
ROUND_OFF_LEAF = 1e-3       # of the median leaf's reference gradient norm


def load_limits(workload: str, data_dir: Path = DATA_DIR
                ) -> Dict[str, float]:
    return json.loads((Path(data_dir) / "limits" / f"{workload}.json")
                      .read_text())


def _worst_leaf_gap(got: np.ndarray, want: np.ndarray,
                    keep: np.ndarray) -> float:
    """got (devices, leaves) against want (leaves,)."""
    floor = np.maximum(want, np.median(want))
    gap = np.abs(np.atleast_2d(got) - want[None]) / floor[None]
    gap = np.where(keep[None], gap, 0.0)
    return float(np.max(gap)) if np.all(np.isfinite(got[..., keep])) \
        else math.inf


def _leaf_errors(got, want) -> np.ndarray:
    """Per leaf, the norm of the elementwise difference over the
    reference's norm (inf where the program's leaf is not finite)."""
    err = [np.linalg.norm(np.asarray(a, np.float64) - b)
           / max(np.linalg.norm(b), 1e-30)
           for a, b in zip(got, (np.asarray(x, np.float64) for x in want))]
    return np.where(np.isfinite(err), err, math.inf)


def gaps(prog: Dict, ref: Dict) -> Dict:
    """Program readings against the reference's: {name: gap}, with the
    later steps' loss gaps and the leaf that sets ``grad_err_max``."""
    lp = np.asarray(prog["loss"], np.float64)
    lr = np.asarray(ref["loss"], np.float64)
    per_step = np.where(np.isfinite(lp), np.abs(lp - lr) / np.abs(lr),
                        math.inf)
    g_ref = np.asarray(ref["grad_norm"], np.float64)
    moved = g_ref >= ROUND_OFF_LEAF * np.median(g_ref)
    every = np.ones_like(moved)
    err = _leaf_errors(prog["grad"], ref["grad"])
    return {
        "loss_gap": float(per_step[0]),
        "grad_err": float(np.median(err)),
        "grad_err_max": float(np.max(err)),
        "grad_err_leaf": ref["leaves"][int(np.argmax(err))],
        "loss_gaps": [float(x) for x in per_step],
        "grad_gap": _worst_leaf_gap(np.asarray(prog["grad_norm"]), g_ref,
                                    every),
        "delta_gap": _worst_leaf_gap(np.asarray(prog["delta_norm"]),
                                     np.asarray(ref["delta_norm"],
                                                np.float64), moved),
    }


def judge(got: Dict[str, float], limits: Dict[str, float]) -> Dict:
    """{name: {"value", "limit"}} for every limited number, and whether
    all of them hold."""
    checks = {n: {"value": got[n], "limit": limits[n]}
              for n in NAMES if n in limits}
    ok = bool(checks) and all(c["value"] <= c["limit"]
                              for c in checks.values())
    return {"correct": ok, "checks": checks}


def worst_leaves(prog: Dict, ref: Dict) -> Dict[str, str]:
    """Which leaf sets each per-leaf gap (for the calibration's notes)."""
    out = {}
    for key in ("grad_norm", "delta_norm"):
        want = np.asarray(ref[key], np.float64)
        floor = np.maximum(want, np.median(want))
        gap = np.max(np.abs(np.atleast_2d(prog[key]) - want[None])
                     / floor[None], axis=0)
        out[key] = ref["leaves"][int(np.argmax(gap))]
    return out
