"""Rank the ExchangeConfig space, optionally refine with measured
trials, and cache the winner as a versioned JSON artifact.

Flow (``dryrun --tune`` / ``train.py --tuned``):

  1. ``space.enumerate_space`` → candidates for (tree, P);
  2. analytic rank: ``cost.predict_comm_us`` per candidate (same
     per-stage/per-hop accounting the collective audit verifies);
     candidates that tie on predicted time (overlap moves no extra
     bytes) are split by a deterministic overlap preference —
     backward > staged > fused — since hiding the same bytes earlier
     never loses;
  3. optional refinement: time the analytic top-k end-to-end on the
     real devices (short interleaved trials of the lowered exchange)
     and re-rank those by measurement;
  4. the winner is written to ``<cache_dir>/<key>.json``, keyed by the
     STRUCTURAL tree fingerprint (sparse row counts elided — one tuned
     config covers every batch size of the model) + total workers +
     profile name.  ``train.py --tuned`` resolves the same key at
     startup and constructs the config with zero search.

Artifacts are versioned: a loader finding a different
``ARTIFACT_VERSION`` rejects the file (``TuningArtifactError``) so a
stale cache can never silently configure a newer exchange stack.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import time
from typing import Any, Dict, List, Optional, Sequence, Union

from repro.core import exchange as exchange_lib
from repro.core.exchange import ExchangeConfig
from repro.tuning import cost as cost_lib
from repro.tuning import space as space_lib
from repro.tuning.profile import BandwidthProfile, get_profile

ARTIFACT_VERSION = 1
DEFAULT_CACHE_DIR = os.path.join("experiments", "tuning")

#: deterministic tie-break among equal-predicted candidates: hiding the
#: same wire behind compute earlier in the step never loses
_OVERLAP_PREFERENCE = {False: 2, "staged": 1, "backward": 0}

#: ExchangeConfig fields serialised into artifacts (post-normalisation;
#: the deprecated spellings are always None/False after __post_init__)
_CONFIG_FIELDS = ("algorithm", "sparse_as_dense", "fusion_threshold",
                  "reduce_scatter", "codec", "backend",
                  "hierarchy_levels", "use_kernel", "overlap")


class TuningArtifactError(RuntimeError):
    """Missing, stale-version, or malformed tuning artifact."""


def config_to_dict(cfg: ExchangeConfig) -> Dict[str, Any]:
    return {f: getattr(cfg, f) for f in _CONFIG_FIELDS}


def config_from_dict(d: Dict[str, Any]) -> ExchangeConfig:
    unknown = set(d) - set(_CONFIG_FIELDS)
    if unknown:
        raise TuningArtifactError(
            f"artifact config has unknown fields {sorted(unknown)}")
    return ExchangeConfig(**d)


def artifact_key(grads, n_workers: int,
                 profile: Union[str, BandwidthProfile]) -> str:
    """Stable cache key: structural tree fingerprint (shapes/dtypes,
    sparse row counts elided) + worker count + profile name."""
    fp = exchange_lib.fingerprint(grads, exact=False)
    name = get_profile(profile).name
    payload = f"tune1|{fp}|P{int(n_workers)}|{name}"
    return hashlib.sha256(payload.encode()).hexdigest()[:16]


def artifact_path(cache_dir: str, key: str) -> str:
    return os.path.join(cache_dir, f"{key}.json")


# ---------------------------------------------------------------------------
# Analytic ranking
# ---------------------------------------------------------------------------

def rank_candidates(candidates: List[space_lib.Candidate], grads,
                    profile: Union[str, BandwidthProfile]
                    ) -> List[space_lib.Candidate]:
    """Score every candidate with the cost model and sort ascending
    (cheapest predicted first, overlap preference as the tie-break)."""
    prof = get_profile(profile)
    for c in candidates:
        plan = exchange_lib.compile_plan(grads, c.config)
        c.predicted_us = cost_lib.predict_comm_us(plan, c.levels, prof)
    candidates.sort(key=lambda c: (
        c.predicted_us, _OVERLAP_PREFERENCE.get(c.config.overlap, 3),
        c.label))
    return candidates


# ---------------------------------------------------------------------------
# Measured refinement (needs >= n_workers devices)
# ---------------------------------------------------------------------------

def measure_candidates(candidates: Sequence[space_lib.Candidate],
                       grads, n_workers: int, *, trials: int = 3,
                       model=None, params=None, batch=None
                       ) -> List[space_lib.Candidate]:
    """Time each candidate's exchange on the live devices.

    With ``model``/``params``/``batch`` the measurement is end-to-end
    (loss + backward + exchange, the wait-free path for
    ``overlap="backward"``) so overlap modes genuinely differ; without
    them it times the exchange alone on the provided gradients (overlap
    "backward" then measures its block-aligned staged schedule).
    Candidates are compiled first, then timed round-robin so system
    drift cannot bias one candidate; per-candidate medians land in
    ``measured_us`` (``inf`` + ``error`` on compile failure).
    """
    import jax
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map

    from repro.core import DistributedOptimizer
    from repro.optim import adamw

    devs = np.array(jax.devices()[:n_workers])
    fns: Dict[int, Any] = {}
    for idx, cand in enumerate(candidates):
        cfg = cand.config
        try:
            if cfg.is_hierarchical:
                mesh = Mesh(devs.reshape(2, n_workers // 2),
                            ("pod", "data"))
                axis = ("pod", "data")
            else:
                mesh = Mesh(devs, ("data",))
                axis = ("data",)
            opt = DistributedOptimizer(adamw(1e-3), exchange=cfg,
                                       axis_name=axis)
            stateful = opt.stateful
            probe = grads if grads is not None else None
            state0 = (opt.init_exchange_state(probe, n_workers=n_workers)
                      if stateful else None)

            if model is not None:
                if cfg.overlap_backward:
                    from repro.training.gradients import \
                        wait_free_grad_exchange

                    def fn(p_, b_, s=None, _o=opt):
                        dense, ns, _, _ = wait_free_grad_exchange(
                            model, _o, p_, b_, state=s,
                            sparse_embedding=True)
                        return (dense, ns) if s is not None else dense
                else:
                    from repro.training.gradients import grad_contributions

                    def fn(p_, b_, s=None, _o=opt):
                        g = grad_contributions(model, p_, b_,
                                               sparse_embedding=True)[0]
                        return (_o.exchange(g, state=s)
                                if s is not None else _o.exchange(g))
                # batch replicated (matches the audit harness: every
                # worker computes the same contribution; the exchange
                # cost is what differs between candidates)
                in_specs = ((P(), P(), P(axis)) if stateful
                            else (P(), P()))
                out_specs = ((P(), P(axis)) if stateful else P())
                args = ((params, batch, state0) if stateful
                        else (params, batch))
            else:
                def fn(g_, s=None, _o=opt):
                    return (_o.exchange(g_, state=s)
                            if s is not None else _o.exchange(g_))
                in_specs = (P(), P(axis)) if stateful else (P(),)
                out_specs = (P(), P(axis)) if stateful else P()
                args = (grads, state0) if stateful else (grads,)

            jitted = jax.jit(shard_map(fn, mesh=mesh, in_specs=in_specs,
                                       out_specs=out_specs,
                                       check_vma=False))
            jax.block_until_ready(jitted(*args))    # compile
            jax.block_until_ready(jitted(*args))    # warm
            fns[idx] = (jitted, args)
        except Exception as e:                       # prune at runtime
            cand.measured_us = float("inf")
            cand.error = f"{type(e).__name__}: {e}"[:200]

    samples: Dict[int, List[float]] = {i: [] for i in fns}
    for _ in range(max(trials, 1)):
        for idx, (jitted, args) in fns.items():
            t0 = time.perf_counter()
            jax.block_until_ready(jitted(*args))
            samples[idx].append(time.perf_counter() - t0)
    for idx, ts in samples.items():
        candidates[idx].measured_us = sorted(ts)[len(ts) // 2] * 1e6
    return list(candidates)


# ---------------------------------------------------------------------------
# End-to-end search
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TuningResult:
    key: str
    profile: str
    n_workers: int
    tree_fingerprint: str
    candidates: List[space_lib.Candidate]    # analytic rank order
    winner: space_lib.Candidate
    trials: int

    def table(self) -> str:
        """Ranked markdown table (dryrun --tune output)."""
        lines = ["| rank | config | predicted_us | measured_us |",
                 "|---|---|---|---|"]
        for r, c in enumerate(self.candidates, 1):
            meas = (f"{c.measured_us:.1f}" if c.measured_us is not None
                    else "-")
            star = " *" if c is self.winner else ""
            lines.append(f"| {r} | {c.label}{star} | "
                         f"{c.predicted_us:.1f} | {meas} |")
        return "\n".join(lines)


def search(grads, n_workers: int, *,
           profile: Union[str, BandwidthProfile] = "ethernet",
           trials: int = 0, top_k: int = 5,
           model=None, params=None, batch=None,
           **space_kw) -> TuningResult:
    """Enumerate, rank analytically, optionally refine the top-k with
    measured trials (requires live devices), and pick the winner."""
    prof = get_profile(profile)
    cands = space_lib.enumerate_space(grads, n_workers, **space_kw)
    if not cands:
        raise ValueError("empty tuning space")
    rank_candidates(cands, grads, prof)
    if trials > 0:
        head = cands[:min(top_k, len(cands))]
        measure_candidates(head, grads, n_workers, trials=trials,
                           model=model, params=params, batch=batch)
        winner = min(head, key=lambda c: c.measured_us)
    else:
        winner = cands[0]
    return TuningResult(
        key=artifact_key(grads, n_workers, prof),
        profile=prof.name, n_workers=n_workers,
        tree_fingerprint=exchange_lib.fingerprint(grads, exact=False),
        candidates=cands, winner=winner, trials=trials)


# ---------------------------------------------------------------------------
# Artifact I/O
# ---------------------------------------------------------------------------

def save_artifact(result: TuningResult,
                  cache_dir: str = DEFAULT_CACHE_DIR) -> str:
    os.makedirs(cache_dir, exist_ok=True)
    path = artifact_path(cache_dir, result.key)
    doc = {
        "version": ARTIFACT_VERSION,
        "key": result.key,
        "tree_fingerprint": result.tree_fingerprint,
        "n_workers": result.n_workers,
        "profile": result.profile,
        "trials": result.trials,
        "winner": config_to_dict(result.winner.config),
        "winner_label": result.winner.label,
        "ranking": [
            {"config": config_to_dict(c.config), "label": c.label,
             "predicted_us": c.predicted_us,
             "measured_us": c.measured_us, "error": c.error}
            for c in result.candidates],
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=2)
    return path


def load_artifact(path: str) -> Dict[str, Any]:
    """Load + validate one artifact file.  Raises TuningArtifactError
    on missing files, version mismatch, or a missing winner."""
    if not os.path.exists(path):
        raise TuningArtifactError(f"no tuning artifact at {path}")
    with open(path) as f:
        doc = json.load(f)
    v = doc.get("version")
    if v != ARTIFACT_VERSION:
        raise TuningArtifactError(
            f"stale tuning artifact {path}: version {v!r} != "
            f"{ARTIFACT_VERSION} (re-run dryrun --tune)")
    if "winner" not in doc:
        raise TuningArtifactError(f"malformed tuning artifact {path}: "
                                  f"no winner entry")
    return doc


def load_tuned_config(grads, n_workers: int,
                      profile: Union[str, BandwidthProfile],
                      cache_dir: str = DEFAULT_CACHE_DIR
                      ) -> Optional[Dict[str, Any]]:
    """Resolve the cached artifact for this (tree, P, profile) key.
    Returns the artifact dict (with ``config`` parsed into
    ``ExchangeConfig`` under ``"exchange_config"``), or None when no
    valid artifact exists — callers fall back to an analytic search."""
    key = artifact_key(grads, n_workers, profile)
    path = artifact_path(cache_dir, key)
    try:
        doc = load_artifact(path)
    except TuningArtifactError:
        return None
    doc["exchange_config"] = config_from_dict(doc["winner"])
    doc["path"] = path
    return doc
