"""Autotuner validation: analytic rank vs measured rank.

The tuner's claim is that the α–β cost model (over the plan's audited
per-stage/per-hop accounting) ranks ExchangeConfigs well enough that
measuring only the analytic top-k finds the true winner.  This module
checks that claim on the acceptance substrate — the REDUCED
transformer-big on 8 emulated CPU workers:

  1. enumerate a trimmed config space (identity/int8 x jax/hierarchical
     x three overlap modes, 128 MiB fusion threshold);
  2. rank it analytically under the ``cpu`` BandwidthProfile (the
     shared-memory emulation numbers, where codec compute and launch
     latency dominate the "wire");
  3. measure EVERY candidate end-to-end (loss + backward + exchange,
     round-robin interleaved) — the ground truth the analytic rank is
     judged against;
  4. report the Spearman rank correlation and, for the candidate the
     real ``search(trials>0)`` flow would select (measured-best of the
     analytic top-5), its rank in the full measured order.  The
     acceptance contract wants that selection in the measured top-2.
"""
from __future__ import annotations

import textwrap

from benchmarks.common import run_cpu_workers

_TUNE_CODE = textwrap.dedent("""
    import jax, jax.numpy as jnp
    from repro.configs import get_config
    from repro.core.fusion import DEFAULT_FUSION_THRESHOLD
    from repro.data import make_pipeline
    from repro.models import build_model
    from repro.training.gradients import grad_contributions
    from repro.tuning import enumerate_space, rank_candidates
    from repro.tuning import measure_candidates

    cfg = get_config('transformer-big').reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    pipe = make_pipeline(cfg, batch_per_host=2, seq_len=32)
    batch = {k: jnp.asarray(v) for k, v in pipe.batch_at(0).items()}
    grads, _, _ = grad_contributions(model, params, batch,
                                     sparse_embedding=True)

    cands = enumerate_space(
        grads, 8, codecs=('identity', 'int8'),
        overlaps=(False, 'staged', 'backward'),
        thresholds=(DEFAULT_FUSION_THRESHOLD,),
        include_sparse_gather=False, include_reduce_scatter=False)
    rank_candidates(cands, grads, 'cpu')
    measure_candidates(cands, grads, 8, trials=5,
                       model=model, params=params, batch=batch)

    ok = [c for c in cands if c.error is None]
    by_meas = sorted(ok, key=lambda c: c.measured_us)
    meas_rank = {id(c): r for r, c in enumerate(by_meas, 1)}
    n = len(ok)
    if n > 1:
        d2 = sum((r - meas_rank[id(c)]) ** 2
                 for r, c in enumerate(ok, 1))
        rho = 1 - 6 * d2 / (n * (n * n - 1))
    else:
        rho = 1.0
    # what search(trials>0, top_k=5) would select: measured-best of
    # the analytic top-5
    head = ok[:5]
    sel = min(head, key=lambda c: c.measured_us)
    print('N_OK', n, 'N_ALL', len(cands))
    print('SPEARMAN', round(rho, 4))
    print('SELECTED', sel.label, 'RANK', meas_rank[id(sel)])
    print('ANALYTIC_BEST', ok[0].label, 'RANK', meas_rank[id(ok[0])])
    for r, c in enumerate(ok, 1):
        print('CAND', r, meas_rank[id(c)],
              round(c.predicted_us, 1), round(c.measured_us, 1),
              c.label)
""")


def run(emit):
    out = run_cpu_workers(_TUNE_CODE, timeout=1800)

    def grab(tag):
        return out.split(tag)[1].split()[0]

    n_ok, n_all = int(grab("N_OK")), float(grab("N_ALL"))
    rho = float(grab("SPEARMAN"))
    sel_rank = int(out.split("SELECTED")[1].split("RANK")[1]
                   .split()[0])
    ana_rank = int(out.split("ANALYTIC_BEST")[1].split("RANK")[1]
                   .split()[0])
    emit("tune_space_measured_cpuP8", n_ok, f"of_{int(n_all)}_candidates")
    emit("tune_rank_spearman_cpuP8", 0.0,
         f"rho={rho:.3f}_analytic_vs_measured")
    emit("tune_analytic_best_measured_rank_cpuP8", float(ana_rank),
         "rank_of_analytic_no1_in_measured_order")
    emit("tune_selected_measured_rank_cpuP8", float(sel_rank),
         f"measured_best_of_analytic_top5_in_top2={sel_rank <= 2}")
    for line in out.splitlines():
        if not line.startswith("CAND "):
            continue
        f = line.split()
        ana, meas, pred_us, meas_us = f[1], f[2], f[3], f[4]
        label = f[5].replace(",", ";")
        emit(f"tune_cand_{label}_cpuP8", float(meas_us),
             f"predicted_us={pred_us}_analytic_rank={ana}"
             f"_measured_rank={meas}")
