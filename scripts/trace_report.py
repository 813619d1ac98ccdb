#!/usr/bin/env python3
"""Summarize a training profile: device time per layer and per exchange
stage, predicted vs measured.

    PYTHONPATH=src python scripts/trace_report.py TRACE_DIR [--json]

``TRACE_DIR`` is what ``train.py --trace-dir`` writes: the
``jax.profiler`` capture of the loop's last steps (``.xplane.pb`` and a
Perfetto trace under ``plugins/profile/``), the compiled step's HLO
text (``step.hlo.txt``, which names each device op's scope) and the
plan's ``exchange.json`` (stage names, the plan's wire accounting, the
tuner's predicted per-stage cost, the runtime-measured wire bytes).
It renders:

* per stage: predicted µs vs the stage's measured device µs per step,
  split into exposed (no other op runs) and hidden time;
* per stage: planned wire bytes vs the bytes the runtime wire counters
  billed, and their ratio (1.000 = the plan's accounting is exact, the
  ``--audit-exchange`` contract);
* per step: device ms by layer scope (``model/...``, ``optim/update``,
  ``exchange``), the rest, and idle time; idle share by Trainer span;
* the donated share: the bytes of the train state whose buffers the
  compiled step reuses for its outputs, over the state's bytes
  (``donation.json``; 100% when every leaf is donated and aliased);
* a machine-readable ``--json`` form for CI (the telemetry smoke
  asserts one row per schedule stage and ``wire_exact``).

Exit status: 0 when the directory parses and every stage has a row; 2
on a missing or malformed capture.  Wire inexactness does NOT fail the
exit code — CI asserts on the JSON.
"""
import argparse
import json
import sys

from repro.telemetry import report as report_lib


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("trace_dir", help="directory written by "
                                      "train.py --trace-dir")
    ap.add_argument("--json", action="store_true",
                    help="emit the summary as JSON instead of a table")
    args = ap.parse_args(argv)

    try:
        summary = report_lib.summarize_profile(args.trace_dir)
    except FileNotFoundError as e:
        print(f"malformed trace directory: {e}", file=sys.stderr)
        return 2
    names, rows = summary["stage_names"], summary["rows"]
    if not names:
        print("malformed trace directory: no stage names in exchange.json",
              file=sys.stderr)
        return 2
    if not summary["n_workers_traced"]:
        print("malformed trace directory: no device ops in the capture",
              file=sys.stderr)
        return 2

    if args.json:
        print(json.dumps(summary, indent=2))
        return 0

    print(f"trace: {args.trace_dir}")
    print(f"mode={summary['mode']} codec={summary['codec']} "
          f"backend={summary['backend']} "
          f"workers_traced={summary['n_workers_traced']} "
          f"steps_traced={summary['n_steps_traced']}")
    print(f"step: {summary['step_us'] / 1e3:.2f} ms on the device")
    print("per step, ms: " + "  ".join(
        f"{k}={v:.3f}" for k, v in summary["layers_ms"].items()))
    print("device idle, % of the window, by host span: " + "  ".join(
        f"{k}={v:.3f}" for k, v in summary["idle_share_by_span"].items()))
    don = summary["donation"]
    if don is not None and don["share"] is not None:
        print(f"donated: {don['share'] * 100:.2f}% of the train state "
              f"({don['aliased_bytes']} of {don['state_bytes']} bytes "
              f"per device reused by the step's outputs)")
    print()
    print(report_lib.render_table(rows))
    exposed = sum(r["exposed_us"] for r in rows)
    hidden = sum(r["hidden_us"] for r in rows)
    total = exposed + hidden
    if total:
        print(f"\nexchange: {total / 1e3:.3f} ms per step, "
              f"{hidden / total * 100:.0f}% hidden under other ops")
    print(f"wire exact vs plan: {report_lib.wire_exact(rows)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
