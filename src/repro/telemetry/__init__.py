"""Runtime observability for the exchange stack.

Modules (lazily imported — ``hooks`` is the only one the hot path
touches, and it is stdlib-only):

* ``hooks``   — process-global hook points (wire recorder, stage
  scopes) and the names of the step's device scopes and the
  Trainer's host spans.  Core modules import this directly.
* ``trace``   — ``measure_wire`` (abstract-eval wire counting against
  the plan's accounting) and ``load_profile`` (the ``jax.profiler``
  capture of the real loop, device ops resolved to their scopes).
* ``metrics`` — counters / gauges / histograms, a JSONL sink, and the
  Trainer's ``StepRecorder``.
* ``report``  — profile summarization: device time per layer and per
  exchange stage (exposed vs hidden), idle time by Trainer span, and
  the predicted-vs-measured diff against ``tuning.cost``.
"""
from __future__ import annotations

from repro.telemetry import hooks  # stdlib-only; safe to load eagerly

_LAZY = {
    "trace": "repro.telemetry.trace",
    "metrics": "repro.telemetry.metrics",
    "report": "repro.telemetry.report",
    # convenience re-exports
    "measure_wire": "repro.telemetry.trace",
    "load_profile": "repro.telemetry.trace",
    "MetricsLogger": "repro.telemetry.metrics",
    "StepRecorder": "repro.telemetry.metrics",
    "LatencyHistogram": "repro.telemetry.metrics",
    "summarize_profile": "repro.telemetry.report",
    "predicted_vs_measured": "repro.telemetry.report",
    "render_table": "repro.telemetry.report",
}

__all__ = ["hooks"] + sorted(_LAZY)


def __getattr__(name):
    target = _LAZY.get(name)
    if target is None:
        raise AttributeError(f"module 'repro.telemetry' has no attribute {name!r}")
    import importlib
    mod = importlib.import_module(target)
    value = mod if name in ("trace", "metrics", "report") else getattr(mod, name)
    globals()[name] = value
    return value
