"""Trace summarization: where a step's device time goes, per layer and
per exchange stage, from the profiler's capture of the real loop.

Operates on a ``telemetry.trace.Profile`` (``load_profile`` of the
directory ``train.py --trace-dir`` writes): device ops per chip, each
with its scope path, the Trainer's host spans, and the plan's
``exchange.json`` (stage names, wire accounting, the tuner's per-stage
prediction, runtime-measured wire bytes), and ``donation.json`` (the
share of the train state the compiled step reuses).  The CLI is
``scripts/trace_report.py``.

Definitions (per chip, per step, then averaged over chips):

* a layer's **device time** is the union of the intervals of the ops
  whose innermost scope it is; JAX keeps a scope through autodiff, so
  a model layer counts forward plus backward;
* a stage's **exposed** time is the part of its ops' union that no op
  outside the stage covers (an op that encloses others, such as a
  ``while`` around its body, covers nothing itself); **hidden** is the
  rest.  Hidden/total is
  the overlap the staged/wait-free schedules exist to maximise;
* **idle** time is the capture window (first op to last op) that no
  device op covers, split by the Trainer span the host was inside;
  **loop** time is what an enclosing op (a ``while`` around its body)
  covers beyond its body's ops.
"""
from __future__ import annotations

import json
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from repro.telemetry import hooks
from repro.telemetry.trace import Profile, load_profile, read_donation

Intervals = List[Tuple[int, int]]
LAYERS = hooks.LAYER_SCOPES + (hooks.EXCHANGE,)


def _merge(iv: Iterable[Tuple[int, int]]) -> Intervals:
    out: List[List[int]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _length(iv: Intervals) -> int:
    return sum(e - s for s, e in iv)


def _intersect(a: Intervals, b: Intervals) -> Intervals:
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _gaps(busy: Intervals, lo: int, hi: int) -> Intervals:
    """The parts of [lo, hi) that ``busy`` (merged) leaves uncovered."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def _device_ops(prof: Profile, device: int):
    return [o for o in prof.ops if o.device == device]


def _leaves(ops) -> list:
    """Ops that enclose no other op (a ``while`` keeps its body's ops)."""
    ops = sorted(ops, key=lambda o: (o.start, -o.end))
    parent = set()
    stack: list = []
    for k, o in enumerate(ops):
        while stack and ops[stack[-1]].end <= o.start:
            stack.pop()
        if stack and o.end <= ops[stack[-1]].end:
            parent.add(stack[-1])
        stack.append(k)
    return [o for k, o in enumerate(ops) if k not in parent]


def _layer(path: str) -> Optional[str]:
    """The innermost layer scope (or the exchange) an op lies under."""
    hits = [(path.rfind(s), s) for s in LAYERS if hooks.in_scope(path, s)]
    return max(hits)[1] if hits else None


def stage_timings(prof: Profile, names: Sequence[str]
                  ) -> Dict[str, Dict[str, float]]:
    """Per exchange stage: measured, exposed and hidden device µs per
    step, averaged over chips."""
    out = {n: {"collective_us": 0.0, "exposed_us": 0.0, "hidden_us": 0.0}
           for n in names}
    devs = prof.devices()
    for d in devs:
        ops = _device_ops(prof, d)
        leaves = _leaves(ops)
        for n in names:
            mine = _merge((o.start, o.end) for o in ops
                          if hooks.in_scope(o.path, n))
            rest = _merge((o.start, o.end) for o in leaves
                          if not hooks.in_scope(o.path, n))
            meas = _length(mine)
            exp = meas - _length(_intersect(mine, rest))
            row = out[n]
            row["collective_us"] += meas
            row["exposed_us"] += exp
            row["hidden_us"] += meas - exp
    scale = 1e3 * max(len(devs), 1) * prof.steps()
    for row in out.values():
        for k in row:
            row[k] /= scale
    return out


def layer_split(prof: Profile) -> Dict[str, float]:
    """Per step, device ms of each layer scope (``hooks.LAYER_SCOPES``)
    and of the exchange over the ops that enclose no other op, each
    counted under its innermost scope (``model/layers`` keeps the layer
    scan's own ops); ``other``, such ops under none of them; ``loop``,
    the own time of enclosing ops (a ``while`` beyond its body's ops:
    loop control); and ``idle``, averaged over chips."""
    out = dict.fromkeys(LAYERS, 0.0)
    devs = prof.devices()
    other = loop = idle = 0.0
    for d in devs:
        ops = _device_ops(prof, d)
        leaves = _leaves(ops)
        layer = {o: _layer(o.path) for o in leaves}
        for s in LAYERS:
            out[s] += _length(_merge((o.start, o.end) for o in leaves
                                     if layer[o] == s))
        busy = _merge((o.start, o.end) for o in ops)
        work = _merge((o.start, o.end) for o in leaves)
        scoped = _merge((o.start, o.end) for o in leaves if layer[o])
        other += _length(work) - _length(scoped)
        loop += _length(busy) - _length(work)
        idle += _length(_gaps(busy, busy[0][0], busy[-1][1]))
    out.update(other=other, loop=loop, idle=idle)
    scale = 1e6 * max(len(devs), 1) * prof.steps()
    return {k: v / scale for k, v in out.items()}


def idle_by_span(prof: Profile) -> Dict[str, float]:
    """Share (%) of the capture window in which the device is idle while
    the host is inside each Trainer span, averaged over chips."""
    out = {n: 0.0 for n in hooks.TRAINER_SPANS}
    devs = prof.devices()
    for d in devs:
        busy = _merge((o.start, o.end) for o in _device_ops(prof, d))
        lo, hi = busy[0][0], busy[-1][1]
        idle = _gaps(busy, lo, hi)
        for n in out:
            host = _merge((s.start, s.end) for s in prof.spans
                          if s.name == n)
            out[n] += 100.0 * _length(_intersect(idle, host)) / (hi - lo)
    return {n: v / max(len(devs), 1) for n, v in out.items()}


def predicted_vs_measured(prof: Profile) -> List[Dict[str, Any]]:
    """One row per schedule stage: the tuner's predicted µs, the
    measured device µs per step with its exposed/hidden split, planned
    vs runtime-measured wire bytes, and the drift ratios that close the
    loop ``dryrun --audit-exchange`` only checks statically."""
    meta = prof.meta
    names = list(meta.get("stage_names", ()))
    timings = stage_timings(prof, names) if prof.ops else {}
    return stage_rows(meta, timings)


def stage_rows(meta: Dict[str, Any],
               timings: Optional[Dict[str, Dict[str, float]]] = None
               ) -> List[Dict[str, Any]]:
    """Rows of ``predicted_vs_measured`` from an ``exchange.json``
    block; without ``timings`` (the dry-run's wire leg) the time
    columns are None."""
    predicted = meta.get("predicted_us", {})
    planned_wire = meta.get("planned_wire_bytes", {})
    measured_wire = meta.get("measured_wire_bytes", {})
    rows = []
    for n in meta.get("stage_names", ()):
        t = (timings or {}).get(n, {})
        meas_us = t.get("collective_us")
        pred_us = predicted.get(n)
        pw = planned_wire.get(n)
        mw = measured_wire.get(n)
        rows.append({
            "stage": n,
            "predicted_us": pred_us,
            "measured_us": meas_us,
            "exposed_us": t.get("exposed_us"),
            "hidden_us": t.get("hidden_us"),
            "us_ratio": (meas_us / pred_us
                         if pred_us and meas_us is not None else None),
            "planned_wire_bytes": pw,
            "measured_wire_bytes": mw,
            "wire_ratio": (mw / pw if pw and mw is not None else
                           (1.0 if not pw and not mw else None)),
        })
    return rows


def wire_exact(rows: Sequence[Dict[str, Any]]) -> bool:
    """True when every stage's runtime wire counter equals the plan's
    accounting (the acceptance contract for exact backends/codecs)."""
    return all(r["wire_ratio"] is not None
               and abs(r["wire_ratio"] - 1.0) < 1e-9 for r in rows)


def summarize_profile(trace_dir: str) -> Dict[str, Any]:
    """The report of one ``--trace-dir``: per-stage rows, the per-step
    split by layer, idle by Trainer span, and the plan's labels."""
    prof = load_profile(trace_dir)
    meta = prof.meta
    rows = predicted_vs_measured(prof)
    devs = prof.devices()
    win = [max(o.end for o in _device_ops(prof, d))
           - min(o.start for o in _device_ops(prof, d)) for d in devs]
    return {
        "n_stages": len(rows),
        "stage_names": list(meta.get("stage_names", ())),
        "mode": meta.get("mode"), "codec": meta.get("codec"),
        "backend": meta.get("backend"),
        "n_workers_traced": len(devs),
        "n_steps_traced": prof.steps(),
        "step_us": (sum(win) / len(win) / 1e3 / prof.steps()
                    if win else None),
        "wire_exact": wire_exact(rows),
        "layers_ms": layer_split(prof) if devs else {},
        "idle_share_by_span": idle_by_span(prof) if devs else {},
        "donation": read_donation(trace_dir),
        "rows": rows,
    }


def render_table(rows: Sequence[Dict[str, Any]]) -> str:
    """Fixed-width predicted-vs-measured table."""
    hdr = (f"{'stage':<52} {'pred_us':>9} {'meas_us':>9} {'exp_us':>8} "
           f"{'hid_us':>8} {'wire_plan':>10} {'wire_meas':>10} {'ratio':>6}")
    lines = [hdr, "-" * len(hdr)]

    def fmt(v, spec):
        width = int(spec.rstrip("df").split(".")[0])
        return format(v, spec) if v is not None else "-".rjust(width)

    for r in rows:
        mw = r["measured_wire_bytes"]
        mw = int(mw) if mw is not None else None
        lines.append(
            f"{r['stage']:<52} {fmt(r['predicted_us'], '9.1f')} "
            f"{fmt(r['measured_us'], '9.1f')} "
            f"{fmt(r['exposed_us'], '8.1f')} {fmt(r['hidden_us'], '8.1f')} "
            f"{fmt(r['planned_wire_bytes'], '10d')} "
            f"{fmt(mw, '10d')} "
            f"{fmt(r['wire_ratio'], '6.3f')}")
    return "\n".join(lines)


def summarize_metrics_jsonl(path: str) -> Dict[str, Any]:
    """Roll up a metrics JSONL file (``kind=step`` rows + the trailing
    ``summary``) into the numbers a report renders."""
    steps: List[Dict[str, Any]] = []
    summary: Optional[Dict[str, Any]] = None
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            rec = json.loads(line)
            if rec.get("kind") == "step":
                steps.append(rec)
            elif rec.get("kind") == "summary":
                summary = rec
    out: Dict[str, Any] = {"n_steps": len(steps)}
    if steps:
        last = steps[-1]
        out["final_loss"] = last.get("loss")
        for k in ("step_ms", "data_ms", "compute_ms", "tok_s"):
            vals = [s[k] for s in steps if k in s]
            if vals:
                out[f"mean_{k}"] = sum(vals) / len(vals)
    if summary:
        out["counters"] = summary.get("counters", {})
        out["histograms"] = summary.get("histograms", {})
    return out
