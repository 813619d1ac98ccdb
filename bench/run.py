#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload tbig-train-1chip --seed 7 \
        --seconds 10 --trace 0

Everything about the cell is data: ``BENCHMARK.json`` names its config
file (``bench/configs/``), its traffic file (``bench/traffic/``) and its
metrics (one reader each under ``bench/metrics/``); the comparison's
limits are in ``bench/limits/<workload>.json``; the config file names
its plain reference (``"reference"``: a module of ``bench/references/``),
which gives the weights, the readings compared and the counts.

A run: batches and weights from ``--seed``; the program's training step
built as the launcher builds it; the first steps through the program's
``Trainer.run``, read for the comparison; a short warm-up that also
sizes the window; then the window: whole steps of one ``Trainer.run``
call for about ``--seconds``, no compilation inside (counted; a run with
any fails).  ``--trace 1`` adds a traced window of a few steps and
reports the per-layer metrics instead of the end-to-end ones.  After the
windows the program's state is freed and the config's plain reference
repeats the first steps; the comparison decides ``correct``.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each compared number beside its
limit).  The last lines of standard error repeat the checks.  Without a
TPU, or with fewer chips than the cell asks for, it exits 2 and prints
no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                              # noqa: E402
import gc                                                    # noqa: E402
import importlib                                             # noqa: E402
import json                                                  # noqa: E402
import math                                                  # noqa: E402
import os                                                    # noqa: E402
import shutil                                                # noqa: E402
import sys                                                   # noqa: E402
from pathlib import Path                                     # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# the compile cache's path is part of its key: one fixed place in the
# checkout, so only the first run of a cell there compiles
CACHE_DIR = ROOT / ".bench_cache" / "jax"
TRACE_DIR = ROOT / ".bench_cache" / "trace"
CAL_STEPS = 5          # warm-up steps that also measure the step time
TRACE_STEPS = 8        # steps in the traced window


class NoChip(Exception):
    """The cell cannot run on this machine."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_cell(workload: str, spec=None):
    """(cell, config, end-to-end metrics, per-layer metrics) of a
    workload of ``spec`` (default: BENCHMARK.json)."""
    if spec is None:
        spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cfg = json.loads((ROOT / conf["file"]).read_text())

    def applies(m):
        return workload in m.get("workloads", [workload])

    return (cell, cfg, [m for m in spec["end_to_end"] if applies(m)],
            [m for m in spec["per_layer"] if applies(m)])


def setup_jax():
    """JAX with its compile cache in the checkout (none when pinned to
    the CPU, where the benchmark only runs its own tests)."""
    import jax
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    # no eviction: the cache is the checkout's own, and eviction's
    # bookkeeping files fail every write once one of them is missing
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def pick_devices(jax, chips: int, allow_cpu: bool):
    devs = jax.devices()
    if devs[0].platform != "tpu" and not allow_cpu:
        raise NoChip(f"needs a TPU; JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX sees {len(devs)}")
    return devs[:chips]


def read_metrics(metrics, rec) -> dict:
    out = {}
    for m in metrics:
        value = importlib.import_module(f"bench.metrics.{m['name']}").read(rec)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None, allow_cpu: bool = False, spec=None,
         data_dir: Path = ROOT / "bench") -> int:
    """``allow_cpu``, ``spec`` and ``data_dir`` (traffic and limits) are
    for the benchmark's own tests, which run small cells on the CPU."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)

    cell, cfg, e2e, per_layer = load_cell(args.workload, spec)
    chips = cell["chips"]
    jax = setup_jax()
    try:
        devices = pick_devices(jax, chips, allow_cpu)
    except NoChip as e:
        log(f"bench: {e}")
        return 2
    kind = devices[0].device_kind
    peaks_doc = json.loads((ROOT / "bench" / "peaks.json").read_text())
    peaks = peaks_doc["devices"].get(kind)
    if peaks is None and not allow_cpu:
        log(f"bench: no peaks for device kind {kind!r} in bench/peaks.json")
        return 2

    from bench import check, flops, generator, harness, program
    from bench.trace import breakdown, device_seconds, load as load_trace

    reference = harness.reference(cfg)
    tr = generator.load(cell["traffic"], data_dir)
    if tr["chips"] != chips:
        raise SystemExit(f"traffic {cell['traffic']} is for {tr['chips']} "
                         f"chips, the cell for {chips}")
    limits = check.load_limits(args.workload, data_dir)
    counter = harness.CompileCounter()
    pool = generator.make_pool(tr, cfg, args.seed, chips)
    feed = generator.PoolFeed(pool, annotate=jax.profiler.TraceAnnotation)
    prog = program.build(cfg, tr, devices, feed)
    quiet = lambda msg: None                                  # noqa: E731

    t_build = time.perf_counter() - T_START
    readings, state = harness.first_steps(prog, cfg, args.seed, feed, log)
    t_first = time.perf_counter() - T_START
    log(f"bench: first losses {readings['loss']}")
    offset = harness.CHECK_STEPS
    cal_s, state, _ = harness.timed(prog, state, feed, CAL_STEPS, offset,
                                    counter, quiet)
    offset += CAL_STEPS
    steps = max(CAL_STEPS, math.ceil(args.seconds * CAL_STEPS / cal_s))
    setup_s = time.perf_counter() - T_START

    window_s, state, compiles = harness.timed(prog, state, feed, steps,
                                              offset, counter, quiet)
    offset += steps
    if compiles:
        log(f"bench: {compiles} compilations inside the window")
        return 1
    hist = state["history"][-1]
    final_loss = hist.get("loss", float("nan"))
    rows, seq = tr["batch_per_chip"] * chips, tr["seq_len"]
    per_chip = tr["batch_per_chip"] * seq
    rec = harness.Record(
        setup_s=setup_s, window_s=window_s, window_steps=steps,
        window_tokens=steps * rows * seq, input_ms=hist["data_ms"],
        peak_bytes=0, chips=chips,
        flops_per_step=reference.train_step_flops(cfg, rows, seq),
        densify_bytes=flops.densify_bytes(
            cfg, per_chip, flops.unique_rows(
                [b["tokens"] for b in pool], chips),
            jax.numpy.dtype(cfg["dtype"]).itemsize),
        peaks=peaks)
    log(f"bench: window {steps} steps in {window_s:.3f} s "
        f"(warm-up {CAL_STEPS} in {cal_s:.3f} s); set-up {setup_s:.3f} s "
        f"(built at {t_build:.3f} s, first steps read at {t_first:.3f} s); "
        f"final loss {final_loss}")

    if args.trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        with jax.profiler.trace(str(TRACE_DIR)):
            with jax.profiler.TraceAnnotation("bench/traced_window"):
                _, state, compiles = harness.timed(
                    prog, state, feed, TRACE_STEPS, offset, counter, quiet)
        if compiles:
            log(f"bench: {compiles} compilations inside the traced window")
            return 1
        hlo = program.step_hlo(prog, state["params"], state["opt_state"],
                               pool[0])
        rec.trace = load_trace(str(TRACE_DIR), hlo)
        rec.trace_steps = TRACE_STEPS
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    rec.peak_bytes = harness.peak_bytes(devices)
    del state, prog
    gc.collect()

    t_ref = time.perf_counter()
    ref = harness.reference_readings(cfg, args.seed, pool, tr["launcher"]
                                     ["warmup"], chips, devices)
    got = check.gaps(readings, ref)
    log(f"bench: reference and comparison {time.perf_counter() - t_ref:.3f} s"
        f", run {time.perf_counter() - T_START:.3f} s; "
        f"later losses' gaps {got['loss_gaps']}")
    verdict = check.judge(got, limits)
    log(f"bench: reference losses {ref['loss']}")
    log(f"bench: worst leaves {check.worst_leaves(readings, ref)}")

    result = {
        "correct": verdict["correct"] and math.isfinite(final_loss),
        "attempted": steps,
        "failed": 0 if math.isfinite(final_loss) else steps,
        "metrics": read_metrics(per_layer if args.trace else e2e, rec),
        "device": {"platform": devices[0].platform, "kind": kind,
                   "count": len(devices), "memory_peak_bytes": rec.peak_bytes},
    }
    if args.trace:
        busy_s, win_s = device_seconds(rec.trace)
        result["device"].update(busy_s=busy_s, window_s=win_s)
        result["breakdown"] = breakdown(rec.trace)
    result["checks"] = verdict["checks"]
    for name, c in verdict["checks"].items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    sys.exit(main())
