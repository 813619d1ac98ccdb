# One function per paper table/figure. Prints ``name,us_per_call,derived``
# CSV rows (benchmark harness entrypoint — deliverable d).
#
#   PYTHONPATH=src python -m benchmarks.run [--only fig3,...] [--fast]
#       [--json]
#
# ``--json`` additionally writes one machine-readable ``BENCH_<name>.json``
# per module (the perf-trajectory artifact CI uploads).
#
# Modules that emulate workers run them in a child pinned to the CPU
# (rows named ``cpuP8``), so a child never contends for an accelerator
# this process holds; this file itself opens no backend before the
# modules run (provenance is stamped after them).  A failed module or
# child makes the run exit non-zero.
#
# Modules (paper artifact -> module):
#   Fig 3 / Fig 5 space : accumulation_memory
#   Fig 5 time          : accumulation_time
#   Figs 4/6/7/8        : weak_scaling
#   Figs 9/10/11        : strong_scaling
#   Fig 12              : quality_invariance
#   §Roofline           : roofline  (aggregates experiments/dryrun)
#   §Overlap            : overlap   (exposed vs hidden communication time)
#   §Autotuner          : tune      (analytic rank vs measured rank)
#   §Serving            : serving_load (Poisson TTFT/TPOT + hot swap)
import argparse
import json
import os
import subprocess
import sys
import time
import traceback


def provenance(timestamp=None):
    """Stamp a BENCH json with where its numbers came from: git rev,
    caller-supplied timestamp (wall clocks on CI runners drift; the
    caller knows better), jax version, and the device kind — so two
    artifacts are only ever compared when these match."""
    prov = {"timestamp": timestamp}
    try:
        prov["git_rev"] = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True,
            text=True, timeout=10, check=True).stdout.strip()
    except Exception:
        prov["git_rev"] = None
    try:
        import jax
        prov["jax_version"] = jax.__version__
        prov["device_kind"] = jax.devices()[0].device_kind
        prov["n_devices"] = jax.device_count()
    except Exception:
        prov["jax_version"] = prov["device_kind"] = None
    return prov


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated module substrings to run")
    ap.add_argument("--fast", action="store_true",
                    help="skip the (slow) training-based Fig 12 benchmark")
    ap.add_argument("--json", action="store_true",
                    help="write BENCH_<module>.json next to the CSV "
                         "output (machine-readable results)")
    ap.add_argument("--json-dir", default=".",
                    help="directory for the BENCH_<module>.json files")
    ap.add_argument("--timestamp", default=None,
                    help="caller-supplied run timestamp recorded in the "
                         "BENCH json provenance block")
    args = ap.parse_args()

    from benchmarks import (accumulation_memory, accumulation_time,
                            overlap, weak_scaling, strong_scaling,
                            roofline)
    modules = [("accumulation_memory", accumulation_memory),
               ("accumulation_time", accumulation_time),
               ("overlap", overlap),
               ("weak_scaling", weak_scaling),
               ("strong_scaling", strong_scaling),
               ("roofline", roofline)]
    if not args.fast:
        from benchmarks import quality_invariance, serving_load, tune
        modules.insert(5, ("quality_invariance", quality_invariance))
        modules.append(("tune", tune))
        modules.append(("serving_load", serving_load))
    if args.only:
        keys = args.only.split(",")
        modules = [(n, m) for n, m in modules
                   if any(k in n for k in keys)]

    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")

    results, failed = [], []
    for name, mod in modules:
        rows = []

        def emit(row_name: str, us: float, derived: str,
                 _rows=rows) -> None:
            print(f"{row_name},{us:.1f},{derived}")
            sys.stdout.flush()
            _rows.append({"name": row_name, "us_per_call": us,
                          "derived": derived})

        t0 = time.perf_counter()
        try:
            mod.run(emit)
        except Exception:
            print(f"benchmark {name} failed:", file=sys.stderr)
            traceback.print_exc()
            failed.append(name)
            continue
        wall_s = time.perf_counter() - t0
        emit(f"_module_{name}_wall_s", wall_s * 1e6, "total")
        results.append((name, wall_s, rows))
    if args.json:
        # stamped after the modules: provenance opens the backend
        prov = provenance(args.timestamp)
        for name, wall_s, rows in results:
            path = os.path.join(args.json_dir, f"BENCH_{name}.json")
            with open(path, "w") as f:
                json.dump({"module": name, "wall_s": wall_s,
                           "provenance": prov, "rows": rows},
                          f, indent=2)
    if failed:
        print(f"failed: {','.join(failed)}", file=sys.stderr)
        return 1
    return 0


if __name__ == '__main__':
    sys.exit(main())
