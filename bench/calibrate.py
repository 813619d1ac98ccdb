#!/usr/bin/env python3
"""Readings that the comparison's limits are set from (not part of a run).

    python3 bench/calibrate.py --workload tbig-train-1chip \
        --seeds 101-112 --control-seeds 101-103 --out chiprun_out/cal.json

In one process, with the step compiled once: for every seed, the
program's first steps against the config's plain reference (the lower
readings); for each control seed, the controls and the planted faults
against the reference (the upper readings).  Two controls: the
reference in float8 put in the program's place, and the program's own
lower-precision path, its int8 gradient codec, switched on.  The faults
are those the reference module plants (its ``FAULTS``; those in
``MULTI_CHIP_FAULTS`` only on several chips): for the decoder, half of
every batch left out; the embedding's gradient densified by overwriting
repeated rows instead of adding them; and on several chips the exchange
left out (each chip trains on its own rows).  A step that returns its
state unchanged reads 1 on ``delta_gap`` by definition and needs no run.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def seed_list(text: str):
    out = []
    for part in text.split(","):
        if "-" in part:
            a, b = part.split("-")
            out += range(int(a), int(b) + 1)
        elif part:
            out.append(int(part))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1-12")
    ap.add_argument("--control-seeds", default="1-3")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    from bench import run as runner
    cell, cfg, _, _ = runner.load_cell(args.workload)
    jax = runner.setup_jax()
    devices = runner.pick_devices(jax, cell["chips"], allow_cpu=False)
    from bench import check, generator, harness, program

    tr = generator.load(cell["traffic"])
    warmup = tr["launcher"]["warmup"]
    chips = cell["chips"]
    feed = generator.PoolFeed([])
    prog = program.build(cfg, tr, devices, feed)
    int8 = program.build(cfg, dict(tr, launcher=dict(tr["launcher"],
                                                     codec="int8")),
                         devices, feed)
    quiet = lambda msg: None                                  # noqa: E731
    ref_mod = harness.reference(cfg)
    faults = tuple(f for f in ref_mod.FAULTS if f != "none" and (
        chips > 1 or f not in ref_mod.MULTI_CHIP_FAULTS))
    kinds = ("program", "control_fp8", "control_int8") + faults
    out = {"workload": args.workload, "warmup": warmup, "device":
           f"{devices[0].platform} {devices[0].device_kind} x{len(devices)}",
           **{k: {} for k in kinds}}

    def ref_of(seed, pool, precision="f32", fault="none"):
        return harness.reference_readings(cfg, seed, pool, warmup, chips,
                                          devices, precision, fault)

    for seed in seed_list(args.seeds):
        t0 = time.perf_counter()
        feed.pool = generator.make_pool(tr, cfg, seed, chips)
        readings, state = harness.first_steps(prog, cfg, seed, feed, quiet)
        del state
        ref = ref_of(seed, feed.pool)
        g = check.gaps(readings, ref)
        out["program"][seed] = dict(g, worst=check.worst_leaves(readings, ref),
                                    loss=readings["loss"],
                                    ref_loss=ref["loss"])
        print(f"seed {seed}: {g} ({time.perf_counter() - t0:.1f} s)",
              file=sys.stderr, flush=True)
        if seed not in seed_list(args.control_seeds):
            continue
        del readings
        got = {"control_int8": harness.first_steps(int8, cfg, seed, feed,
                                                   quiet)[0],
               "control_fp8": ref_of(seed, feed.pool, "fp8"),
               **{f: ref_of(seed, feed.pool, fault=f) for f in faults}}
        for name, r in got.items():
            fg = check.gaps(r, ref)
            out[name][seed] = dict(fg, worst=check.worst_leaves(r, ref))
            print(f"  {name}: {fg}", file=sys.stderr, flush=True)
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(out, indent=1))
    for kind in kinds:
        rows = out[kind].values()
        if rows:
            summary = {n: (min(r[n] for r in rows), max(r[n] for r in rows))
                       for n in check.NAMES}
            print(f"{kind}: (min, max) {summary}", flush=True)
    return 0


if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    sys.exit(main())
