"""The phases of a run that ``run.py`` and ``calibrate.py`` share.

Everything model-specific comes from the config's reference module,
``bench/references/<cfg["reference"]>.py`` (``reference(cfg)``): the
weights, the reference's readings and faults, the optimizer's first
moment decay and the counts.

``first_steps`` builds nothing: it takes the built program, gives it the
benchmark's weights from the seed, drives the first steps through the
window's own call (``Trainer.run``) and feed on distinct rows, and reads
what the comparison needs from the program's state.  ``timed`` is the
measured window: whole steps of one ``Trainer.run`` call that ends in
``block_until_ready`` on the returned params, with every compilation in
it counted.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib
import time
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from bench import program

CHECK_STEPS = 3              # steps the reference follows


@dataclasses.dataclass
class Record:
    """What one run measured: the metric readers take it from here."""
    setup_s: float
    window_s: float
    window_steps: int
    window_tokens: int
    input_ms: float
    peak_bytes: int
    chips: int
    flops_per_step: float        # all chips
    densify_bytes: float         # one chip's densify per step
    peaks: Optional[Dict]        # the device's row of peaks.json
    trace: Optional[object] = None   # bench.trace.Trace of a traced window
    trace_steps: int = 0


class CompileCounter:
    """Counts tracing, lowering, compiling and compile-cache loads."""

    EVENTS = ("/jax/core/compile/", "/jax/compilation_cache/")

    def __init__(self):
        self.count = 0
        self.on = False
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event: str, duration: float, **kw) -> None:
        if self.on and event.startswith(self.EVENTS):
            self.count += 1

    @contextlib.contextmanager
    def counting(self):
        self.on, self.count = True, 0
        try:
            yield self
        finally:
            self.on = False


def reference(cfg: Dict):
    """The plain reference module that the config file names, after it
    has checked that it models the config."""
    mod = importlib.import_module(f"bench.references.{cfg['reference']}")
    mod.check_config(cfg)
    return mod


def weights(prog, cfg: Dict, seed: int):
    return reference(cfg).init_params(cfg, seed, cfg["dtype"],
                                      NamedSharding(prog.mesh, P()))


def first_steps(prog, cfg: Dict, seed: int, feed, log) -> Tuple[Dict, Dict]:
    """Run the first ``CHECK_STEPS`` steps through ``Trainer.run``; return
    the program's readings and the ``Trainer.run`` result the window
    continues from.  The first step's gradient is read back from
    AdamW's first moment (per chip as norms, the first chip's whole on
    the host), the change of the parameters against the seed's weights
    made afresh."""
    b1 = reference(cfg).ADAM_B1
    params = weights(prog, cfg, seed)
    program.check_layout(prog, params)
    params, opt_state = program.init_state(prog, params)
    feed.offset = 0
    r1 = program.run(prog, params, opt_state, 1, 1, log)
    del params, opt_state
    moment = program.first_moment(r1["opt_state"])
    grad_norm = program.per_device_norms(prog, moment) / (1 - b1)
    grad = [np.asarray(x.addressable_shards[0].data) / (1 - b1)
            for x in jax.tree_util.tree_leaves(moment)]
    del moment
    feed.offset = 1
    rest = program.run(prog, r1.pop("params"), r1.pop("opt_state"),
                       CHECK_STEPS - 1, 1, log)
    p0 = weights(prog, cfg, seed)
    delta = program.per_device_norms(prog, rest["params"], p0)
    del p0
    losses = [h["loss"] for h in r1["history"] + rest["history"]]
    return {"loss": losses, "grad_norm": grad_norm, "grad": grad,
            "delta_norm": delta}, rest


def timed(prog, state: Dict, feed, steps: int, offset: int,
          counter: CompileCounter, log) -> Tuple[float, Dict, int]:
    """(seconds, Trainer.run result, compilations) of one window that
    continues from ``state`` (a ``Trainer.run`` result, emptied here so
    that only the program holds the state it starts from)."""
    feed.offset = offset
    with counter.counting():
        t0 = time.perf_counter()
        res = program.run(prog, state.pop("params"), state.pop("opt_state"),
                          steps, steps, log)
        jax.block_until_ready(res["params"])
        dt = time.perf_counter() - t0
    return dt, res, counter.count


def peak_bytes(devices) -> int:
    """Largest ``peak_bytes_in_use`` over the devices (0 where the
    backend keeps no statistics)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def reference_readings(cfg: Dict, seed: int, pool: List, warmup: int,
                       chips: int, devices, precision: str = "f32",
                       fault: str = "none") -> Dict:
    """The reference's readings of the first steps, its blocks of rows
    dealt out over ``devices``."""
    ref = reference(cfg)
    params0 = ref.init_params(
        cfg, seed, cfg["dtype"], jax.sharding.SingleDeviceSharding(devices[0]))
    with jax.default_device(devices[0]):
        return ref.train_readings(params0, pool[:CHECK_STEPS], cfg,
                                  warmup, chips, devices, precision, fault)
