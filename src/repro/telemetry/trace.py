"""Wire measurement and the profiler trace of the real training loop.

Two tools, neither of which changes the compiled program:

* ``measure_wire(fn, *args)`` — run ONE abstract evaluation
  (``jax.eval_shape``) of an exchange program with a ``WireRecorder``
  installed.  Every collective call site in ``core/comm.py`` /
  ``core/backend.py`` bills its per-worker wire bytes (using the same
  per-hop formulas as the plan's static accounting) to the enclosing
  stage scope.  Nothing executes — this is the runtime drift detector
  for what ``dryrun --audit-exchange`` checks against lowered HLO.
  ``plan_trace_meta`` writes it, with the plan's stage names, wire
  accounting and the tuner's predicted per-stage cost, into
  ``exchange.json`` beside a profile.

* ``load_profile(dir)`` — read what ``Trainer.run`` wrote under
  ``TrainerConfig(profile_dir=...)``: the ``jax.profiler`` capture of
  the loop's last steps (``.xplane.pb``) and the compiled step's HLO
  text, and (``read_donation``) the share of the train state whose
  buffers the step reuses.  Device ops come out per chip, each with the
  scope path that ``jax.named_scope`` wrote into its HLO ``op_name``
  metadata (the ``telemetry.hooks`` vocabulary and the
  ``exchange/sNN/...`` stage names); host spans come from the Python
  main thread, on the device ops' clock.  ``telemetry.report`` reduces
  them.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import math
import os
import re
from typing import Any, Callable, Dict, List, Optional

import jax

from repro.telemetry import hooks

EXCHANGE_META = "exchange.json"   # beside a profile: the plan's accounting
STEP_HLO = "step.hlo.txt"         # beside a profile: op -> scope metadata
DONATION = "donation.json"        # beside a profile: the state the step reuses


# ---------------------------------------------------------------------------
# Wire measurement (abstract — no execution, no program changes)
# ---------------------------------------------------------------------------

def measure_wire(fn: Callable, *args) -> hooks.WireRecorder:
    """Abstractly evaluate ``fn(*args)`` with a WireRecorder installed
    and return it.  Shapes/dtypes seen by the collective call sites are
    exact (tracer avals), so recorded bytes match the plan's static
    accounting formula-for-formula; stage scopes entered by the plan
    attribute every collective to its ``plan.stage_name``."""
    rec = hooks.WireRecorder()
    # jax caches inner traces (shard_map / custom_vjp bodies via
    # lu.cache); if fn was already lowered uninstrumented, a plain
    # eval_shape would replay the cached jaxpr and never run the
    # Python-level hook sites — force a full retrace
    jax.clear_caches()
    hooks.install_wire_recorder(rec)
    try:
        jax.eval_shape(fn, *args)
    finally:
        hooks.clear_wire_recorder()
    return rec


def plan_trace_meta(plan, n_workers, profile: str = "ethernet",
                    measured: Optional[hooks.WireRecorder] = None
                    ) -> Dict[str, Any]:
    """Self-contained metadata block for a profile directory: stage
    names, the plan's per-stage wire accounting, the tuner's per-stage
    predicted cost, and (when given) the wire bytes a ``measure_wire``
    recorder observed — everything ``trace_report`` needs besides the
    trace, without recompiling the plan."""
    names = plan.stage_names()
    stages = plan.schedule.stages
    planned = {n: int(plan.stage_wire_bytes(s, n_workers))
               for n, s in zip(names, stages)}
    meta: Dict[str, Any] = {
        "stage_names": list(names),
        "n_workers": (list(n_workers)
                      if isinstance(n_workers, (list, tuple))
                      else n_workers),
        "profile": profile,
        "mode": ("backward" if plan.config.overlap_backward
                 else "staged" if plan.config.overlap
                 else "zero1" if plan.config.zero1 else "fused"),
        "codec": plan.config.codec,
        "backend": plan.config.backend,
        "planned_wire_bytes": planned,
        "jax_version": jax.__version__,
    }
    try:
        from repro.tuning import cost as cost_lib
        from repro.tuning import get_profile
        prof = get_profile(profile)
        meta["predicted_us"] = {
            n: float(cost_lib.predict_stage_us(plan, s, n_workers, prof))
            for n, s in zip(names, stages)}
    except Exception as e:   # profile/tuning optional for raw traces
        meta["predicted_us_error"] = str(e)
    if measured is not None:
        meta["measured_wire_bytes"] = {
            k: v for k, v in measured.stage_wire_bytes().items()}
    return meta


def donated_share(compiled, state) -> Dict[str, Any]:
    """Bytes per device of the step's inputs that its outputs alias
    (the donated buffers it reuses) against the bytes per device of the
    train state ``state``: ``share`` is 1.0 where every state leaf is
    reused, and None where the backend reports no memory analysis."""
    state_bytes = sum(math.prod(x.sharding.shard_shape(x.shape))
                      * x.dtype.itemsize
                      for x in jax.tree_util.tree_leaves(state))
    stats = compiled.memory_analysis()
    aliased = None if stats is None else int(stats.alias_size_in_bytes)
    return {"aliased_bytes": aliased, "state_bytes": int(state_bytes),
            "share": (aliased / state_bytes
                      if aliased is not None and state_bytes else None)}


def write_step_hlo(trace_dir: str, jit_step, args, n_state: int) -> str:
    """Write the compiled step's HLO text into ``trace_dir``, and beside
    it (``donation.json``) the ``donated_share`` of the train state, the
    first ``n_state`` of ``args``."""
    compiled = jit_step.lower(*args).compile()
    path = os.path.join(trace_dir, STEP_HLO)
    with open(path, "w") as f:
        f.write(compiled.as_text())
    with open(os.path.join(trace_dir, DONATION), "w") as f:
        json.dump(donated_share(compiled, args[:n_state]), f, indent=1)
    return path


def read_donation(trace_dir: str) -> Optional[Dict[str, Any]]:
    path = os.path.join(trace_dir, DONATION)
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def write_meta(meta: Dict[str, Any], trace_dir: str) -> str:
    os.makedirs(trace_dir, exist_ok=True)
    path = os.path.join(trace_dir, EXCHANGE_META)
    with open(path, "w") as f:
        json.dump(meta, f, indent=1)
    return path


def read_meta(trace_dir: str) -> Dict[str, Any]:
    path = os.path.join(trace_dir, EXCHANGE_META)
    if not os.path.exists(path):
        return {}
    with open(path) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# The profiler's trace
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Op:
    device: int
    name: str
    start: int          # ns
    end: int            # ns
    path: str = ""      # the HLO op's op_name: its scope path


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: int
    end: int


@dataclasses.dataclass
class Profile:
    ops: List[Op]
    spans: List[Span]
    meta: Dict[str, Any]

    def devices(self) -> List[int]:
        return sorted({o.device for o in self.ops})

    def steps(self) -> int:
        """Steps in the capture: the Trainer's per-step host spans."""
        return max(sum(s.name == hooks.STEP for s in self.spans), 1)


_DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:(\d+)")
_HLO_INSTR = re.compile(r"^\s*(?:ROOT\s+)?(%[^\s=]+)\s*=(.*)$", re.M)
_OP_NAME = re.compile(r'metadata=\{op_name="([^"]*)"')
_OPERAND = re.compile(r"%[\w.\-]+")
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_HLO_MODULE = re.compile(r"^HloModule ([^\s,]+)", re.M)
# the Python main thread's lines: the profiler's Python tracer, named
# for the interpreter ("python", "python3"), which holds the Trainer's
# spans, and the thread's TraceMe line ("main/<tid>") of the runtime's
_HOST_LINE = re.compile(r"^(python[\d.]*|main)\b")


def hlo_scopes(hlo_text: str) -> Dict[str, str]:
    """HLO instruction name -> its scope path, from a compiled module's
    text: the ``op_name`` metadata that ``jax.named_scope`` writes.  An
    instruction the compiler made without metadata (a layout copy, a
    split reduction, a rewritten dot) takes the path of its first
    operand that has one; failing that (a loop buffer's initial value)
    the path of its first user, through tuples, which carry none of
    their own."""
    paths: Dict[str, str] = {}
    operands: Dict[str, List[str]] = {}
    tuples = set()
    for m in _HLO_INSTR.finditer(hlo_text):
        name, rest = m.groups()
        meta = _OP_NAME.search(rest)
        if meta:
            paths[name] = meta.group(1)
        head = rest.split(" metadata=")[0]
        op = _OPCODE.search(head)
        if op and op.group(1) == "tuple":
            tuples.add(name)
        operands[name] = [t for t in _OPERAND.findall(head)
                          if t in operands]
    for name, ins in operands.items():     # operands come first
        if name not in paths and name not in tuples:
            known = [paths[o] for o in ins if o in paths]
            if known:
                paths[name] = known[0]
    users: Dict[str, List[str]] = {}
    for name, ins in operands.items():
        for o in ins:
            users.setdefault(o, []).append(name)
    for name in reversed(list(operands)):  # users come later
        if name not in paths:
            known = [paths[u] for u in users.get(name, ()) if u in paths]
            if known:
                paths[name] = known[0]
    return paths


def _xplane_files(trace_dir: str) -> List[str]:
    """The newest capture's ``.xplane.pb`` files under ``trace_dir``."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    newest = max(os.path.dirname(f) for f in files)
    return sorted(f for f in files if os.path.dirname(f) == newest)


def load_profile(trace_dir: str) -> Profile:
    """Device ops (a TPU's "XLA Ops" line; a CPU backend's ops of the
    step's module on its host threads) and the host spans of the Python
    main thread.  A TPU names an op by its HLO instruction; the scope
    path comes from ``step.hlo.txt`` beside the capture."""
    from jax.profiler import ProfileData
    hlo_path = os.path.join(trace_dir, STEP_HLO)
    hlo = open(hlo_path).read() if os.path.exists(hlo_path) else ""
    scopes = hlo_scopes(hlo)
    module = _HLO_MODULE.search(hlo)
    module = module.group(1) if module else None
    ops: List[Op] = []
    spans: List[Span] = []
    for f in _xplane_files(trace_dir):
        for plane in ProfileData.from_file(f).planes:
            dev = _DEVICE_PLANE.match(plane.name)
            if not dev and not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                if dev and line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    start = int(ev.start_ns)
                    end = start + int(ev.duration_ns)
                    if dev:
                        name = ev.name.split(" = ", 1)[0]
                        ops.append(Op(int(dev.group(1)), name, start, end,
                                      scopes.get(name, "")))
                        continue
                    st = {k: v for k, v in ev.stats}
                    if "hlo_op" in st and "device_ordinal" in st:
                        # a CPU backend runs its "device" ops here
                        if module and st.get("hlo_module") not in (
                                None, module):
                            continue
                        name = "%" + str(st["hlo_op"])
                        ops.append(Op(int(st["device_ordinal"]), name,
                                      start, end, scopes.get(name, "")))
                    elif _HOST_LINE.match(line.name) and end > start:
                        spans.append(Span(ev.name, start, end))
    return Profile(ops, spans, read_meta(trace_dir))
