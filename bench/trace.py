"""From a profiler trace to the numbers the per-layer metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into two
flat lists: device operations (device, name, start, end, the HLO op's
scope path) and host spans (name, start, end).  The reductions
below work on those lists only, so a test can feed them a synthetic
trace:

* ``busy``: the union of a device's operation intervals in a window;
* ``scope_seconds``: device time of the operations whose scope path
  contains a prefix (``exchange/`` for the exchange plan);
* ``exposed``: the part of some operations' time (the collectives)
  during which no other operation runs on that device;
* ``idle_gaps``: the gaps between operations, each labelled by the
  innermost host span that covers it;
* ``top_ops``: device time by operation name.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

NS = 1e-9
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all|allreduce|allgather")


@dataclasses.dataclass(frozen=True)
class Op:
    device: int
    name: str
    start: int          # ns
    end: int            # ns
    path: str = ""      # scope path of the HLO op (named_scope prefixes)


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: int
    end: int


@dataclasses.dataclass
class Trace:
    ops: List[Op]
    spans: List[Span]
    modules: List[Op]   # whole-program executions, where the trace has them

    def devices(self) -> List[int]:
        return sorted({o.device for o in self.ops})


# ---------------------------------------------------------------------------
# loading
# ---------------------------------------------------------------------------

_DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:(\d+)")
_HLO_OP = re.compile(r'^\s*(?:ROOT\s+)?(%[^\s=]+)\s*=.*?'
                     r'metadata=\{op_name="([^"]*)"', re.M)
# the host lines of the Python main thread: the profiler's Python
# tracer ("python") and the thread's own TraceMe line ("main/<tid>"),
# where ``jax.profiler.TraceAnnotation`` spans land on a TPU host
HOST_LINE = re.compile(r"^(python|main)\b")


def hlo_scopes(hlo_text: str) -> Dict[str, str]:
    """HLO instruction name -> its ``op_name`` metadata (the scope path
    that ``jax.named_scope`` writes), from a compiled module's text."""
    return {m.group(1): m.group(2) for m in _HLO_OP.finditer(hlo_text)}


def _stats(ev) -> Dict[str, object]:
    return {k: v for k, v in ev.stats}


def load(profile_dir: str, hlo_text: str = "") -> Trace:
    """Device ops (a TPU's "XLA Ops" line; a CPU backend's ops on its
    host threads), step executions ("XLA Modules") and the spans of the
    host's Python main thread.  A TPU names an op by its HLO text; the
    scope path comes from the compiled module's ``hlo_text``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    scopes = hlo_scopes(hlo_text)
    ops: List[Op] = []
    spans: List[Span] = []
    modules: List[Op] = []
    for f in files:
        for plane in ProfileData.from_file(f).planes:
            m = _DEVICE_PLANE.match(plane.name)
            if not m and not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                if m and line.name not in ("XLA Ops", "XLA Modules"):
                    continue
                for ev in line.events:
                    start = int(ev.start_ns)
                    end = start + int(ev.duration_ns)
                    if m and line.name == "XLA Modules":
                        modules.append(Op(int(m.group(1)), ev.name, start,
                                          end))
                    elif m:
                        name = ev.name.split(" = ", 1)[0]
                        ops.append(Op(int(m.group(1)), name, start, end,
                                      scopes.get(name, "")))
                    else:
                        st = _stats(ev)
                        if "hlo_op" in st and "device_ordinal" in st:
                            # a CPU backend runs its "device" ops here
                            name = "%" + str(st["hlo_op"])
                            ops.append(Op(int(st["device_ordinal"]), name,
                                          start, end, scopes.get(name, "")))
                        elif HOST_LINE.match(line.name) and end > start:
                            spans.append(Span(ev.name, start, end))
    return Trace(ops, spans, modules)


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------

def merge(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if min(e, hi) > max(s, lo)]


def _length(iv) -> int:
    return sum(e - s for s, e in iv)


def _intersect(a, b) -> List[Tuple[int, int]]:
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


def window(trace: Trace, device: int) -> Tuple[int, int]:
    """From the first step program's start to the last one's end on the
    device (whole ops where the trace has no module events)."""
    mods = [m for m in trace.modules if m.device == device]
    src = mods or [o for o in trace.ops if o.device == device]
    return min(o.start for o in src), max(o.end for o in src)


def busy(trace: Trace, device: int, win: Tuple[int, int]) -> float:
    iv = merge((o.start, o.end) for o in trace.ops if o.device == device)
    return _length(_clip(iv, *win)) * NS


def scope_seconds(trace: Trace, device: int, prefix: str,
                  win: Tuple[int, int]) -> float:
    iv = [(o.start, o.end) for o in trace.ops
          if o.device == device and prefix in o.path]
    return _length(_clip(merge(iv), *win)) * NS


def _self_times(ops: List[Op]) -> List[Tuple[Op, int, bool]]:
    """(op, self ns, has children) for one device's ops: an op that
    encloses others (a ``while`` around its body) keeps only the time
    its children do not cover."""
    out: List[List] = []
    stack: List[List] = []
    for o in sorted(ops, key=lambda o: (o.start, -o.end)):
        while stack and stack[-1][0].end <= o.start:
            stack.pop()
        rec = [o, o.end - o.start, False]
        if stack and o.end <= stack[-1][0].end:
            stack[-1][1] -= o.end - o.start
            stack[-1][2] = True
        out.append(rec)
        stack.append(rec)
    return [tuple(r) for r in out]


def exposed(trace: Trace, device: int, win: Tuple[int, int],
            is_target: Callable[[Op], bool]) -> Optional[float]:
    """Seconds of the target ops' time during which no other leaf op
    runs on the device; None where the device ran no target op."""
    mine = [o for o in trace.ops if o.device == device]
    tgt = _clip(merge((o.start, o.end) for o in mine if is_target(o)), *win)
    if not tgt:
        return None
    other = merge((o.start, o.end) for o, _, parent in _self_times(mine)
                  if not parent and not is_target(o))
    return (_length(tgt) - _length(_intersect(tgt, other))) * NS


def is_collective(op: Op) -> bool:
    return bool(COLLECTIVE.search(op.name))


def idle_gaps(trace: Trace, device: int, win: Tuple[int, int],
              top: int = 10) -> List[Tuple[str, float]]:
    """The longest gaps between the device's ops inside ``win``, each
    named by the innermost host span covering its middle."""
    iv = _clip(merge((o.start, o.end) for o in trace.ops
                     if o.device == device), *win)
    gaps = [(e0, s1) for (_, e0), (s1, _) in zip(iv, iv[1:]) if s1 > e0]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (s + e) // 2
        cover = [sp for sp in trace.spans if sp.start <= mid < sp.end]
        label = (min(cover, key=lambda sp: sp.end - sp.start).name
                 if cover else "no host span")
        out.append((label, (e - s) * NS))
    return out


def _label(op: Op) -> str:
    """Op name with the last two parts of its scope path."""
    tail = "/".join(op.path.split("/")[-2:])
    return f"{op.name} {tail}" if tail else op.name


def top_ops(trace: Trace, devices: Sequence[int], win_by_dev: Dict,
            top: int = 10) -> List[Tuple[str, float]]:
    """Device self seconds by op, averaged over ``devices``."""
    tot: Dict[str, float] = {}
    for d in devices:
        lo, hi = win_by_dev[d]
        mine = [o for o in trace.ops if o.device == d
                and o.start >= lo and o.end <= hi]
        for o, self_ns, _ in _self_times(mine):
            key = _label(o)
            tot[key] = tot.get(key, 0.0) + self_ns * NS / len(devices)
    return sorted(tot.items(), key=lambda kv: -kv[1])[:top]


def device_seconds(trace: Trace) -> Tuple[float, float]:
    """(busy, window) seconds of the traced steps, averaged over chips."""
    busy_s = win_s = 0.0
    devs = trace.devices()
    for d in devs:
        w = window(trace, d)
        busy_s += busy(trace, d, w) / len(devs)
        win_s += (w[1] - w[0]) * NS / len(devs)
    return busy_s, win_s


def breakdown(trace: Trace) -> Dict[str, List]:
    """The ``breakdown`` of a result line: top device ops, longest idle
    gaps of the first chip by what the host was doing."""
    devs = trace.devices()
    wins = {d: window(trace, d) for d in devs}
    return {"device_ops": [list(x) for x in top_ops(trace, devs, wins)],
            "idle_gaps": [list(x) for x in
                          idle_gaps(trace, devs[0], wins[devs[0]])]}
