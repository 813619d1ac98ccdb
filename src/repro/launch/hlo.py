"""Optimized-HLO text analysis: collective bytes with while-loop trip
counts multiplied through.

XLA's cost_analysis counts a while body once; the paper's quantity of
interest — bytes moved by collectives per step — needs the layer-scan
multiplier.  We parse the post-optimization HLO text into computations,
attribute collective result-bytes to each computation, recover while trip
counts from the loop-condition constants, and roll bytes up through the
call graph (calls, fusions, conditionals, whiles).
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

COLLECTIVE_OPS = ("all-gather-start", "all-reduce-start",
                  "reduce-scatter", "all-to-all", "collective-permute-start",
                  "all-gather", "all-reduce", "collective-permute")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8,
                "s32": 4, "u64": 8, "u32": 4, "s16": 2, "u16": 2,
                "s8": 1, "u8": 1, "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_COMP_RE = re.compile(r"^(?:ENTRY )?%?([\w\.\-]+)\s*(?:\(.*\))?\s*->.*{\s*$")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_CALLSITE_RE = re.compile(
    r"(?:to_apply|body|condition|branch_computations|called_computations)="
    r"[{]?%?([\w\.\-]+(?:,\s*%?[\w\.\-]+)*)[}]?")
_CONST_RE = re.compile(r"constant\((\d+)\)")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        elems = 1
        if dims:
            for d in dims.split(","):
                elems *= int(d)
        total += elems * _DTYPE_BYTES.get(dt, 4)
    return total


def parse_computations(hlo: str) -> Dict[str, List[str]]:
    """computation name -> list of instruction lines."""
    comps: Dict[str, List[str]] = {}
    cur: Optional[str] = None
    for line in hlo.splitlines():
        stripped = line.strip()
        m = _COMP_RE.match(line) or _COMP_RE.match(stripped)
        if m and stripped.endswith("{"):
            cur = m.group(1)
            comps[cur] = []
            continue
        if stripped.startswith("}"):
            cur = None
            continue
        if cur is not None and "=" in stripped:
            if stripped.startswith("ROOT "):
                stripped = stripped[5:]
            comps[cur].append(stripped)
    return comps


_NAME_EQ_RE = re.compile(r"%?[\w\.\-]+\s*=\s*")
_ARRAY_TYPE_RE = re.compile(r"[a-z0-9]+\[[0-9,]*\](?:{[^}]*})?")


def _result_and_opcode(line: str) -> Tuple[str, str]:
    """("bf16[8,128]{1,0}", "all-reduce") from "%x = bf16[8,128]{1,0}
    all-reduce(...)".  Tuple results are matched by balanced parentheses:
    TPU layouts carry their own, as in ``{0:T(1024)(128)}``."""
    m = _NAME_EQ_RE.match(line)
    if not m:
        return "", ""
    rest = line[m.end():]
    if rest.startswith("("):
        depth = 0
        for end, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        type_str, tail = rest[:end + 1], rest[end + 1:]
    else:
        t = _ARRAY_TYPE_RE.match(rest)
        if not t:
            return "", ""
        type_str, tail = t.group(0), rest[t.end():]
    op = re.match(r"\s+([\w\-]+)", tail)
    return type_str, (op.group(1) if op else "")


def _instr_opcode(line: str) -> str:
    # "%name = bf16[8,128]{1,0} all-reduce(...)" -> opcode after type
    return _result_and_opcode(line)[1]


def _instr_result_bytes(line: str) -> int:
    return _shape_bytes(_result_and_opcode(line)[0])


def _instr_operand_count(line: str, op: str) -> int:
    """Top-level operands of ``op(...)`` in an instruction line."""
    start = line.find(f" {op}(")
    if start < 0:
        return 1
    depth, n, seen = 0, 1, False
    for ch in line[start + len(op) + 2:]:
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            if depth == 0:
                break
            depth -= 1
        elif ch == "," and depth == 0:
            n += 1
        elif not ch.isspace():
            seen = True
    return n if seen else 0


def count_collectives(hlo: str) -> Dict[str, int]:
    """Number of collectives per op kind in the HLO text as the program
    emitted them (flat, no while-trip multipliers — for auditing
    explicitly-scheduled exchange programs, which have no loops).

    Async pairs (``-start``/``-done``) count once.  XLA's collective
    combiners merge independent collectives into one variadic op
    (``all-reduce(%a, %b, %c)`` with a tuple result); each operand of
    such an op counts as one collective, so the count is the same
    whether or not the compiler combined them.
    """
    counts: Dict[str, int] = {}
    for name, lines in parse_computations(hlo).items():
        for line in lines:
            op = _instr_opcode(line)
            if op.endswith("-done"):
                continue
            base = op.replace("-start", "")
            if base in ("all-gather", "all-reduce", "reduce-scatter",
                        "all-to-all", "collective-permute"):
                counts[base] = (counts.get(base, 0)
                                + _instr_operand_count(line, op))
    return counts


def analyze_collectives(hlo: str) -> Dict[str, float]:
    """Collective bytes per op type, while-trip-count-aware."""
    comps = parse_computations(hlo)

    # per-computation local collective bytes + call edges
    local: Dict[str, Dict[str, float]] = {}
    edges: Dict[str, List[Tuple[str, str]]] = {}   # comp -> [(kind, callee)]
    for name, lines in comps.items():
        loc: Dict[str, float] = {}
        ed: List[Tuple[str, str]] = []
        for line in lines:
            op = _instr_opcode(line)
            base = op.replace("-start", "").replace("-done", "")
            rb = _instr_result_bytes(line)
            if base in ("all-gather", "all-reduce", "reduce-scatter",
                        "all-to-all", "collective-permute") \
                    and not op.endswith("-done"):
                loc[base] = loc.get(base, 0.0) + rb
            # post-fusion HBM write-traffic proxy: every instruction's
            # result is materialised except (a) trivial/aliasing ops,
            # (b) control-flow results (their bodies are counted with the
            # trip multiplier; counting the while result would double-
            # count the whole carried state), (c) bf16->f32 convert
            # fusions, which are a CPU-backend lowering artifact — the
            # TPU target computes bf16 natively on the MXU.
            if base in ("parameter", "constant", "tuple",
                        "get-tuple-element", "bitcast", "while",
                        "conditional", "call", "after-all",
                        "opt-barrier", "optimization-barrier"):
                pass
            elif ("calls=%wrapped_convert" in line
                  or "calls=%wrapped_transpose" in line
                  or "calls=%wrapped_broadcast" in line):
                # convert fusions: CPU bf16 artifact (free on the MXU);
                # broadcast-of-constant fusions: buffer zero-inits that
                # XLA aliases/hoists — not steady-state HBM traffic
                pass
            elif "dynamic-update-slice" in line.split("=")[0] \
                    or base == "dynamic-update-slice":
                # in-place updates alias the input buffer: the true write
                # is the (small) updated slice, already accounted for by
                # the op that produced it — counting the full result
                # would bill the whole KV cache per decode step
                pass
            else:
                loc["__bytes__"] = loc.get("__bytes__", 0.0) + rb
            m = re.search(r"body=%?([\w\.\-]+)", line)
            c = re.search(r"condition=%?([\w\.\-]+)", line)
            if m and c:
                ed.append((f"while:{c.group(1)}", m.group(1)))
            elif op == "call":
                for m2 in re.finditer(r"to_apply=%?([\w\.\-]+)", line):
                    ed.append(("call", m2.group(1)))
            m3 = re.search(r"branch_computations=\{([^}]*)\}", line)
            if m3:
                for b in m3.group(1).split(","):
                    ed.append(("branch", b.strip().lstrip("%")))
        local[name] = loc
        edges[name] = ed

    def trip_count(cond_name: str) -> int:
        consts = []
        for line in comps.get(cond_name, []):
            for m in _CONST_RE.finditer(line):
                consts.append(int(m.group(1)))
        return max(consts) if consts else 1

    memo: Dict[str, Dict[str, float]] = {}

    def total(name: str, seen=()) -> Dict[str, float]:
        if name in memo:
            return memo[name]
        if name in seen:
            return {}
        out = dict(local.get(name, {}))
        for kind, callee in edges.get(name, []):
            sub = total(callee, seen + (name,))
            mult = 1
            if kind.startswith("while:"):
                mult = trip_count(kind.split(":", 1)[1])
            for k, v in sub.items():
                out[k] = out.get(k, 0.0) + mult * v
        memo[name] = out
        return out

    entry = None
    for line in hlo.splitlines():
        if line.startswith("ENTRY"):
            m = re.match(r"ENTRY\s+%?([\w\.\-]+)", line)
            if m:
                entry = m.group(1)
                break
    if entry is None or entry not in comps:
        # fall back: sum everything flat
        out: Dict[str, float] = {}
        for loc in local.values():
            for k, v in loc.items():
                out[k] = out.get(k, 0.0) + v
        return out
    return total(entry)
