"""Process-global telemetry hook points and the trace vocabulary.

This module is the *leaf* of the telemetry package: it is stdlib-only
(no jax, no repro imports) so that hot-path modules (``core.comm``,
``core.backend``, ``core.exchange``, ``models``, ``training``) can
import it unconditionally without creating import cycles or pulling
tracing machinery into the default path.

Two things live here:

* The names every layer of the training step is known by in a trace.
  ``jax.named_scope`` writes a device scope into each HLO op's
  ``op_name`` metadata, so the profiler's device ops carry it; JAX
  keeps it through autodiff, so backward ops read e.g.
  ``transpose(jvp(model/ffn))/...`` and a scope counts forward plus
  backward.  ``jax.profiler.TraceAnnotation`` spans land on the host's
  Python line of the same trace (named for the interpreter:
  ``python``, ``python3``), on the device ops' clock.  No name is a
  substring of another.
* The wire recorder: ``wire_recorder()`` returns ``None`` unless one
  was installed around a single abstract evaluation
  (``telemetry.trace.measure_wire``).  Every call site gates on that
  before doing any work, so the disabled path costs one global read at
  trace time only; nothing here executes per training step.
"""
from __future__ import annotations

import re
import threading
from contextlib import contextmanager

__all__ = [
    "WireRecorder", "wire_recorder", "install_wire_recorder",
    "clear_wire_recorder", "stage_scope", "current_stage",
    "record_collective", "UNATTRIBUTED", "EMBED", "LAYERS", "SELF_ATTN",
    "CROSS_ATTN", "FFN", "HEAD", "OPTIM", "EXCHANGE", "LAYER_SCOPES",
    "ONE_BLOCK", "KV_SCAN",
    "STEP", "FETCH", "DEVICE_PUT", "DISPATCH", "LOG", "CHECKPOINT",
    "TRAINER_SPANS", "in_scope",
]

UNATTRIBUTED = "unattributed"

# -- device scopes (``jax.named_scope``) of the training step ---------------
EMBED = "model/embed"            # Model.forward: lookup + sparse taps
LAYERS = "model/layers"          # Model.forward: the scanned layer stack;
#   it holds the next three, and on its own the scan's plumbing (each
#   layer's weight slice, the residuals stored for the backward)
SELF_ATTN = "model/self_attn"    # _block: norm1, attention, residual
CROSS_ATTN = "model/cross_attn"  # _block: norm_x, cross-attention, residual
FFN = "model/ffn"                # _block: norm2, MLP / experts, residual
HEAD = "model/head"              # Model.loss: tied logits + cross-entropy
OPTIM = "optim/update"           # train_step: optimizer update + apply
EXCHANGE = "exchange"            # core/exchange.py: exchange/sNN/<kind>/...
LAYER_SCOPES = (EMBED, LAYERS, SELF_ATTN, CROSS_ATTN, FFN, HEAD, OPTIM)
# nested in SELF_ATTN / CROSS_ATTN: which path ``kernels.ops``'s XLA
# attention took, so a profile splits ``attention_ms`` between them
ONE_BLOCK = "attention/one_block"  # whole kv in one block, custom_vjp
KV_SCAN = "attention/kv_scan"      # online-softmax scan over kv chunks

# -- host spans (``jax.profiler``) of ``Trainer.run``, in step order --------
STEP = "train"                   # StepTraceAnnotation around one step
FETCH = "trainer/fetch"          # pipeline.batch_at
DEVICE_PUT = "trainer/device_put"
DISPATCH = "trainer/dispatch"    # the jitted step's call
LOG = "trainer/log"              # log boundary: host reads, recorder flush
CHECKPOINT = "trainer/checkpoint"
TRAINER_SPANS = (FETCH, DEVICE_PUT, DISPATCH, LOG, CHECKPOINT)


def in_scope(path: str, scope: str) -> bool:
    """Whether an op's ``op_name`` path lies under ``scope``, forward
    (``.../model/ffn/dot_general``) or differentiated
    (``transpose(jvp(model/ffn))/...``)."""
    return re.search(r"(^|[/(])" + re.escape(scope) + r"([/)]|$)",
                     path) is not None

# Telemetry state is intentionally process-global (not thread-local):
# recorders are installed around a single trace/lowering, and jax may
# run parts of tracing on worker threads.  A lock guards install /
# clear; reads are plain (benign under CPython).
_LOCK = threading.Lock()
_WIRE = None
_STAGE: list[str] = []


class WireRecorder:
    """Accumulates per-stage collective counts and wire bytes.

    Populated by ``record_collective`` calls emitted from
    ``core.comm`` / ``core.backend`` while the recorder is installed.
    Bytes use the same per-hop formulas as the plan's static
    accounting, so for an exact backend+codec the recorded totals
    match ``ExchangePlan.stage_wire_bytes`` bit-for-bit.
    """

    def __init__(self) -> None:
        self.per_stage: dict[str, dict] = {}

    def record(self, kind: str, nbytes: float, stage: str | None) -> None:
        key = stage if stage is not None else UNATTRIBUTED
        row = self.per_stage.setdefault(
            key, {"wire_bytes": 0.0, "collectives": 0, "by_kind": {}})
        row["wire_bytes"] += float(nbytes)
        row["collectives"] += 1
        row["by_kind"][kind] = row["by_kind"].get(kind, 0) + 1

    def stage_wire_bytes(self) -> dict[str, float]:
        return {k: v["wire_bytes"] for k, v in self.per_stage.items()}

    def total_wire_bytes(self) -> float:
        return sum(v["wire_bytes"] for v in self.per_stage.values())

    def total_collectives(self) -> int:
        return sum(v["collectives"] for v in self.per_stage.values())

    def as_dict(self) -> dict:
        return {
            "per_stage": {k: dict(v, by_kind=dict(v["by_kind"]))
                          for k, v in self.per_stage.items()},
            "total_wire_bytes": self.total_wire_bytes(),
            "total_collectives": self.total_collectives(),
        }


def wire_recorder():
    """The installed WireRecorder, or None (the default)."""
    return _WIRE


def install_wire_recorder(rec: WireRecorder) -> None:
    global _WIRE
    with _LOCK:
        if _WIRE is not None:
            raise RuntimeError("a WireRecorder is already installed")
        _WIRE = rec


def clear_wire_recorder() -> None:
    global _WIRE
    with _LOCK:
        _WIRE = None



@contextmanager
def stage_scope(label: str):
    """Attribute nested ``record_collective`` calls to a stage.

    No-op-cheap: maintains a plain list even when telemetry is off (a
    trace-time append/pop, nothing captured into the jaxpr).
    """
    _STAGE.append(label)
    try:
        yield
    finally:
        _STAGE.pop()


def current_stage() -> str | None:
    return _STAGE[-1] if _STAGE else None


def record_collective(kind: str, nbytes: float) -> None:
    """Bill one collective to the current stage.

    Callers gate on ``wire_recorder() is not None`` before computing
    ``nbytes``; calling this unconditionally is also safe (no-op when
    nothing is installed).
    """
    rec = _WIRE
    if rec is not None:
        rec.record(kind, nbytes, current_stage())

