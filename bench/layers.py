"""Device time under the program's named scopes: what the per-layer
readers of its layers share.

The program names each layer of its training step with
``jax.named_scope`` (``model/self_attn``, ``model/ffn``, ...), which
reaches every device op's scope path through the HLO metadata, forward
and backward (``transpose(jvp(model/ffn))/...``); no name is a
substring of another, so a substring test finds both.  A program
without them (an older commit) gives these readers nothing to read:
they return None.
"""
from __future__ import annotations

from typing import Optional, Sequence

from bench import trace as T


def scope_ms(rec, scopes: Sequence[str],
             but: Sequence[str] = ()) -> Optional[float]:
    """Device ms per step of the ops under any of ``scopes``, the union
    of their intervals in the traced window, averaged over the chips.
    With ``but``, only the ops that lie under none of those scopes and
    enclose no other op: a loop around ops of ``but`` (the layer scan's
    ``while``) spans them, and is left out."""
    if rec.trace is None:
        return None
    tr = rec.trace
    per, found = [], False
    for d in tr.devices():
        lo, hi = T.window(tr, d)
        ops = [o for o in tr.ops if o.device == d]
        if but:
            ops = [o for o, _, parent in T._self_times(ops) if not parent
                   and not any(s in o.path for s in but)]
        iv = [(o.start, o.end) for o in ops
              if any(s in o.path for s in scopes)]
        found = found or bool(iv)
        per.append(T._length(T._clip(T.merge(iv), lo, hi)) * T.NS)
    if not found:
        return None
    return 1e3 * sum(per) / len(per) / rec.trace_steps
