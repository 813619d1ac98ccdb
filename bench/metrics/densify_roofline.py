"""Least time of densifying the embedding's gradient (bytes from
bench/flops.py over the chip's HBM bandwidth) over the measured device
time of the ops that do it, per step, averaged over the chips.

The ops are those whose scope ends in the exchange plan's ``pack`` with
a scatter (XLA's, which adds the rows into the dense gradient, in place
in the tied head's) or the densify kernel where the plan uses it."""
from bench import trace as T


def is_densify(op):
    p = op.path
    return ("exchange/" in p and "/pack/" in p
            and ("scatter" in p or "densify" in p))


def read(rec):
    if rec.trace is None or rec.peaks is None:
        return None
    tr = rec.trace
    per = []
    for d in tr.devices():
        lo, hi = T.window(tr, d)
        ops = [o for o in tr.ops if o.device == d and is_densify(o)]
        if ops:
            per.append(sum(min(o.end, hi) - max(o.start, lo) for o in ops)
                       * T.NS)
    if not per:
        return None
    measured = sum(per) / len(per) / rec.trace_steps
    least = rec.densify_bytes / rec.peaks["hbm_bytes_per_s"]
    return 100.0 * least / measured
