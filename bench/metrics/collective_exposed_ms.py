"""Device time per step of the exchange plan's collectives (all-reduce,
reduce-scatter, all-gather, ... under ``exchange/`` scopes,
core/exchange.py) during which no other operation runs on the chip: the
part of the cross-chip exchange that compute does not hide.  The worst
chip's, since a step waits for the slowest; None where no chip ran such
a collective (one chip)."""
from bench import trace as T


def is_exchange_collective(op):
    return "exchange/" in op.path and T.is_collective(op)


def read(rec):
    if rec.trace is None:
        return None
    tr = rec.trace
    per = [T.exposed(tr, d, T.window(tr, d), is_exchange_collective)
           for d in tr.devices()]
    per = [x for x in per if x is not None]
    if not per:
        return None
    return 1e3 * max(per) / rec.trace_steps
