"""Device time per step of the ops under the exchange plan's
``exchange/`` scopes (core/exchange.py), averaged over the chips."""
from bench import trace as T


def read(rec):
    if rec.trace is None:
        return None
    tr = rec.trace
    per = [T.scope_seconds(tr, d, "exchange/", T.window(tr, d))
           for d in tr.devices()]
    if not any(per):
        return None
    return 1e3 * sum(per) / len(per) / rec.trace_steps
