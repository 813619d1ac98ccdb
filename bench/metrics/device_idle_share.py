"""1 - the union of device-op intervals over the traced window of whole
steps (first step program's start to the last one's end), averaged over
the chips."""
from bench import trace as T


def read(rec):
    if rec.trace is None:
        return None
    tr = rec.trace
    busy = span = 0.0
    for d in tr.devices():
        w = T.window(tr, d)
        busy += T.busy(tr, d, w)
        span += (w[1] - w[0]) * T.NS
    return 100.0 * (1.0 - busy / span)
