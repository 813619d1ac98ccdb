"""Target tokens trained in the window over all chips, per second of the
window's wall time (host clock, ending in block_until_ready)."""


def read(rec):
    return rec.window_tokens / rec.window_s
