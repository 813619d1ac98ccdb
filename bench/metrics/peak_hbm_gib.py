"""Largest ``peak_bytes_in_use`` over the cell's devices after the
window, in GiB: whether the job fits."""


def read(rec):
    return rec.peak_bytes / 2 ** 30 if rec.peak_bytes else None
