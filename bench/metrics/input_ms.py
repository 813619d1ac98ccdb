"""The program Trainer's own ``data_ms`` over the window's steps: host
time fetching and ``device_put``-ing each batch (training/trainer.py)."""


def read(rec):
    return rec.input_ms
