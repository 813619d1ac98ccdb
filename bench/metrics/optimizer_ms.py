"""Device time per step of the optimizer update and the parameter write:
the ops under the program's ``optim/update`` scope
(training/train_step.py, optim/adamw.py), averaged over the chips."""
from bench.layers import scope_ms


def read(rec):
    return scope_ms(rec, ("optim/update",))
