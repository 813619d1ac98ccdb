"""Device time per step of the embedding lookup and the tied head with
its cross-entropy, forward and backward: the ops under the program's
``model/embed`` or ``model/head`` scopes (models/model.py), averaged
over the chips."""
from bench.layers import scope_ms


def read(rec):
    return scope_ms(rec, ("model/embed", "model/head"))
