"""Device time per step of self- and cross-attention, forward and
backward: the ops under the program's ``model/self_attn`` or
``model/cross_attn`` scopes (models/model.py ``_block``, with their
norms and residuals), averaged over the chips."""
from bench.layers import scope_ms


def read(rec):
    return scope_ms(rec, ("model/self_attn", "model/cross_attn"))
