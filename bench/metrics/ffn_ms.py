"""Device time per step of the feed-forward, forward and backward: the
ops under the program's ``model/ffn`` scope (models/model.py ``_block``:
``norm2``, the SwiGLU MLP, the residual), averaged over the chips."""
from bench.layers import scope_ms


def read(rec):
    return scope_ms(rec, ("model/ffn",))
