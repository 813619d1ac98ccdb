"""Device time per step of the layer scan's own ops: those under the
program's ``model/layers`` scope (models/model.py, around the scan over
the stacked layers) that lie under none of the block scopes it holds
and enclose no other op (not the scan's ``while`` itself), such as each
layer's weight slice and the residuals the forward stores for the
backward; averaged over the chips."""
from bench.layers import scope_ms


def read(rec):
    return scope_ms(rec, ("model/layers",),
                    but=("model/self_attn", "model/cross_attn",
                         "model/ffn"))
