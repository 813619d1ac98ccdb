"""The readers of the program's layer scopes, on a small synthetic
trace (bench/layers.py and its metrics)."""
import types

import pytest

from bench import trace as T
from bench.metrics import (attention_ms, exchange_ms, ffn_ms, head_ms,
                           optimizer_ms, scan_ms)

US = 1000     # ns
READERS = (attention_ms, ffn_ms, head_ms, optimizer_ms, scan_ms)


def synthetic(scoped=True):
    """Two devices, two steps of 100 us each.  Per step at offset t:

    embed [t, t+2), self_attn [t+2, t+12) with its backward
    [t+40, t+50), cross_attn [t+12, t+18), ffn [t+18, t+30) and its
    backward [t+50, t+62), head [t+30, t+40) (forward and backward),
    the exchange's all-reduce [t+62, t+70), optimizer [t+70, t+80),
    the layer scan's residual store [t+80, t+84), idle [t+84, t+100).
    The blocks lie under ``model/layers`` as the scan nests them."""
    def path(scope):
        return scope if scoped else "jit(step)/while/body/dot_general"

    ops, mods = [], []
    for dev in (0, 1):
        for step in range(2):
            t = step * 100 * US
            mods.append(T.Op(dev, "jit_step", t, t + 100 * US))
            for name, a, b, p in (
                    ("%gather.1", 0, 2, "jit(step)/jvp(model/embed)/gather"),
                    ("%fusion.2", 2, 12, "jit(step)/jvp(model/layers)/"
                     "while/body/closed_call/model/self_attn/dot_general"),
                    ("%fusion.3", 12, 18, "jit(step)/jvp(model/layers)/"
                     "while/body/closed_call/model/cross_attn/dot_general"),
                    ("%fusion.4", 18, 30, "jit(step)/jvp(model/layers)/"
                     "while/body/closed_call/model/ffn/dot_general"),
                    ("%fusion.5", 30, 40, "jit(step)/transpose("
                     "jvp(model/head))/dot_general"),
                    ("%fusion.6", 40, 50, "jit(step)/transpose(jvp(model/"
                     "layers))/while/body/closed_call/model/self_attn/"
                     "dot_general"),
                    ("%fusion.7", 50, 62, "jit(step)/transpose(jvp(model/"
                     "layers))/while/body/closed_call/model/ffn/dot_general"),
                    ("%all-reduce.8", 62, 70, "jit(step)/exchange/s00/"
                     "allreduce/bucket=dense0/psum"),
                    ("%fusion.9", 70, 80, "jit(step)/optim/update/add"),
                    ("%fusion.10", 80, 84, "jit(step)/jvp(model/layers)/"
                     "while/body/dynamic_update_slice")):
                ops.append(T.Op(dev, name, t + a * US, t + b * US,
                                path(p) if "exchange" not in p else p))
    return T.Trace(ops, [], mods)


def record(tr, steps=2):
    return types.SimpleNamespace(trace=tr, trace_steps=steps)


def test_attention_counts_self_and_cross_forward_and_backward():
    # self 10 + cross 6 + self backward 10 = 26 us per step
    assert attention_ms.read(record(synthetic())) == pytest.approx(0.026)


def test_ffn_forward_and_backward():
    assert ffn_ms.read(record(synthetic())) == pytest.approx(0.024)


def test_head_with_the_embedding():
    assert head_ms.read(record(synthetic())) == pytest.approx(0.012)


def test_optimizer():
    assert optimizer_ms.read(record(synthetic())) == pytest.approx(0.010)


def test_scan_counts_the_layer_stack_outside_its_blocks():
    assert scan_ms.read(record(synthetic())) == pytest.approx(0.004)
    tr = synthetic()      # the scan's while spans its body: not counted
    tr.ops += [T.Op(d, "%while.11", t * US, (t + 84) * US,
                    "jit(step)/jvp(model/layers)/while")
               for d in (0, 1) for t in (0, 100)]
    assert scan_ms.read(record(tr)) == pytest.approx(0.004)


def test_layers_and_exchange_cover_the_busy_time():
    rec = record(synthetic())
    tr = rec.trace
    layers = sum(m.read(rec) for m in (attention_ms, ffn_ms, head_ms,
                                       optimizer_ms, exchange_ms, scan_ms))
    busy = T.busy(tr, 1, T.window(tr, 1)) / 2 * 1e3
    assert layers == pytest.approx(busy) == pytest.approx(0.084)


def test_a_program_without_scopes_reads_nothing():
    tr = synthetic(scoped=False)
    for m in READERS:
        assert m.read(record(tr)) is None, m.__name__
        assert m.read(record(None)) is None, m.__name__
