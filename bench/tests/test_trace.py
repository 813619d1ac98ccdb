"""The reduction from trace to metrics, on a small synthetic trace."""
import types

import pytest

from bench import trace as T
from bench.metrics import (collective_exposed_ms, densify_roofline,
                           device_idle_share, exchange_ms)

US = 1000     # ns


def synthetic():
    """Two devices, two steps each of 100 us (module events).

    Device 0, per step at offset t: matmul [t, t+40), exchange pack
    scatter [t+40, t+50), all-reduce [t+50, t+70) of which [t+50, t+60)
    overlaps a fusion [t+45, t+60), unpack [t+70, t+80); idle [t+80,
    t+100).  Device 1 is the same shifted by 5 us with no overlap
    fusion."""
    ops, mods = [], []
    for dev, shift in ((0, 0), (1, 5)):
        for step in range(2):
            t = (step * 100 + shift) * US
            mods.append(T.Op(dev, "jit_step", t, t + 100 * US))
            ops += [
                T.Op(dev, "fusion.1", t, t + 40 * US, "jit(step)/layers"),
                T.Op(dev, "scatter.7", t + 40 * US, t + 50 * US,
                     "jit(step)/exchange/s00/allreduce/bucket=dense0/pack/"
                     "scatter-add"),
                T.Op(dev, "all-reduce.3", t + 50 * US, t + 70 * US,
                     "jit(step)/exchange/s00/allreduce/bucket=dense0"),
                T.Op(dev, "copy.2", t + 70 * US, t + 80 * US,
                     "jit(step)/exchange/s00/allreduce/bucket=dense0/unpack"),
            ]
            if dev == 0:
                ops.append(T.Op(dev, "fusion.9", t + 45 * US, t + 60 * US,
                                "jit(step)/adam"))
    spans = [T.Span("bench/traced_window", 0, 300 * US),
             T.Span("bench/fetch", 85 * US, 95 * US)]
    return T.Trace(ops, spans, mods)


def record(tr, steps=2):
    return types.SimpleNamespace(
        trace=tr, trace_steps=steps, densify_bytes=819e9 * 5e-6,
        peaks={"hbm_bytes_per_s": 819e9})


def test_busy_and_idle_share():
    tr = synthetic()
    w0 = T.window(tr, 0)
    assert w0 == (0, 200 * US)
    assert T.busy(tr, 0, w0) == pytest.approx(160e-6)
    busy, win = T.device_seconds(tr)
    assert (busy, win) == (pytest.approx(160e-6), pytest.approx(200e-6))
    assert device_idle_share.read(record(tr)) == pytest.approx(20.0)


def test_exchange_scope_time_per_step():
    # pack 10 + all-reduce 20 + unpack 10 = 40 us per step
    assert exchange_ms.read(record(synthetic())) == pytest.approx(0.040)


def test_exposed_collective_on_the_worst_device():
    tr = synthetic()
    w = T.window(tr, 0)
    assert T.exposed(tr, 0, w, T.is_collective) == pytest.approx(2 * 10e-6)
    assert T.exposed(tr, 1, T.window(tr, 1), T.is_collective) == \
        pytest.approx(2 * 20e-6)


def test_exposed_is_none_without_collectives():
    tr = synthetic()
    tr.ops = [o for o in tr.ops if not T.is_collective(o)]
    assert T.exposed(tr, 0, T.window(tr, 0), T.is_collective) is None


def test_collective_exposed_ms_is_the_worst_chips_per_step():
    # device 0: 10 us of its 20 us all-reduce under no other op, device
    # 1: all 20 us; two steps
    assert collective_exposed_ms.read(record(synthetic())) == \
        pytest.approx(0.020)


def test_collective_exposed_ms_reads_only_the_exchange():
    tr = synthetic()
    tr.ops = [T.Op(o.device, o.name, o.start, o.end, "jit(step)/layers")
              if T.is_collective(o) else o for o in tr.ops]
    assert collective_exposed_ms.read(record(tr)) is None
    assert collective_exposed_ms.read(record(None)) is None


def test_densify_roofline():
    # least time 5 us over measured 10 us per step
    assert densify_roofline.read(record(synthetic())) == pytest.approx(50.0)
    tr = synthetic()
    tr.ops = [o for o in tr.ops if "scatter" not in o.name]
    assert densify_roofline.read(record(tr)) is None


def test_idle_gaps_are_labelled_by_the_innermost_host_span():
    gaps = T.idle_gaps(synthetic(), 0, (0, 200 * US))
    assert [g for g, _ in gaps] == ["bench/fetch"]
    assert gaps[0][1] == pytest.approx(20e-6)
    out = T.breakdown(synthetic())
    assert out["device_ops"][0][0] == "fusion.1 jit(step)/layers"
    assert out["device_ops"][0][1] == pytest.approx(80e-6)


def test_no_trace_reads_nothing():
    rec = record(None)
    for m in (densify_roofline, device_idle_share, exchange_ms):
        assert m.read(rec) is None


def test_an_enclosing_op_keeps_only_its_own_time():
    """A while loop's event spans its body's ops: it neither hides a
    collective that runs beside its body nor counts its body twice."""
    ops = [T.Op(0, "%while.1", 0, 100 * US, "jit(step)/while"),
           T.Op(0, "%fusion.2", 10 * US, 50 * US, "jit(step)/while/body"),
           T.Op(0, "%all-reduce.3", 60 * US, 80 * US, "jit(step)/exchange"),
           T.Op(0, "%fusion.4", 100 * US, 120 * US, "jit(step)/adam")]
    tr = T.Trace(ops, [], [])
    win = T.window(tr, 0)
    assert T.exposed(tr, 0, win, T.is_collective) == pytest.approx(20e-6)
    top = dict(T.top_ops(tr, [0], {0: win}))
    assert top["%while.1 jit(step)/while"] == pytest.approx(40e-6)
    assert top["%fusion.2 while/body"] == pytest.approx(40e-6)


def test_hlo_scopes():
    text = ("  %fusion.10 = bf16[8,4]{1,0} fusion(%a, %b), kind=kCustom, "
            "calls=%fc.10, metadata={op_name=\"jit(step)/exchange/s15/"
            "allreduce/bucket=dense0/pack/scatter-add\" stack_frame_id=1}\n"
            "  ROOT %tuple.3 = (f32[]) tuple(%x)\n")
    assert T.hlo_scopes(text) == {
        "%fusion.10": "jit(step)/exchange/s15/allreduce/bucket=dense0/"
                      "pack/scatter-add"}
