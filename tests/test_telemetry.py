"""Telemetry: stage annotation names, runtime wire counters vs the
plan's accounting, Chrome-trace validity + the trace_report round-trip,
metrics JSONL schema stability, and the disabled-path guarantees
(``hooks.tap`` is the identity, instrumentation adds zero collectives).

Multi-device cases run in subprocesses with 8 emulated CPU workers,
like test_exchange.py / test_wait_free.py."""
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import exchange
from repro.telemetry import hooks
from repro.telemetry import metrics as metrics_lib
from repro.telemetry import report as report_lib
from repro.telemetry import trace as trace_lib

jax.config.update("jax_platform_name", "cpu")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with_devices(code: str, n: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def _grads():
    return {"a": jnp.arange(1024, dtype=jnp.float32).reshape(32, 32),
            "b": jnp.ones((17,), jnp.float32),
            "c": jnp.ones((64, 8), jnp.float32)}


# ---------------------------------------------------------------------------
# Stage annotation names
# ---------------------------------------------------------------------------

def test_stage_names_match_schedule():
    """One name per schedule stage, in schedule order, carrying the
    same collective kind / bucket id / trigger ``describe_schedule``
    prints — the trace rows and the schedule table must agree."""
    plan = exchange.compile_plan(
        _grads(), exchange.ExchangeConfig(sparse_as_dense=True,
                                          codec="int8"))
    names = plan.stage_names()
    assert len(names) == plan.schedule.n_stages
    assert len(set(names)) == len(names)
    for k, (name, stage) in enumerate(zip(names, plan.schedule.stages)):
        m = re.match(r"exchange/s(\d+)/(\w+)/bucket=(dense|leaf)(\d+)",
                     name)
        assert m, name
        assert int(m.group(1)) == k
        assert int(m.group(4)) == stage.bucket_id
    # the schedule table mentions every bucket the names mention
    table = plan.describe_schedule(8)
    for name, stage in zip(names, plan.schedule.stages):
        assert f"bucket {stage.bucket_id}" in table


def test_stage_names_carry_trigger():
    cfg = exchange.ExchangeConfig(sparse_as_dense=True,
                                  overlap="backward")
    plan = exchange.compile_plan(
        {"embedding": jnp.ones((8, 4)), "layers": jnp.ones((64, 4))}, cfg)
    names = plan.stage_names()
    assert all("/trigger=" in n for n in names)


def test_stage_name_index_lookup():
    plan = exchange.compile_plan(
        _grads(), exchange.ExchangeConfig(sparse_as_dense=True))
    for k, stage in enumerate(plan.schedule.stages):
        assert plan.stage_name(stage) == plan.stage_name(stage, index=k)


# ---------------------------------------------------------------------------
# Hooks: disabled path is inert
# ---------------------------------------------------------------------------

def test_tap_identity_when_disabled():
    x = jnp.arange(4.0)
    assert hooks.tap("pack", x) is x
    assert hooks.tracer() is None
    assert hooks.wire_recorder() is None


def test_stage_scope_nesting():
    assert hooks.current_stage() is None
    with hooks.stage_scope("outer"):
        assert hooks.current_stage() == "outer"
        with hooks.stage_scope("inner"):
            assert hooks.current_stage() == "inner"
        assert hooks.current_stage() == "outer"
    assert hooks.current_stage() is None


def test_double_install_raises():
    rec = hooks.WireRecorder()
    hooks.install_wire_recorder(rec)
    try:
        with pytest.raises(RuntimeError):
            hooks.install_wire_recorder(hooks.WireRecorder())
    finally:
        hooks.clear_wire_recorder()


def test_disabled_instrumentation_adds_zero_collectives():
    """With no tracer/recorder installed (the default), the lowered
    exchange contains exactly the plan's collectives and no host
    callbacks — the named scopes are metadata only."""
    from repro.launch import hlo as hlo_lib

    code = r"""
import jax, numpy as np
jax.config.update("jax_platform_name", "cpu")
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map
from repro.core import exchange
from repro.launch import hlo as hlo_lib

g = {"a": jnp.ones((32, 32)), "b": jnp.ones((17,)),
     "c": jnp.ones((64, 8))}
plan = exchange.compile_plan(
    g, exchange.ExchangeConfig(sparse_as_dense=True, codec="int8"))
mesh = Mesh(np.array(jax.devices()), ("data",))
sm = shard_map(lambda gg: plan.execute(gg, "data"), mesh=mesh,
               in_specs=(P(),), out_specs=P(), check_vma=False)
txt = jax.jit(sm).lower(g).compile().as_text()
counts = hlo_lib.count_collectives(txt)
print("OPS", sum(counts.values()), plan.hlo_collectives(8))
print("CALLBACKS", txt.count("xla_python_cpu_callback"))
"""
    out = run_with_devices(code)
    ops = out.splitlines()[-2].split()
    assert ops[1] == ops[2], out
    assert out.splitlines()[-1] == "CALLBACKS 0", out


# ---------------------------------------------------------------------------
# Wire counters close the loop against the plan accounting
# ---------------------------------------------------------------------------

WIRE_CASES = [
    ("identity-fused", 'exchange.ExchangeConfig(sparse_as_dense=True)'),
    ("int8", 'exchange.ExchangeConfig(sparse_as_dense=True, codec="int8")'),
    ("rs-ag", 'exchange.ExchangeConfig(sparse_as_dense=True, '
              'reduce_scatter=True)'),
    ("ringsim", 'exchange.ExchangeConfig(sparse_as_dense=True, '
                'backend="ringsim")'),
    ("staged", 'exchange.ExchangeConfig(sparse_as_dense=True, '
               'codec="int8", overlap=True)'),
]


@pytest.mark.parametrize("label,cfg", WIRE_CASES)
def test_measured_wire_matches_plan(label, cfg):
    """``measure_wire`` (one abstract eval with the WireRecorder in)
    must bill exactly ``plan.stage_wire_bytes`` to every stage."""
    code = r"""
import jax, numpy as np
jax.config.update("jax_platform_name", "cpu")
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map
from repro.core import exchange
from repro.telemetry import trace as trace_lib

g = {"a": jnp.arange(1024, dtype=jnp.float32).reshape(32, 32),
     "b": jnp.ones((17,), jnp.float32), "c": jnp.ones((64, 8))}
plan = exchange.compile_plan(g, CFG)
mesh = Mesh(np.array(jax.devices()), ("data",))
sm = shard_map(lambda gg: plan.execute(gg, "data"), mesh=mesh,
               in_specs=(P(),), out_specs=P(), check_vma=False)
rec = trace_lib.measure_wire(sm, g)
got = rec.stage_wire_bytes()
names = plan.stage_names()
for n, s in zip(names, plan.schedule.stages):
    want = plan.stage_wire_bytes(s, 8)
    assert abs(got.get(n, 0) - want) < 1e-6, (n, got.get(n, 0), want)
assert rec.total_collectives() > 0
print("WIRE-OK", len(names))
""".replace("CFG", cfg)
    out = run_with_devices(code)
    assert "WIRE-OK" in out


def test_measured_wire_hierarchical():
    code = r"""
import jax, numpy as np
jax.config.update("jax_platform_name", "cpu")
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map
from repro.core import exchange
from repro.telemetry import trace as trace_lib

g = {"a": jnp.ones((32, 32)), "b": jnp.ones((17,))}
plan = exchange.compile_plan(g, exchange.ExchangeConfig(
    sparse_as_dense=True, backend="hierarchical", codec="int8"))
mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("pod", "data"))
sm = shard_map(lambda gg: plan.execute(gg, ("pod", "data")), mesh=mesh,
               in_specs=(P(),), out_specs=P(), check_vma=False)
rec = trace_lib.measure_wire(sm, g)
got = rec.stage_wire_bytes()
for n, s in zip(plan.stage_names(), plan.schedule.stages):
    want = plan.stage_wire_bytes(s, (2, 4))
    assert abs(got.get(n, 0) - want) < 1e-6, (n, got.get(n, 0), want)
print("WIRE-OK")
"""
    assert "WIRE-OK" in run_with_devices(code)


def test_measured_wire_zero1_and_stateful():
    """The recorder works under the other two step signatures: the
    fused ZeRO-1 step (grad RS + param AG billed to the same stage
    name) and the stateful (error-feedback) exchange."""
    code = r"""
import jax, numpy as np
jax.config.update("jax_platform_name", "cpu")
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map
from repro.core import exchange
from repro.optim import adamw, zero1 as z1
from repro.telemetry import trace as trace_lib

g = {"a": jnp.ones((40, 40)), "b": jnp.ones((33,))}
params = {"a": jnp.zeros((40, 40)), "b": jnp.zeros((33,))}
mesh = Mesh(np.array(jax.devices()), ("data",))

plan = exchange.compile_plan(g, exchange.ExchangeConfig(
    zero1=True, sparse_as_dense=True, param_codec="int8"))
base = adamw(1e-3)
zst = z1.init_state(plan, base, params, n_workers=8)
sm = shard_map(lambda gg, pp, zz: z1.zero1_step(plan, base, gg, pp, zz,
                                                "data")[0],
               mesh=mesh,
               in_specs=(P(), P(), z1.state_specs(plan, zst, "data")),
               out_specs=P(), check_vma=False)
rec = trace_lib.measure_wire(sm, g, params, zst)
got = rec.stage_wire_bytes()
for n, s in zip(plan.stage_names(), plan.schedule.stages):
    want = plan.stage_wire_bytes(s, 8)
    assert abs(got.get(n, 0) - want) < 1e-6, (n, got.get(n, 0), want)
print("ZERO1-OK")

plan2 = exchange.compile_plan(g, exchange.ExchangeConfig(
    sparse_as_dense=True, codec="int8", error_feedback=True))
st0 = plan2.init_state(n_workers=8)
sm2 = shard_map(lambda gg, ss: plan2.execute(gg, "data", state=ss),
                mesh=mesh, in_specs=(P(), P("data")),
                out_specs=(P(), P("data")), check_vma=False)
rec2 = trace_lib.measure_wire(sm2, g, st0)
got2 = rec2.stage_wire_bytes()
for n, s in zip(plan2.stage_names(), plan2.schedule.stages):
    want = plan2.stage_wire_bytes(s, 8)
    assert abs(got2.get(n, 0) - want) < 1e-6, (n, got2.get(n, 0), want)
print("STATEFUL-OK")
"""
    out = run_with_devices(code)
    assert "ZERO1-OK" in out and "STATEFUL-OK" in out


# ---------------------------------------------------------------------------
# Trace capture: Chrome validity, bitwise identity, report round-trip
# ---------------------------------------------------------------------------

def test_capture_trace_valid_and_bitwise(tmp_path):
    """An instrumented capture (a) produces a Chrome trace with one row
    set per schedule stage and wire exactly matching the plan, and (b)
    returns outputs BITWISE identical to the untraced execution — taps
    are identity ops."""
    out_json = tmp_path / "trace.json"
    code = r"""
import jax, numpy as np, json
jax.config.update("jax_platform_name", "cpu")
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map
from repro.core import exchange
from repro.telemetry import trace as trace_lib

g = {"a": jnp.arange(1024, dtype=jnp.float32).reshape(32, 32),
     "b": jnp.ones((17,), jnp.float32)}
plan = exchange.compile_plan(g, exchange.ExchangeConfig(
    sparse_as_dense=True, codec="int8", overlap=True))
mesh = Mesh(np.array(jax.devices()), ("data",))
sm = shard_map(lambda gg: plan.execute(gg, "data"), mesh=mesh,
               in_specs=(P(),), out_specs=P(), check_vma=False)
base = jax.jit(sm)(g)
trace = trace_lib.capture_exchange_trace(
    plan, sm, (g,), ("data",), 8, out_path=OUT)
traced_out = trace_lib.StepTracer(("data",)).capture(sm, g)
for x, y in zip(jax.tree_util.tree_leaves(base),
                jax.tree_util.tree_leaves(traced_out)):
    assert x.dtype == y.dtype and bool(jnp.array_equal(x, y))
after = jax.jit(sm)(g)
for x, y in zip(jax.tree_util.tree_leaves(base),
                jax.tree_util.tree_leaves(after)):
    assert bool(jnp.array_equal(x, y))
print("BITWISE-OK")
""".replace("OUT", repr(str(out_json)))
    out = run_with_devices(code)
    assert "BITWISE-OK" in out

    trace = report_lib.load_trace(str(out_json))
    assert trace["otherData"]["schema"] == trace_lib.TRACE_SCHEMA
    names = trace["otherData"]["stage_names"]
    evs = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    for e in evs:   # structurally valid Chrome events
        assert {"name", "pid", "tid", "ts", "dur"} <= set(e)
        assert e["dur"] >= 0
    stages_seen = {e["args"]["stage"] for e in evs
                   if e.get("cat") == "exchange"}
    assert stages_seen == set(names)
    collected = {e["args"]["stage"] for e in evs
                 if e.get("cat") == "exchange"
                 and e["name"] == "collective"}
    assert collected == set(names)

    rows = report_lib.predicted_vs_measured(trace)
    assert [r["stage"] for r in rows] == names
    assert report_lib.wire_exact(rows)
    summary = report_lib.summarize_trace(trace)
    assert summary["n_workers_traced"] == 8
    assert set(summary["stages"]) == set(names)


def test_trace_report_cli(tmp_path):
    """scripts/trace_report.py round-trips a synthetic trace."""
    events = [{"stage": "exchange/s00/allreduce/bucket=dense0",
               "phase": ph, "worker": w, "t": 0.001 * (k + 1)}
              for w in (0, 1)
              for k, ph in enumerate(trace_lib.PHASES)]
    trace = trace_lib.chrome_trace(
        events, ["exchange/s00/allreduce/bucket=dense0"],
        [{"t_start": 0.0, "t_end": 0.01}],
        meta={"planned_wire_bytes":
              {"exchange/s00/allreduce/bucket=dense0": 100},
              "measured_wire_bytes":
              {"exchange/s00/allreduce/bucket=dense0": 100},
              "predicted_us":
              {"exchange/s00/allreduce/bucket=dense0": 123.0}})
    path = tmp_path / "t.json"
    trace_lib.write_trace(trace, str(path))

    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "trace_report.py"),
         str(path), "--json"],
        env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    d = json.loads(out.stdout)
    assert d["n_stages"] == 1 and d["wire_exact"] is True
    assert d["rows"][0]["predicted_us"] == 123.0
    assert d["rows"][0]["measured_us"] > 0


def test_exposed_hidden_split():
    """Interval arithmetic: a collective fully covered by compute
    slices is hidden; an uncovered one is exposed."""
    name = "exchange/s00/allreduce/bucket=dense0"
    other = "exchange/s01/allreduce/bucket=dense1"
    # stage s00's collective spans [0, 3ms]; stage s01's pack (a
    # compute slice on another row) spans [0, 4ms] and covers it fully
    events = [
        {"stage": name, "phase": "collective", "worker": 0, "t": 0.003},
        {"stage": other, "phase": "pack", "worker": 0, "t": 0.004},
    ]
    trace = trace_lib.chrome_trace(events, [name, other],
                                   [{"t_start": 0.0, "t_end": 0.005}])
    s = report_lib.summarize_trace(trace)["stages"][name]
    assert s["hidden_us"] == pytest.approx(s["collective_us"])
    assert s["exposed_us"] == pytest.approx(0.0)

    events2 = [{"stage": name, "phase": "collective", "worker": 0,
                "t": 0.003}]
    trace2 = trace_lib.chrome_trace(events2, [name],
                                    [{"t_start": 0.0, "t_end": 0.005}])
    s2 = report_lib.summarize_trace(trace2)["stages"][name]
    assert s2["exposed_us"] == pytest.approx(s2["collective_us"])


# ---------------------------------------------------------------------------
# Metrics: JSONL schema, StepRecorder, histograms
# ---------------------------------------------------------------------------

def test_metrics_jsonl_schema(tmp_path):
    path = tmp_path / "m.jsonl"
    rec = metrics_lib.StepRecorder(metrics_lib.MetricsLogger(str(path)),
                                   tokens_per_step=128)
    for i in range(3):
        rec.step_start()
        rec.data_loaded()
        rec.step_end({"loss": 1.0 - 0.1 * i,
                      "overflow": np.bool_(i == 1)})
    rows = rec.flush()
    assert len(rows) == 3
    rec.close()

    lines = [json.loads(x) for x in path.read_text().splitlines() if x]
    assert all(r["schema"] == metrics_lib.SCHEMA for r in lines)
    kinds = [r["kind"] for r in lines]
    assert kinds.count("step") == 3 and kinds[-1] == "summary"
    step0 = next(r for r in lines if r["kind"] == "step")
    for k in ("step", "step_ms", "data_ms", "compute_ms", "tok_s",
              "loss"):
        assert k in step0, step0
    assert lines[-1]["counters"]["overflow_skipped_steps"] == 1

    s = report_lib.summarize_metrics_jsonl(str(path))
    assert s["n_steps"] == 3
    assert s["final_loss"] == pytest.approx(0.8)
    assert s["counters"]["overflow_skipped_steps"] == 1


def test_recorder_defers_device_values():
    """step_end must not force a host sync: device arrays are held
    as-is until flush()."""
    rec = metrics_lib.StepRecorder()
    rec.step_start()
    rec.step_end({"loss": jnp.float32(2.5)})
    assert rec.rows == []             # nothing converted yet
    rows = rec.flush()
    assert rows[0]["loss"] == pytest.approx(2.5)


def test_latency_histogram_percentiles():
    h = metrics_lib.LatencyHistogram("x", max_samples=100)
    for i in range(1, 101):
        h.observe(i / 1000.0)
    s = h.summary()
    assert s["count"] == 100
    assert s["p50_ms"] == pytest.approx(51.0, abs=2.0)
    assert s["p99_ms"] == pytest.approx(100.0, abs=2.0)
    # decimating reservoir keeps going past max_samples
    for i in range(200):
        h.observe(0.5)
    assert h.summary()["count"] == 300


def test_serving_latency_histograms():
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving import ServeEngine

    cfg = get_config("transformer-big").reduced()
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    logger = metrics_lib.MetricsLogger()
    eng = ServeEngine(m, params, cache_len=32, metrics=logger)
    out = eng.generate(np.ones((2, 4), np.int32), max_new=4)
    assert out.shape[0] == 2
    summ = eng.latency_summary()
    assert summ["serve/prefill"]["count"] == 1
    assert summ["serve/decode_token"]["count"] >= 1
    assert summ["serve/decode_token"]["p99_ms"] > 0
    assert logger.counter("serve/requests").value == 2


# ---------------------------------------------------------------------------
# Trainer integration
# ---------------------------------------------------------------------------

def test_trainer_records_history_and_metrics(tmp_path):
    from repro.configs import get_config
    from repro.core import DistributedOptimizer
    from repro.data import make_pipeline
    from repro.models import build_model
    from repro.optim import adamw
    from repro.training.train_step import make_train_step
    from repro.training.trainer import Trainer, TrainerConfig

    cfg = get_config("transformer-big").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = DistributedOptimizer(adamw(1e-3), axis_name=None)
    step_fn = make_train_step(model, opt)
    opt_state = opt.init(params)
    pipe = make_pipeline(cfg, 2, 8)
    path = tmp_path / "m.jsonl"
    rec = metrics_lib.StepRecorder(metrics_lib.MetricsLogger(str(path)),
                                   tokens_per_step=16)
    tr = Trainer(model, step_fn, pipe,
                 TrainerConfig(total_steps=4, log_every=2), recorder=rec)
    res = tr.run(params, opt_state, log=lambda s: None)
    rec.close()
    assert len(res["history"]) == 2
    assert all("data_ms" in h and "overflow_skipped" in h
               for h in res["history"])
    lines = [json.loads(x) for x in path.read_text().splitlines() if x]
    steps = [r for r in lines if r["kind"] == "step"]
    assert len(steps) == 4
    assert all("loss" in s and "compute_ms" in s for s in steps)
