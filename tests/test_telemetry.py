"""Telemetry: stage annotation names, runtime wire counters vs the
plan's accounting, the layer scopes in the compiled step, the Trainer's
host spans and the profiler capture of the real loop (bitwise-inert,
reported per layer and per stage by scripts/trace_report.py), metrics
JSONL schema stability, and the disabled-path guarantees (no host
callbacks, instrumentation adds zero collectives).

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
from repro.telemetry.trace import Op, Profile, Span

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
# Layer scopes in the compiled step
# ---------------------------------------------------------------------------

def _reduced_model():
    from repro.configs import get_config
    from repro.models import build_model

    cfg = get_config("transformer-big").reduced()
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _exchange_opt():
    from repro.core import DistributedOptimizer, ExchangeConfig
    from repro.optim import adamw

    return DistributedOptimizer(adamw(1e-3), exchange=ExchangeConfig(
        sparse_as_dense=True))


@pytest.fixture(scope="module")
def step_hlo_paths():
    """op_name paths of the compiled reduced transformer-big step (dense
    autodiff of the embedding, so its lookup has a backward too)."""
    from repro.data import make_pipeline
    from repro.training import make_train_step

    cfg, model, params = _reduced_model()
    opt = _exchange_opt()
    step = make_train_step(model, opt)
    batch = {k: jnp.asarray(v)
             for k, v in make_pipeline(cfg, 2, 16).batch_at(0).items()}
    txt = jax.jit(step).lower(params, opt.init(params),
                              batch).compile().as_text()
    return re.findall(r'op_name="([^"]*)"', txt)


@pytest.mark.parametrize("scope", hooks.LAYER_SCOPES)
def test_layer_scope_in_compiled_step(step_hlo_paths, scope):
    """Each layer scope names ops of the compiled step, forward and
    differentiated (``transpose(...)``); the optimizer is never
    differentiated."""
    mine = [p for p in step_hlo_paths if hooks.in_scope(p, scope)]
    forward = [p for p in mine if "transpose(" not in p]
    backward = [p for p in mine if "transpose(" in p]
    assert forward, scope
    if scope == hooks.OPTIM:
        assert not backward, backward[:3]
    else:
        assert backward, scope
    others = [s for s in hooks.LAYER_SCOPES if s != scope]
    assert not any(scope in s for s in others)   # no name inside another


@pytest.mark.parametrize("scope", [hooks.SELF_ATTN, hooks.CROSS_ATTN])
def test_one_block_backward_in_attention_scope(step_hlo_paths, scope):
    """The one-block attention's recomputing backward (a custom_vjp, so
    not JAX's own transpose) keeps its layer's scope: the recomputed
    scores and the dq, dk and dv matmuls are counted in
    ``attention_ms``, and ``attention/one_block`` tells its share."""
    dots = [p for p in step_hlo_paths if hooks.in_scope(p, hooks.ONE_BLOCK)
            and p.endswith("dot_general")]
    assert all(hooks.in_scope(p, hooks.SELF_ATTN)
               or hooks.in_scope(p, hooks.CROSS_ATTN) for p in dots), dots
    mine = {p.rsplit("/", 2)[-2] for p in dots if hooks.in_scope(p, scope)
            and "transpose(" in p}
    # the backward's recomputed QK^T and dp = do V^T, dq, then dk and dv
    assert mine == {"bqhd,bkhd->bhqk", "bhqk,bkhd->bqhd",
                    "bhqk,bqhd->bkhd"}, mine
    assert not any(hooks.in_scope(p, hooks.KV_SCAN) for p in step_hlo_paths)


def test_step_has_no_host_callback():
    """With no profile directory (the default), the lowered step holds
    no host callback: spans and scopes add nothing to the program."""
    from repro.data import make_pipeline
    from repro.training import make_train_step

    cfg, model, params = _reduced_model()
    opt = _exchange_opt()
    step = make_train_step(model, opt, sparse_embedding=True)
    batch = {k: jnp.asarray(v)
             for k, v in make_pipeline(cfg, 2, 16).batch_at(0).items()}
    callback = re.compile(r"custom[_-]call\b[^\n]*callback")
    lowered = jax.jit(step).lower(params, opt.init(params), batch)
    for txt in (lowered.as_text(), lowered.compile().as_text()):
        assert not callback.search(txt), callback.search(txt).group(0)
    # the pattern finds a host callback where there is one
    from jax.experimental import io_callback
    tapped = jax.jit(lambda x: io_callback(lambda v: v, x, x)).lower(
        jnp.ones(3)).compile().as_text()
    assert callback.search(tapped)


# ---------------------------------------------------------------------------
# The profiler capture of the real loop
# ---------------------------------------------------------------------------

def _run_trainer(profile_dir, ckpt_dir, steps=3, log_every=1, profile_steps=2):
    """Reduced transformer-big through ``Trainer.run``; with a
    ``ckpt_dir`` every step logs and saves a checkpoint, so that it
    holds all five Trainer spans."""
    from repro.data import make_pipeline
    from repro.training import Trainer, TrainerConfig, make_train_step

    cfg, model, params = _reduced_model()
    opt = _exchange_opt()
    step = make_train_step(model, opt, sparse_embedding=True)
    tr = Trainer(model, step, make_pipeline(cfg, 2, 16), TrainerConfig(
        total_steps=steps, log_every=log_every,
        checkpoint_every=int(ckpt_dir is not None),
        checkpoint_dir=None if ckpt_dir is None else str(ckpt_dir),
        profile_dir=None if profile_dir is None else str(profile_dir),
        profile_steps=profile_steps))
    # ``run`` consumes the state it is given; the caller keeps ``params``
    res = tr.run(jax.tree_util.tree_map(jnp.copy, params), opt.init(params),
                 log=lambda s: None)
    return res, opt, step, params


@pytest.fixture(scope="module")
def profiled_run(tmp_path_factory):
    base = tmp_path_factory.mktemp("profiled")
    res, opt, step, params = _run_trainer(base / "trace", base / "ckpt")
    return base / "trace", res, opt, step, params


def test_trainer_spans_in_order_and_bitwise(profiled_run, tmp_path):
    """The profiled steps carry the five Trainer spans in order inside
    their step span, and the run's params equal, bit for bit, those of
    the same run without the profiler."""
    trace_dir, res, _, _, _ = profiled_run
    prof = trace_lib.load_profile(str(trace_dir))
    steps = sorted((s for s in prof.spans if s.name == hooks.STEP),
                   key=lambda s: s.start)
    assert len(steps) == 2
    for st in steps:
        inside = sorted((s for s in prof.spans
                         if s.name in hooks.TRAINER_SPANS
                         and st.start <= s.start and s.end <= st.end),
                        key=lambda s: s.start)
        assert [s.name for s in inside] == list(hooks.TRAINER_SPANS)
    assert (trace_dir / trace_lib.STEP_HLO).exists()
    assert list(trace_dir.glob("plugins/profile/*/perfetto_trace.json.gz"))

    plain, _, _, _ = _run_trainer(None, tmp_path / "ckpt")
    for x, y in zip(jax.tree_util.tree_leaves(res["params"]),
                    jax.tree_util.tree_leaves(plain["params"])):
        assert x.dtype == y.dtype and bool(jnp.array_equal(x, y))


def test_layer_scopes_cover_device_time(tmp_path):
    """The layer scopes plus the exchange's cover at least 90% of the
    device's busy time per step of the reduced step on the CPU: of the
    time its ops do work, in a run that syncs only at its end, as a
    training run does between log boundaries.  The loop control of an
    enclosing ``while`` (on the CPU backend, thread hand-offs between
    its body's ops, which stretch under load) is reported apart."""
    _run_trainer(tmp_path / "trace", None, steps=4, log_every=4)
    split = report_lib.layer_split(
        trace_lib.load_profile(str(tmp_path / "trace")))
    scoped = sum(v for k, v in split.items()
                 if k not in ("other", "loop", "idle"))
    assert split[hooks.FFN] > 0 and split[hooks.OPTIM] > 0
    assert split["loop"] >= 0
    assert scoped >= 0.9 * (scoped + split["other"]), split


def test_trace_report_cli(profiled_run):
    """scripts/trace_report.py reads a profile directory: one row per
    schedule stage with device time, the layer split, wire exact."""
    from repro.data import make_pipeline
    from repro.training.gradients import abstract_grad_contributions

    trace_dir, _, opt, step, params = profiled_run
    cfg, model, _ = _reduced_model()
    batch = {k: jnp.asarray(v)
             for k, v in make_pipeline(cfg, 2, 16).batch_at(0).items()}
    plan = opt.plan(abstract_grad_contributions(model, params, batch,
                                                sparse_embedding=True))
    wire = trace_lib.measure_wire(step, params, opt.init(params), batch)
    trace_lib.write_meta(trace_lib.plan_trace_meta(plan, 1, measured=wire),
                         str(trace_dir))

    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    script = os.path.join(REPO, "scripts", "trace_report.py")
    out = subprocess.run([sys.executable, script, str(trace_dir), "--json"],
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    d = json.loads(out.stdout)
    assert d["n_stages"] == len(d["stage_names"]) == len(d["rows"]) > 0
    assert d["wire_exact"] is True and d["n_workers_traced"] == 1
    assert d["n_steps_traced"] == 2
    assert set(hooks.LAYER_SCOPES) <= set(d["layers_ms"])
    assert set(d["idle_share_by_span"]) == set(hooks.TRAINER_SPANS)
    for r in d["rows"]:
        assert r["predicted_us"] is not None
        assert r["exposed_us"] + r["hidden_us"] == \
            pytest.approx(r["measured_us"])
    table = subprocess.run([sys.executable, script, str(trace_dir)],
                           env=env, capture_output=True, text=True,
                           timeout=300)
    assert table.returncode == 0 and "wire exact vs plan: True" in \
        table.stdout
    empty = subprocess.run([sys.executable, script, str(trace_dir / "nope")],
                           env=env, capture_output=True, text=True,
                           timeout=300)
    assert empty.returncode == 2


def test_capture_trace_valid_and_bitwise(tmp_path):
    """``train.py --trace-dir`` on 8 workers profiles the real loop's
    last steps: every schedule stage has device time on all 8 chips and
    wire exactly matching the plan, and the run's params are BITWISE
    those of the same run without the profile."""
    out_dir = tmp_path / "trace"
    code = r"""
import jax, numpy as np
jax.config.update("jax_platform_name", "cpu")
from repro.launch.train import train
argv = ["--arch", "transformer-big", "--reduced", "--dist", "horovod",
        "--overlap", "staged", "--codec", "int8", "--steps", "3",
        "--batch-per-worker", "1", "--seq-len", "16", "--log-every", "3"]
a = train(argv + ["--trace-dir", OUT])
b = train(argv)
for x, y in zip(jax.tree_util.tree_leaves(a["params"]),
                jax.tree_util.tree_leaves(b["params"])):
    assert x.dtype == y.dtype and np.array_equal(np.asarray(x),
                                                 np.asarray(y))
print("BITWISE-OK")
""".replace("OUT", repr(str(out_dir)))
    out = run_with_devices(code)
    assert "BITWISE-OK" in out

    s = report_lib.summarize_profile(str(out_dir))
    names = s["stage_names"]
    assert s["n_stages"] == len(names) > 0
    assert [r["stage"] for r in s["rows"]] == names
    assert s["wire_exact"] and s["n_workers_traced"] == 8
    assert s["n_steps_traced"] == 2 and s["mode"] == "staged"
    for r in s["rows"]:
        assert r["measured_us"] > 0, r["stage"]
    assert s["layers_ms"][hooks.EXCHANGE] > 0


def test_hlo_scopes_name_ops_without_metadata():
    """An op the compiler made without metadata takes its first
    operand's scope, else (a loop buffer's initial value) its first
    user's, through the tuple that feeds the loop."""
    text = """
  %constant.1 = f32[] constant(0)
  %broadcast.2 = f32[4]{0} broadcast(%constant.1), dimensions={}
  %copy.3 = f32[4]{0} copy(%broadcast.2)
  %add.4 = f32[4]{0} add(%p.0, %p.0), metadata={op_name="jit(step)/jvp(model/embed)/add"}
  %reduce-window.5 = f32[2]{0} reduce-window(%add.4, %constant.1), window={size=2}
  %tuple.6 = (f32[4]{0}, f32[4]{0}) tuple(%add.4, %copy.3)
  %while.7 = (f32[4]{0}, f32[4]{0}) while(%tuple.6), condition=%c, body=%b, metadata={op_name="jit(step)/jvp(model/layers)/while"}
"""
    scopes = trace_lib.hlo_scopes(text)
    assert scopes["%reduce-window.5"] == "jit(step)/jvp(model/embed)/add"
    assert scopes["%copy.3"] == "jit(step)/jvp(model/layers)/while"
    assert scopes["%tuple.6"] == "jit(step)/jvp(model/layers)/while"


def test_exposed_hidden_split():
    """Interval arithmetic on device ops: a stage op fully covered by an
    op outside the stage is hidden; an uncovered one is exposed; an
    enclosing op (a ``while``) hides nothing."""
    name = "exchange/s00/allreduce/bucket=dense0"
    other = "exchange/s01/allreduce/bucket=dense1"
    us = 1000
    steps = [Span(hooks.STEP, 0, 10 * us)]
    covered = [Op(0, "%all-reduce.1", 1 * us, 4 * us, f"jit(step)/{name}"),
               Op(0, "%fusion.2", 0, 2 * us, f"jit(step)/{other}/pack"),
               Op(0, "%fusion.5", 2 * us, 5 * us, "jit(step)/optim/update")]
    s = report_lib.stage_timings(Profile(covered, steps, {}),
                                 [name, other])[name]
    assert s["hidden_us"] == pytest.approx(s["collective_us"]) == \
        pytest.approx(3.0)
    assert s["exposed_us"] == pytest.approx(0.0)

    alone = [Op(0, "%while.3", 0, 9 * us, "jit(step)/while"),
             Op(0, "%all-reduce.1", 1 * us, 3 * us, f"jit(step)/{name}"),
             Op(0, "%fusion.4", 5 * us, 6 * us,
                "jit(step)/transpose(jvp(model/head))/dot")]
    prof = Profile(alone, steps, {})
    s2 = report_lib.stage_timings(prof, [name])[name]
    assert s2["exposed_us"] == pytest.approx(s2["collective_us"]) == \
        pytest.approx(2.0)
    split = report_lib.layer_split(prof)
    assert split[hooks.HEAD] == pytest.approx(1e-3)
    assert split["exchange"] == pytest.approx(2e-3)
    assert split["other"] == pytest.approx(0.0)
    assert split["loop"] == pytest.approx(6e-3)      # the while's own
    assert split["idle"] == pytest.approx(0.0)


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
