"""ZeRO-1 sharded optimizer state: config validation, plan accounting
for the fused RS + param-allgather schedule, flat-shard AdamW identity,
per-worker memory bounds, shard-aware checkpointing, and the 8-worker
bitwise-identity + mid-run-resume contracts (subprocesses on 8 emulated
CPU workers, like test_exchange_state.py)."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import restore_checkpoint, save_checkpoint
from repro.core import DistributedOptimizer, ExchangeConfig, compile_plan
from repro.optim import adamw, apply_updates, sgd_momentum
from repro.optim import zero1 as z1

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
    rng = np.random.default_rng(0)
    return {"a": jnp.asarray(rng.standard_normal((12, 8)), jnp.float32),
            "b": jnp.asarray(rng.standard_normal(37), jnp.float32)}


def _params(seed=1):
    rng = np.random.default_rng(seed)
    return {"a": jnp.asarray(rng.standard_normal((12, 8)), jnp.float32),
            "b": jnp.asarray(rng.standard_normal(37), jnp.float32)}


# ---------------------------------------------------------------------------
# config validation
# ---------------------------------------------------------------------------

def test_zero1_config_rules():
    cfg = ExchangeConfig(zero1=True)
    assert cfg.zero1 and cfg.param_codec == "identity"
    with pytest.raises(ValueError, match="subsumes"):
        ExchangeConfig(zero1=True, reduce_scatter=True)
    with pytest.raises(ValueError, match="hierarchical"):
        ExchangeConfig(zero1=True, backend="hierarchical")
    with pytest.raises(ValueError, match="overlap"):
        ExchangeConfig(zero1=True, overlap="backward")
    # staged overlap is fine (the zero1 schedule is itself staged)
    ExchangeConfig(zero1=True, overlap="staged")
    with pytest.raises(ValueError, match="param_codec"):
        ExchangeConfig(param_codec="bf16")       # needs zero1=True
    with pytest.raises(ValueError, match="stateful"):
        ExchangeConfig(zero1=True, param_codec="int8+ef")


def test_zero1_requires_flat_optimizer():
    opt = DistributedOptimizer(sgd_momentum(),
                               exchange=ExchangeConfig(zero1=True))
    with pytest.raises(ValueError, match="flat"):
        opt.init_zero1_state(_grads(), _params())


def test_zero1_plans_refuse_plain_exchange():
    opt = DistributedOptimizer(adamw(1e-2),
                               exchange=ExchangeConfig(zero1=True))
    with pytest.raises(ValueError, match="zero1"):
        opt.exchange(_grads())


# ---------------------------------------------------------------------------
# plan accounting: fused RS + param-AG stages
# ---------------------------------------------------------------------------

def test_zero1_wire_equals_allreduce():
    """Linear-codec zero1 wire (RS + param AG) must exactly equal the
    replicated reduce-scatter plan's (same padded RS+AG pattern), and
    the allreduce plan's up to bucket padding."""
    g = _grads()
    plan_z = compile_plan(g, ExchangeConfig(sparse_as_dense=True,
                                            zero1=True))
    plan_rs = compile_plan(g, ExchangeConfig(sparse_as_dense=True,
                                             reduce_scatter=True))
    plan_r = compile_plan(g, ExchangeConfig(sparse_as_dense=True))
    n_dense = len(plan_z.dense_buckets)
    for p in (2, 4, 8):
        assert plan_z.wire_bytes(p) == plan_rs.wire_bytes(p)
        # allreduce bills the unpadded buckets: equal within the
        # padding slack of < P elements per bucket
        slack = n_dense * p * 4 * 2
        assert 0 <= plan_z.wire_bytes(p) - plan_r.wire_bytes(p) <= slack
    # one RS + one AG per dense stage; the replicated plan runs one AR
    assert plan_z.n_collectives == 2 * plan_r.n_collectives
    assert plan_z.hlo_collectives(8) == 2 * plan_r.hlo_collectives(8)


def test_zero1_quantised_grad_keeps_values_and_scales():
    g = _grads()
    plan = compile_plan(g, ExchangeConfig(sparse_as_dense=True,
                                          zero1=True, codec="int8"))
    for st in plan.schedule.stages:
        # int8 grad half: values + scales allgather; param half:
        # identity f32 allgather -> 3 collectives per dense stage
        assert plan.stage_collectives(st) == 3
    ref = compile_plan(g, ExchangeConfig(sparse_as_dense=True,
                                         codec="int8"))
    for st, sr in zip(plan.schedule.stages, ref.schedule.stages):
        grad_wire = ref.stage_hop_wire_bytes(sr, 8)
        both = plan.stage_hop_wire_bytes(st, 8)
        param_wire = tuple(b - r for b, r in zip(both, grad_wire))
        shard = plan.zero1_shard_elems(st, 8)
        assert param_wire == (7 * shard * 4,)    # (P-1) f32 shard hops


def test_zero1_single_worker_moves_nothing():
    plan = compile_plan(_grads(), ExchangeConfig(sparse_as_dense=True,
                                                 zero1=True))
    assert plan.wire_bytes(1) == 0


def test_zero1_stats_report_memory():
    opt = DistributedOptimizer(adamw(1e-2),
                               exchange=ExchangeConfig(zero1=True))
    stats = opt.exchange_stats(_grads(), 8, profile=None)
    assert "+zero1" in stats.strategy
    assert stats.zero1 and stats.opt_state_bytes > 0
    assert "memory/worker:" in stats.describe()
    repl = DistributedOptimizer(adamw(1e-2)).exchange_stats(
        _grads(), 8, profile=None)
    assert not repl.zero1
    assert stats.opt_state_bytes < repl.opt_state_bytes


# ---------------------------------------------------------------------------
# per-worker optimizer-state memory: the 1/P bound
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("state_dtype,frac", [("float32", 1.0),
                                              ("bfloat16", 0.5)])
def test_zero1_state_bytes_one_over_p(state_dtype, frac):
    plan = compile_plan(_grads(), ExchangeConfig(sparse_as_dense=True,
                                                 zero1=True))
    p = 8
    repl = z1.optimizer_state_bytes(plan, p, "float32", zero1=False)
    shard = z1.optimizer_state_bytes(plan, p, state_dtype)
    n_dense = sum(1 for s in plan.schedule.stages if s.kind == "dense")
    slack = n_dense * p * 8 + 8                  # padding + step counter
    assert shard <= repl * frac / p + slack
    # the concrete state matches the static accounting
    state = z1.init_state(plan, adamw(1e-2, state_dtype=state_dtype),
                          _params(), n_workers=p)
    nbytes = 4 + sum(a.size * a.dtype.itemsize
                     for a in jax.tree_util.tree_leaves(
                         state._replace(step=()))) // p
    assert nbytes == shard


def test_zero1_lossy_param_codec_stores_master():
    plan = compile_plan(_grads(), ExchangeConfig(
        sparse_as_dense=True, zero1=True, codec="int8",
        param_codec="bf16"))
    state = z1.init_state(plan, adamw(1e-2), _params(), n_workers=4)
    assert all(not isinstance(s, tuple) for s in state.param_shards)
    lossless = compile_plan(_grads(), ExchangeConfig(
        sparse_as_dense=True, zero1=True))
    state0 = z1.init_state(lossless, adamw(1e-2), _params(), n_workers=4)
    assert all(isinstance(s, tuple) for s in state0.param_shards)
    assert z1.optimizer_state_bytes(plan, 4) > \
        z1.optimizer_state_bytes(lossless, 4)


# ---------------------------------------------------------------------------
# flat-shard AdamW: same math as the tree update
# ---------------------------------------------------------------------------

def test_adamw_flat_update_matches_tree_update():
    base = adamw(lr=3e-3, weight_decay=0.01)
    g, p = _grads()["a"].reshape(-1), _params()["a"].reshape(-1)
    state = base.init(p)
    upd, state = base.update(g, state, p)
    tree_p = apply_updates(p, upd)
    flat_state = base.flat_init(p.size)
    flat_p, flat_state = base.flat_update(g, flat_state, p,
                                          jnp.int32(1))
    np.testing.assert_array_equal(np.asarray(tree_p),
                                  np.asarray(flat_p))
    np.testing.assert_array_equal(np.asarray(state.mu),
                                  np.asarray(flat_state[0]))


def test_adamw_bf16_state_dtype_storage():
    base = adamw(1e-3, state_dtype="bfloat16")
    assert base.state_dtype == "bfloat16"
    st = base.init({"w": jnp.ones(4)})
    assert st.mu["w"].dtype == jnp.bfloat16
    m, v = base.flat_init(6)
    assert m.dtype == jnp.bfloat16 and v.dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# single-device zero1 == replicated (no mesh required)
# ---------------------------------------------------------------------------

def test_zero1_step_single_device_bitwise():
    g, params = _grads(), _params()
    base = adamw(lr=1e-2, weight_decay=0.01)
    opt = DistributedOptimizer(base, exchange=ExchangeConfig(zero1=True))
    z = opt.init_zero1_state(g, params)
    pz, z, _ = opt.zero1_step(g, params, z)
    pz, z, _ = opt.zero1_step(g, pz, z)

    ref = DistributedOptimizer(base, exchange=ExchangeConfig())
    st, pr = base.init(params), params
    for _ in range(2):
        upd, st = base.update(ref.exchange(g), st, pr)
        pr = apply_updates(pr, upd)
    for k in params:
        np.testing.assert_array_equal(np.asarray(pz[k]),
                                      np.asarray(pr[k]))
    assert int(z.step) == 2


# ---------------------------------------------------------------------------
# shard-aware checkpointing
# ---------------------------------------------------------------------------

def test_zero1_checkpoint_roundtrip_same_mesh(tmp_path):
    plan = compile_plan(_grads(), ExchangeConfig(sparse_as_dense=True,
                                                 zero1=True))
    base = adamw(1e-2)
    state = z1.init_state(plan, base, _params(), n_workers=8)
    state = state._replace(step=jnp.int32(5))
    save_checkpoint(str(tmp_path), 5, state)
    like = z1.init_state(plan, base, _params(), n_workers=8)
    restored, step = restore_checkpoint(str(tmp_path), like)
    assert step == 5 and int(restored.step) == 5
    for a, b in zip(jax.tree_util.tree_leaves(state),
                    jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    z1.check_state(plan, restored, 8)


def test_zero1_checkpoint_mesh_resize_fails_clearly(tmp_path):
    # a 41-element leaf pads to 48 on 8 workers but 44 on 4, so both
    # the plan-level and checkpoint-level guards have to fire
    g41 = {"w": jnp.ones((41,), jnp.float32)}
    plan = compile_plan(g41, ExchangeConfig(sparse_as_dense=True,
                                            zero1=True))
    base = adamw(1e-2)
    state8 = z1.init_state(plan, base, g41, n_workers=8)
    # the plan-level guard: validating an 8-way local shard against a
    # 4-worker mesh names the re-partitioning problem
    local = jax.tree_util.tree_map(
        lambda a: a[: a.shape[0] // 8] if np.ndim(a) else a, state8)
    with pytest.raises(ValueError, match="mesh"):
        z1.check_state(plan, local, 4)
    # the checkpoint-level guard: restoring into a different mesh's
    # template points at the ZeRO-1 shard, not a bare shape mismatch
    save_checkpoint(str(tmp_path), 1, state8)
    like4 = z1.init_state(plan, base, g41, n_workers=4)
    with pytest.raises(ValueError, match="ZeRO-1"):
        restore_checkpoint(str(tmp_path), like4)


# ---------------------------------------------------------------------------
# 8 emulated workers: bitwise identity + mid-run checkpoint resume
# ---------------------------------------------------------------------------

_WORKER_PRELUDE = r"""
import functools
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map
from repro.core import DistributedOptimizer, ExchangeConfig
from repro.optim import adamw, apply_updates
from repro.optim import zero1 as z1

mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
params = {"a": jax.random.normal(jax.random.PRNGKey(0), (16, 8)),
          "b": jax.random.normal(jax.random.PRNGKey(1), (37,))}
ga = jax.random.normal(jax.random.PRNGKey(2), (8, 16, 8))
gb = jax.random.normal(jax.random.PRNGKey(3), (8, 37))
base = adamw(lr=1e-2, weight_decay=0.01)
gabs = {"a": jax.ShapeDtypeStruct((16, 8), jnp.float32),
        "b": jax.ShapeDtypeStruct((37,), jnp.float32)}


def make_zero1(cfg):
    opt = DistributedOptimizer(base, exchange=cfg, axis_name="data")
    plan = opt.plan(gabs)
    z0 = opt.init_zero1_state(gabs, params, n_workers=8)
    zspec = z1.state_specs(plan, z0, "data")
    ex0 = (opt.init_exchange_state(gabs, n_workers=8)
           if opt.stateful else None)
    if ex0 is None:
        @functools.partial(shard_map, mesh=mesh,
            in_specs=(P(), zspec, (P("data"), P("data"))),
            out_specs=(P(), zspec), check_vma=False)
        def step(p, z, g):
            gg = {"a": g[0][0], "b": g[1][0]}
            np_, nz, _ = opt.zero1_step(gg, p, z)
            return np_, nz
        return step, z0, None
    exspec = jax.tree_util.tree_map(lambda _: P("data"), ex0)
    @functools.partial(shard_map, mesh=mesh,
        in_specs=(P(), zspec, exspec, (P("data"), P("data"))),
        out_specs=(P(), zspec, exspec), check_vma=False)
    def step(p, z, e, g):
        gg = {"a": g[0][0], "b": g[1][0]}
        return opt.zero1_step(gg, p, z, exchange_state=e)
    return step, z0, ex0


def run_replicated(cfg, steps):
    opt = DistributedOptimizer(base, exchange=cfg, axis_name="data")
    ex0 = (opt.init_exchange_state(gabs, n_workers=8)
           if opt.stateful else None)
    st, pcur = base.init(params), params
    if ex0 is None:
        @functools.partial(shard_map, mesh=mesh,
            in_specs=(P(), (P("data"), P("data"))), out_specs=P(),
            check_vma=False)
        def ex_fn(p, g):
            return opt.exchange({"a": g[0][0], "b": g[1][0]})
        for _ in range(steps):
            upd, st = base.update(ex_fn(pcur, (ga, gb)), st, pcur)
            pcur = apply_updates(pcur, upd)
        return pcur
    exspec = jax.tree_util.tree_map(lambda _: P("data"), ex0)
    @functools.partial(shard_map, mesh=mesh,
        in_specs=(P(), exspec, (P("data"), P("data"))),
        out_specs=(P(), exspec), check_vma=False)
    def ex_fn(p, e, g):
        return opt.exchange({"a": g[0][0], "b": g[1][0]}, state=e)
    ecur = ex0
    for _ in range(steps):
        dense, ecur = ex_fn(pcur, ecur, (ga, gb))
        upd, st = base.update(dense, st, pcur)
        pcur = apply_updates(pcur, upd)
    return pcur
"""


def test_zero1_bitwise_identity_8workers():
    code = _WORKER_PRELUDE + r"""
for kw in (dict(), dict(codec="bf16"), dict(codec="int8"),
           dict(codec="int8", error_feedback=True)):
    step, z, ex = make_zero1(ExchangeConfig(zero1=True, **kw))
    pz = params
    for _ in range(3):
        if ex is None:
            pz, z = step(pz, z, (ga, gb))
        else:
            pz, z, ex = step(pz, z, ex, (ga, gb))
    pr = run_replicated(ExchangeConfig(**kw), 3)
    for k in params:
        assert bool(jnp.array_equal(pz[k], pr[k])), (kw, k)
print("OK")
"""
    assert "OK" in run_with_devices(code)


def test_zero1_checkpoint_resume_midrun_8workers(tmp_path):
    # 4 uninterrupted steps vs save-at-2 / restore / 2 more — bitwise,
    # with the int8+ef codec state riding the checkpoint alongside the
    # sharded Zero1State
    code = _WORKER_PRELUDE + r"""
import os
from repro.checkpoint import restore_checkpoint, save_checkpoint

ckdir = os.environ["CKPT_DIR"]

cfg = ExchangeConfig(zero1=True, codec="int8", error_feedback=True)
step, z0, ex0 = make_zero1(cfg)

pz, z, ex = params, z0, ex0
for _ in range(4):
    pz, z, ex = step(pz, z, ex, (ga, gb))

pc, zc, ec = params, z0, ex0
for _ in range(2):
    pc, zc, ec = step(pc, zc, ec, (ga, gb))
save_checkpoint(ckdir, 2, (pc, zc, ec))
(pc, zc, ec), s = restore_checkpoint(ckdir, (pc, zc, ec))
assert s == 2
for _ in range(2):
    pc, zc, ec = step(pc, zc, ec, (ga, gb))

for k in params:
    assert bool(jnp.array_equal(pz[k], pc[k])), k
for a, b in zip(jax.tree_util.tree_leaves(z),
                jax.tree_util.tree_leaves(zc)):
    assert bool(jnp.array_equal(a, b))
print("OK")
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["CKPT_DIR"] = str(tmp_path)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "OK" in out.stdout


def test_zero1_audit_exact_8workers():
    code = r"""
from repro.launch.dryrun import audit_exchange_plan
for kw in (dict(), dict(codec="int8")):
    r = audit_exchange_plan(arch="transformer-big", n_workers=8,
                            reduced=True, zero1=True, **kw)
    assert r["counts_match"], (kw, r["hlo_counts"], r["planned_hlo_ops"])
    assert r["wire_ratio"] == 1.0, (kw, r["wire_ratio"])
print("OK")
"""
    assert "OK" in run_with_devices(code)
