"""Stateful exchange API: ExchangeState threading, the zero-state
adapter's bitwise-identity contract, ErrorFeedback codecs, checkpoint
round-trip of codec state, and the hierarchical per-hop requantizing
reduction (accounting + lowered-HLO audits run in subprocesses on 8
emulated CPU workers, like test_exchange.py)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (DistributedOptimizer, ExchangeConfig, ExchangeState,
                        IndexedSlices, available_codecs, compile_plan,
                        get_codec)
from repro.core.codecs import ErrorFeedbackCodec
from repro.optim import adamw

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


def _tree(seed=0):
    rng = np.random.default_rng(seed)
    tree = {f"w{i}": jnp.asarray(rng.standard_normal((16 + i, 8)),
                                 jnp.float32) for i in range(4)}
    tree["emb"] = [IndexedSlices(
        jnp.asarray(rng.integers(0, 24, 6, dtype=np.int32)),
        jnp.asarray(rng.standard_normal((6, 8)), jnp.float32), (24, 8)),
        jnp.asarray(rng.standard_normal((24, 8)), jnp.float32)]
    return tree


# ---------------------------------------------------------------------------
# ExchangeState pytree + registry plumbing
# ---------------------------------------------------------------------------

def test_exchange_state_is_a_pytree():
    st = ExchangeState([(), jnp.zeros(4), ()])
    leaves, treedef = jax.tree_util.tree_flatten(st)
    assert len(leaves) == 1                      # empty tuples: no leaves
    rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
    assert isinstance(rebuilt, ExchangeState)
    assert rebuilt.n_stages == 3
    # flat keys for the checkpoint path
    with_paths = jax.tree_util.tree_flatten_with_path(st)[0]
    assert len(with_paths) == 1
    # jit round-trip
    doubled = jax.jit(lambda s: jax.tree_util.tree_map(lambda x: 2 * x,
                                                       s))(st)
    np.testing.assert_array_equal(np.asarray(doubled.bucket_states[1]),
                                  np.zeros(4))


def test_ef_registry_and_config_normalisation():
    # "+ef" names resolve (cached singleton), base registry is unchanged
    c1, c2 = get_codec("int8+ef"), get_codec("int8+ef")
    assert c1 is c2 and isinstance(c1, ErrorFeedbackCodec)
    assert c1.stateful and not c1.linear
    assert "int8+ef" not in available_codecs()   # suffix, not a new entry
    # error_feedback=True folds onto the suffixed codec name, so both
    # spellings compare/hash/cache identically
    assert ExchangeConfig(codec="int8", error_feedback=True) == \
        ExchangeConfig(codec="int8+ef")
    assert ExchangeConfig(codec="int8",
                          error_feedback=True).error_feedback is False
    # stacking feedback on feedback is rejected
    with pytest.raises(ValueError):
        get_codec("int8+ef+ef")
    # stateful codecs have no RS+AG path
    with pytest.raises(ValueError):
        ExchangeConfig(sparse_as_dense=True, codec="bf16+ef",
                       reduce_scatter=True)


def test_ef_wire_accounting_matches_inner_codec():
    """Error feedback changes state, never the wire: byte/collective
    accounting must equal the wrapped codec's exactly."""
    tree = _tree()
    for inner in ("int8", "bf16"):
        a = compile_plan(tree, ExchangeConfig(sparse_as_dense=True,
                                              codec=inner))
        b = compile_plan(tree, ExchangeConfig(sparse_as_dense=True,
                                              codec=inner,
                                              error_feedback=True))
        assert a.wire_bytes(8) == b.wire_bytes(8)
        assert a.n_collectives == b.n_collectives
        assert a.hlo_collectives(8) == b.hlo_collectives(8)
        assert b.state_bytes() == 4 * sum(
            bu.n_elems for bu in b.dense_buckets)


# ---------------------------------------------------------------------------
# zero-state adapter: stateless codecs through the stateful API
# ---------------------------------------------------------------------------

def test_zero_state_adapter_is_bitwise_identity_locally():
    """Acceptance: threading an (empty) ExchangeState through execute
    is bitwise identical to the legacy tree-only call, fused and
    overlap, for linear codecs."""
    tree = _tree()
    for codec in ("identity", "bf16"):
        for overlap in (False, True):
            plan = compile_plan(tree, ExchangeConfig(
                sparse_as_dense=True, codec=codec, overlap=overlap))
            legacy = plan.execute(tree, axis_name=None)
            st = plan.init_state()
            assert not jax.tree_util.tree_leaves(st)   # truly empty
            out, st2 = plan.execute(tree, axis_name=None, state=st)
            assert isinstance(st2, ExchangeState)
            for a, b in zip(jax.tree_util.tree_leaves(legacy),
                            jax.tree_util.tree_leaves(out)):
                np.testing.assert_array_equal(np.asarray(a),
                                              np.asarray(b))


def test_stateful_codec_requires_threaded_state():
    tree = _tree()
    plan = compile_plan(tree, ExchangeConfig(sparse_as_dense=True,
                                             codec="int8+ef"))
    with pytest.raises(ValueError, match="stateful"):
        plan.execute(tree, axis_name=None)
    # a state with the wrong stage count is rejected (different plan)
    with pytest.raises(ValueError, match="stage"):
        plan.execute(tree, axis_name=None,
                     state=ExchangeState([()]))
    with pytest.raises(TypeError):
        plan.execute(tree, axis_name=None, state=[()])


def test_error_feedback_compensates_over_steps():
    """Repeating the same gradient: the 2-step AVERAGE decoded output
    must be strictly closer to the truth than a single quantised step
    (the EF dithering guarantee), and residuals must be nonzero."""
    tree = {"w": jnp.asarray(
        np.random.default_rng(3).standard_normal(512), jnp.float32)}
    plan = compile_plan(tree, ExchangeConfig(sparse_as_dense=True,
                                             codec="int8+ef"))
    st = plan.init_state()
    o1, st = plan.execute(tree, axis_name=None, state=st)
    o2, st = plan.execute(tree, axis_name=None, state=st)
    err1 = float(jnp.abs(o1["w"] - tree["w"]).max())
    err_avg = float(jnp.abs((o1["w"] + o2["w"]) / 2 - tree["w"]).max())
    assert err_avg < err1
    assert float(jnp.abs(st.bucket_states[0]).max()) > 0


# ---------------------------------------------------------------------------
# stats + describe
# ---------------------------------------------------------------------------

def test_stats_report_state_bytes_and_hop_wire():
    tree = _tree()
    opt = DistributedOptimizer(adamw(1e-3), exchange=ExchangeConfig(
        sparse_as_dense=True, codec="int8", error_feedback=True,
        backend="hierarchical"), axis_name=("pod", "data"))
    stats = opt.exchange_stats(tree, n_workers=(2, 4))
    assert stats.state_bytes == opt.plan(tree).state_bytes() > 0
    assert len(stats.hop_wire_bytes) == 2
    assert sum(stats.hop_wire_bytes) == stats.wire_bytes
    text = stats.describe()
    assert "codec state" in text and "per-hop wire" in text
    assert "state B" in text                     # per-stage column
    # stateless flat runs keep the old shape: no state line, single hop
    flat = DistributedOptimizer(adamw(1e-3), exchange=ExchangeConfig(
        sparse_as_dense=True))
    fstats = flat.exchange_stats(tree, 8)
    assert fstats.state_bytes == 0
    assert "codec state" not in fstats.describe()


def test_hierarchical_int8_per_hop_wire_beats_full_mesh():
    """ROADMAP item: per-hop requantize restores the hierarchical
    bandwidth win for quantised wires — Σ_k (p_k - 1)·payload, not the
    full-mesh (P - 1)·payload."""
    tree = {"w": jnp.ones((64, 64), jnp.float32)}
    hier = compile_plan(tree, ExchangeConfig(sparse_as_dense=True,
                                             codec="int8",
                                             backend="hierarchical"))
    flat = compile_plan(tree, ExchangeConfig(sparse_as_dense=True,
                                             codec="int8"))
    payload = 4096 + 4                           # int8 values + f32 scale
    assert flat.wire_bytes(8) == 7 * payload
    assert hier.wire_bytes((2, 4)) == (1 + 3) * payload
    assert hier.hop_wire_bytes((2, 4)) == (1 * payload, 3 * payload)
    assert hier.wire_bytes((2, 4)) < flat.wire_bytes(8)
    # 2 (values+scales) rounds per level, not one full-mesh gather
    assert hier.n_collectives == 4


# ---------------------------------------------------------------------------
# checkpoint round-trip: mid-run resume with identical residuals
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip_resumes_with_identical_residuals(tmp_path):
    """Satellite acceptance: save/restore mid-run resumes with IDENTICAL
    residuals — a 2+2-step run through a checkpoint equals a straight
    4-step run bitwise (params AND ExchangeState)."""
    from repro.checkpoint import restore_checkpoint, save_checkpoint

    rng = np.random.default_rng(0)
    params = {"w": jnp.asarray(rng.standard_normal(256), jnp.float32)}
    grads = [{"w": jnp.asarray(rng.standard_normal(256), jnp.float32)}
             for _ in range(4)]
    opt = DistributedOptimizer(adamw(1e-2), exchange=ExchangeConfig(
        sparse_as_dense=True, codec="int8", error_feedback=True))
    plan = opt.plan(grads[0])

    def run(params, opt_state, st, gs):
        for g in gs:
            dense, st = opt.exchange(g, state=st)
            updates, opt_state = opt.base.update(dense, opt_state, params)
            params = jax.tree_util.tree_map(lambda p, u: p + u, params,
                                            updates)
        return params, opt_state, st

    # straight 4-step run
    p_a, o_a, s_a = run(params, opt.init(params), plan.init_state(), grads)
    # 2 steps, checkpoint, restore, 2 more
    p_b, o_b, s_b = run(params, opt.init(params), plan.init_state(),
                        grads[:2])
    save_checkpoint(str(tmp_path), 2, (p_b, o_b, s_b))
    like = (params, opt.init(params), plan.init_state())
    (p_c, o_c, s_c), step = restore_checkpoint(str(tmp_path), like)
    assert step == 2
    for a, b in zip(jax.tree_util.tree_leaves(s_b),
                    jax.tree_util.tree_leaves(s_c)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    p_c, o_c, s_c = run(p_c, o_c, s_c, grads[2:])
    for a, b in zip(jax.tree_util.tree_leaves((p_a, s_a)),
                    jax.tree_util.tree_leaves((p_c, s_c))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_trainer_checkpoints_and_resumes_exchange_state(tmp_path):
    """End-to-end: Trainer saves (params, opt_state, ExchangeState) and
    a resumed run continues from the restored residuals bitwise."""
    from repro.configs import get_config
    from repro.data import make_pipeline
    from repro.models import build_model
    from repro.training import Trainer, TrainerConfig, make_train_step
    from repro.training.gradients import abstract_grad_contributions

    cfg = get_config("transformer-big").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = DistributedOptimizer(adamw(1e-2), exchange=ExchangeConfig(
        sparse_as_dense=True, codec="int8", error_feedback=True))
    step = make_train_step(model, opt, sparse_embedding=True)
    assert step.stateful_exchange
    pipe = make_pipeline(cfg, batch_per_host=4, seq_len=16, task="copy")
    b0 = {k: jnp.asarray(v) for k, v in pipe.batch_at(0).items()}
    g = abstract_grad_contributions(model, params, b0,
                                    sparse_embedding=True)
    ex0 = opt.init_exchange_state(g)

    def trainer(total, resume):
        return Trainer(model, step, pipe, TrainerConfig(
            total_steps=total, log_every=total,
            checkpoint_every=2, checkpoint_dir=str(tmp_path),
            resume=resume))

    def copy(tree):
        # ``run`` consumes the state it is given: each fresh run gets a copy
        return jax.tree_util.tree_map(jnp.copy, tree)

    straight = trainer(4, resume=False).run(
        copy(params), opt.init(params), log=lambda s: None,
        exchange_state=copy(ex0))

    for f in os.listdir(tmp_path):
        os.remove(os.path.join(tmp_path, f))
    trainer(2, resume=False).run(copy(params), opt.init(params),
                                 log=lambda s: None,
                                 exchange_state=copy(ex0))
    resumed = trainer(4, resume=True).run(
        params, opt.init(params), log=lambda s: None, exchange_state=ex0)

    for a, b in zip(
            jax.tree_util.tree_leaves((straight["params"],
                                       straight["exchange_state"])),
            jax.tree_util.tree_leaves((resumed["params"],
                                       resumed["exchange_state"]))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# scaled train step threading
# ---------------------------------------------------------------------------

def test_scaled_train_step_threads_exchange_state():
    from repro.configs import get_config
    from repro.data import make_pipeline
    from repro.models import build_model
    from repro.training.gradients import abstract_grad_contributions
    from repro.training.microbatch import (LossScaler,
                                           make_scaled_train_step)

    cfg = get_config("transformer-big").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = DistributedOptimizer(adamw(1e-2), exchange=ExchangeConfig(
        sparse_as_dense=True, codec="int8", error_feedback=True))
    scaler = LossScaler(init_scale=2.0)
    step = jax.jit(make_scaled_train_step(model, opt, scaler))
    pipe = make_pipeline(cfg, batch_per_host=4, seq_len=16, task="copy")
    batch = {k: jnp.asarray(v) for k, v in pipe.batch_at(0).items()}
    g = abstract_grad_contributions(model, params, batch)
    ex = opt.init_exchange_state(g)
    opt_state, sstate = opt.init(params), scaler.init()
    params, opt_state, sstate, ex, metrics = step(params, opt_state,
                                                  sstate, ex, batch)
    assert float(metrics["loss"]) > 0
    assert any(float(jnp.abs(l).max()) > 0
               for l in jax.tree_util.tree_leaves(ex))


def test_overflow_step_rolls_back_exchange_state():
    """An overflowed encode must not bank its residuals: inf grads
    round-trip to inf-inf = NaN, and a poisoned ExchangeState would
    NaN every subsequent step's wire.  On overflow the state rolls
    back with params/opt_state."""
    from repro.configs import get_config
    from repro.data import make_pipeline
    from repro.models import build_model
    from repro.training.gradients import abstract_grad_contributions
    from repro.training.microbatch import (LossScaler,
                                           make_scaled_train_step)

    cfg = get_config("transformer-big").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = DistributedOptimizer(adamw(1e-2), exchange=ExchangeConfig(
        sparse_as_dense=True, codec="int8", error_feedback=True))
    # inf scale makes every scaled gradient non-finite: guaranteed skip
    scaler = LossScaler(init_scale=float("inf"))
    step = jax.jit(make_scaled_train_step(model, opt, scaler))
    pipe = make_pipeline(cfg, batch_per_host=4, seq_len=16, task="copy")
    batch = {k: jnp.asarray(v) for k, v in pipe.batch_at(0).items()}
    g = abstract_grad_contributions(model, params, batch)
    ex0 = opt.init_exchange_state(g)
    opt_state, sstate = opt.init(params), scaler.init()
    _, _, _, ex1, metrics = step(params, opt_state, sstate, ex0, batch)
    assert bool(metrics["overflow"])
    for new, old in zip(jax.tree_util.tree_leaves(ex1),
                        jax.tree_util.tree_leaves(ex0)):
        np.testing.assert_array_equal(np.asarray(new), np.asarray(old))


def test_residuals_rescale_with_loss_scale():
    """Residuals live in loss-scaled units: when the scaler grows, the
    banked residual must be converted to the new units, or the next
    step compensates at the wrong magnitude."""
    from repro.configs import get_config
    from repro.data import make_pipeline
    from repro.models import build_model
    from repro.training.gradients import abstract_grad_contributions
    from repro.training.microbatch import (LossScaler,
                                           make_scaled_train_step)

    cfg = get_config("transformer-big").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    pipe = make_pipeline(cfg, batch_per_host=4, seq_len=16, task="copy")
    batch = {k: jnp.asarray(v) for k, v in pipe.batch_at(0).items()}

    def one_step(growth_interval):
        opt = DistributedOptimizer(adamw(1e-2), exchange=ExchangeConfig(
            sparse_as_dense=True, codec="int8", error_feedback=True))
        scaler = LossScaler(init_scale=2.0,
                            growth_interval=growth_interval)
        step = jax.jit(make_scaled_train_step(model, opt, scaler))
        g = abstract_grad_contributions(model, params, batch)
        ex = opt.init_exchange_state(g)
        out = step(params, opt.init(params), scaler.init(), ex, batch)
        return out[3]                              # new ExchangeState

    # same incoming scale (2.0) → identical encode and residual; the
    # growing scaler doubles to 4.0 after the step, so its banked state
    # must be exactly 2x the constant scaler's (bitwise: power of two)
    ex_const = one_step(growth_interval=10 ** 6)
    ex_grow = one_step(growth_interval=1)
    assert any(float(jnp.abs(l).max()) > 0
               for l in jax.tree_util.tree_leaves(ex_const))
    for a, b in zip(jax.tree_util.tree_leaves(ex_grow),
                    jax.tree_util.tree_leaves(ex_const)):
        np.testing.assert_array_equal(np.asarray(a), 2 * np.asarray(b))


def test_error_feedback_config_accepts_codec_instances():
    cfg = ExchangeConfig(sparse_as_dense=True, codec=get_codec("int8"),
                         error_feedback=True)
    assert cfg.codec == "int8+ef"


def test_register_codec_invalidates_cached_ef_wrapper():
    from repro.core import codecs as codecs_mod

    original = get_codec("int8")
    assert get_codec("int8+ef").inner is original
    try:
        replacement = codecs_mod.Int8Codec()
        codecs_mod.register_codec(replacement, name="int8")
        assert get_codec("int8+ef").inner is replacement
    finally:
        codecs_mod.register_codec(original, name="int8")
    assert get_codec("int8+ef").inner is original


# ---------------------------------------------------------------------------
# multi-worker acceptance (subprocess, 8 emulated workers)
# ---------------------------------------------------------------------------

def test_stateful_api_bitwise_and_per_hop_audit_across_workers():
    """Acceptance: (1) linear codecs through the stateful API are
    BITWISE identical to the stateless PR 3 path under shard_map, fused
    and overlap; (2) hierarchical int8 lowers the per-hop requantize
    path with exact wire/collective accounting against the HLO; (3)
    error feedback adds zero collectives and zero wire bytes."""
    out = run_with_devices(textwrap.dedent("""
        import functools
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        from repro.core import DistributedOptimizer, ExchangeConfig
        from repro.optim import adamw

        P_ = len(jax.devices())
        mesh = Mesh(np.array(jax.devices()), ('data',))
        rng = np.random.default_rng(0)
        ws = jnp.asarray(rng.standard_normal((P_, 4, 40, 8)), jnp.float32)
        tree = {'w%d' % k: ws[0, k] for k in range(4)}

        # (1) zero-state adapter bitwise identity, fused + overlap
        for codec in ('identity', 'bf16'):
            for overlap in (False, True):
                cfgx = ExchangeConfig(sparse_as_dense=True, codec=codec,
                                      overlap=overlap)
                opt = DistributedOptimizer(adamw(1e-3), exchange=cfgx,
                                           axis_name=('data',))
                st0 = opt.init_exchange_state(tree, n_workers=P_)

                def f_legacy(w, opt=opt):
                    g = {'w%d' % k: w[0, k] for k in range(4)}
                    out = opt.exchange(g)
                    return jnp.stack([out['w%d' % k]
                                      for k in range(4)])[None]

                def f_state(w, s, opt=opt):
                    g = {'w%d' % k: w[0, k] for k in range(4)}
                    out, s = opt.exchange(g, state=s)
                    return jnp.stack([out['w%d' % k]
                                      for k in range(4)])[None], s

                legacy = jax.jit(shard_map(
                    f_legacy, mesh=mesh, in_specs=(P('data'),),
                    out_specs=P('data'), check_vma=False))(ws)
                stateful, _ = jax.jit(shard_map(
                    f_state, mesh=mesh,
                    in_specs=(P('data'), P('data')),
                    out_specs=(P('data'), P('data')),
                    check_vma=False))(ws, st0)
                assert np.array_equal(np.asarray(legacy)[0],
                                      np.asarray(stateful)[0]), \
                    (codec, overlap)

        # (2) + (3): per-hop requantize + EF audits, exact vs HLO
        from repro.launch.dryrun import audit_exchange_plan
        r = audit_exchange_plan(arch='transformer-big', n_workers=8,
                                codec='int8', backend='hierarchical')
        assert r['counts_match'], r
        assert abs(r['wire_ratio'] - 1.0) < 1e-6, r
        hops = r['planned_hop_wire_bytes']
        assert len(hops) == 2 and sum(hops) == r['planned_wire_bytes']
        flat = audit_exchange_plan(arch='transformer-big', n_workers=8,
                                   codec='int8')
        assert r['planned_wire_bytes'] < flat['planned_wire_bytes']
        ef = audit_exchange_plan(arch='transformer-big', n_workers=8,
                                 codec='int8', backend='hierarchical',
                                 error_feedback=True)
        assert ef['counts_match'], ef
        assert abs(ef['wire_ratio'] - 1.0) < 1e-6, ef
        assert ef['hlo_ops'] == r['hlo_ops']
        assert ef['planned_wire_bytes'] == r['planned_wire_bytes']
        assert ef['codec_state_bytes'] > 0
        print('OK')
    """))
    assert "OK" in out


def test_error_feedback_improves_loss_across_workers():
    """The CI smoke contract in test form: 8-worker int8+ef training
    must land within tolerance of the fp32 wire (and at least as close
    as plain int8)."""
    out = run_with_devices(textwrap.dedent("""
        import functools
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        from repro.core import DistributedOptimizer, ExchangeConfig
        from repro.optim import adamw

        P_ = len(jax.devices())
        mesh = Mesh(np.array(jax.devices()), ('data',))
        rng = np.random.default_rng(0)
        N = 512
        w_true = jnp.asarray(rng.standard_normal(N), jnp.float32)
        xs = jnp.asarray(rng.standard_normal((P_, 64, N)), jnp.float32)

        def final_loss(codec, ef):
            opt = DistributedOptimizer(adamw(3e-2),
                exchange=ExchangeConfig(sparse_as_dense=True,
                                        codec=codec, error_feedback=ef,
                                        fusion_threshold=1 << 20),
                axis_name=('data',))
            params = {'w': jnp.zeros(N)}
            # every codec rides the stateful protocol (zero-state
            # adapter for identity/int8) — one calling convention
            st = opt.init_exchange_state(params, n_workers=P_)

            def step(params, opt_state, st, x):
                def loss_fn(p):
                    err = x[0] @ (p['w'] - w_true)
                    return jnp.mean(err ** 2)
                loss, g = jax.value_and_grad(loss_fn)(params)
                dense, st = opt.exchange(g, state=st)
                updates, opt_state = opt.base.update(dense, opt_state,
                                                     params)
                params = jax.tree_util.tree_map(lambda p, u: p + u,
                                                params, updates)
                return params, opt_state, st, loss

            sm = jax.jit(shard_map(step, mesh=mesh,
                in_specs=(P(), P(), P('data'), P('data')),
                out_specs=(P(), P(), P('data'), P()),
                check_vma=False))
            opt_state = opt.init(params)
            for i in range(60):
                params, opt_state, st, loss = sm(params, opt_state, st,
                                                 xs)
            return float(loss)

        f32 = final_loss('identity', False)
        q8 = final_loss('int8', False)
        ef = final_loss('int8', True)
        print('f32', f32, 'int8', q8, 'int8+ef', ef)
        assert ef <= q8 + 1e-6, (ef, q8)
        assert abs(ef - f32) <= max(0.5 * abs(q8 - f32), 0.1 * abs(f32),
                                    1e-3), (f32, q8, ef)
        print('OK')
    """))
    assert "OK" in out
