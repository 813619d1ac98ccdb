"""Wait-free backprop (``ExchangeConfig(overlap="backward")``): block-
aligned bucketing, custom_vjp-launched in-backward collectives, bitwise
identity with the fused plan, and ExchangeState/checkpoint composition
(multi-device cases run in subprocesses with 8 emulated CPU workers,
like test_exchange.py)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import restore_checkpoint, save_checkpoint
from repro.configs import get_config
from repro.core import DistributedOptimizer, ExchangeConfig
from repro.data import make_pipeline
from repro.models import build_model
from repro.optim import adamw
from repro.training.gradients import (abstract_grad_contributions,
                                      grad_contributions,
                                      wait_free_grad_exchange)
from repro.training.microbatch import (LossScaler, accumulate_microbatches,
                                       make_scaled_train_step,
                                       split_microbatches)
from repro.training.train_step import make_train_step

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


def _model_and_batch(arch="transformer-big", batch=2, seq=16, seed=0):
    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    b = {k: jnp.asarray(v)
         for k, v in make_pipeline(cfg, batch, seq).batch_at(0).items()}
    return cfg, model, params, b


def _bitwise(a, b) -> bool:
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        x.dtype == y.dtype and bool(jnp.array_equal(x, y))
        for x, y in zip(la, lb))


# -- config / plan statics ---------------------------------------------------

def test_overlap_mode_normalization():
    assert ExchangeConfig().overlap is False
    assert ExchangeConfig(overlap=None).overlap is False
    assert ExchangeConfig(overlap="off").overlap is False
    assert ExchangeConfig(overlap=True).overlap == "staged"
    assert ExchangeConfig(overlap="staged").overlap == "staged"
    assert ExchangeConfig(overlap="backward").overlap == "backward"
    assert ExchangeConfig(overlap="backward").overlap_backward
    assert not ExchangeConfig(overlap="staged").overlap_backward
    with pytest.raises(ValueError, match="unknown overlap mode"):
        ExchangeConfig(overlap="sideways")


def test_backward_buckets_never_cross_blocks():
    """With a huge fusion threshold the staged plan fuses everything
    into one bucket; the backward plan must still split at block
    boundaries, because a bucket can only launch mid-backward if ALL
    its leaves come from one custom_vjp boundary."""
    cfg, model, params, batch = _model_and_batch()
    grads = abstract_grad_contributions(model, params, batch,
                                        sparse_embedding=False)
    big = 1 << 40
    staged = DistributedOptimizer(
        adamw(1e-3), exchange=ExchangeConfig(
            sparse_as_dense=True, fusion_threshold=big, overlap="staged"),
        axis_name=None).plan(grads)
    bwd = DistributedOptimizer(
        adamw(1e-3), exchange=ExchangeConfig(
            sparse_as_dense=True, fusion_threshold=big, overlap="backward"),
        axis_name=None).plan(grads)
    assert staged.schedule.n_stages == 1
    assert bwd.schedule.n_stages == len(params)     # one bucket per block
    for st in bwd.schedule.stages:
        blocks = {bwd.leaf_blocks[i] for i in st.leaf_ids}
        assert len(blocks) == 1, st
        assert st.trigger == blocks.pop()
    hooked, tail = bwd.backward_block_stages(set(params))
    assert tail == ()
    assert sorted(hooked) == sorted(params)
    # every stage is exactly one of hooked/tail, in schedule order
    all_ids = sorted(i for ids in hooked.values() for i in ids)
    assert all_ids == list(range(bwd.schedule.n_stages))


def test_backward_block_stages_tail_for_unhooked():
    """Gather stages and stages of unhooked blocks (sparse embedding:
    its contributions are assembled outside autodiff) go to the tail."""
    cfg, model, params, batch = _model_and_batch()
    grads = abstract_grad_contributions(model, params, batch,
                                        sparse_embedding=True)
    plan = DistributedOptimizer(
        adamw(1e-3), exchange=ExchangeConfig(
            sparse_as_dense=False, overlap="backward"),
        axis_name=None).plan(grads)
    hooked_blocks = set(params) - {"embedding"}
    hooked, tail = plan.backward_block_stages(hooked_blocks)
    assert "embedding" not in hooked
    assert tail                                   # gather + tied dense
    for sid in tail:
        st = plan.schedule.stages[sid]
        blocks = {plan.leaf_blocks[i] for i in st.leaf_ids}
        assert st.kind == "gather" or blocks == {"embedding"}


def test_stats_trigger_column_and_strategy():
    cfg, model, params, batch = _model_and_batch()
    grads = abstract_grad_contributions(model, params, batch,
                                        sparse_embedding=True)
    opt = DistributedOptimizer(
        adamw(1e-3), exchange=ExchangeConfig(
            sparse_as_dense=False, overlap="backward"),
        axis_name=("data",))
    stats = opt.exchange_stats(grads, n_workers=8)
    text = stats.describe()
    assert "overlap=backward" in text
    assert "trigger=" in text
    assert "wait-free backward" in text
    assert "+overlap:backward" in stats.strategy
    # staged keeps the legacy rendering (existing tests/logs key on it)
    opt_s = DistributedOptimizer(
        adamw(1e-3), exchange=ExchangeConfig(
            sparse_as_dense=False, overlap=True),
        axis_name=("data",))
    stats_s = opt_s.exchange_stats(grads, n_workers=8)
    assert "overlap=on" in stats_s.describe()
    assert stats_s.strategy.endswith("+overlap")


# -- single-device bitwise identity ------------------------------------------

@pytest.mark.parametrize("sparse", [False, True])
def test_wait_free_grad_exchange_matches_fused_bitwise(sparse):
    cfg, model, params, batch = _model_and_batch()
    ex = ExchangeConfig(sparse_as_dense=not sparse, overlap="backward")
    opt = DistributedOptimizer(adamw(1e-3), exchange=ex, axis_name=None)
    grads, loss_ref, _ = grad_contributions(model, params, batch,
                                            sparse_embedding=sparse)
    ref = opt.plan(grads).execute_fused(grads, None)
    dense, state, loss, metrics = wait_free_grad_exchange(
        model, opt, params, batch, sparse_embedding=sparse)
    assert state is None
    assert _bitwise(ref, dense)
    assert jnp.array_equal(loss, loss_ref)
    assert int(metrics["exchange_stages"]) == opt.plan(grads).schedule.n_stages


@pytest.mark.parametrize("sparse", [False, True])
def test_wait_free_train_step_matches_fused(sparse):
    cfg, model, params, batch = _model_and_batch()
    outs = {}
    for overlap in (False, "backward"):
        ex = ExchangeConfig(sparse_as_dense=not sparse, overlap=overlap)
        opt = DistributedOptimizer(adamw(1e-3), exchange=ex, axis_name=None)
        step = jax.jit(make_train_step(model, opt,
                                       sparse_embedding=sparse))
        p2, o2, m = step(params, opt.init(params), batch)
        outs[overlap] = (p2, m["loss"])
    assert _bitwise(outs[False][0], outs["backward"][0])
    assert jnp.array_equal(outs[False][1], outs["backward"][1])


# -- satellite: deferred microbatches + int8+ef + checkpoint/resume ----------

def test_wait_free_microbatch_ef_residuals_checkpoint_resume(tmp_path):
    """Deferred final microbatch + overlap='backward' + int8+ef: the
    wait-free step's params AND error-feedback residuals stay bitwise
    identical to the fused execution of the same deferred contribution
    representation — including across a checkpoint/resume boundary."""
    n_mb = 4
    cfg, model, params, batch = _model_and_batch(batch=8)
    scaler = LossScaler()
    b2 = {k: jnp.asarray(v) for k, v in
          make_pipeline(cfg, 8, 16).batch_at(1).items()}

    # the deferred representation both paths exchange
    g_abs = jax.eval_shape(
        lambda p, b: accumulate_microbatches(
            model, p, split_microbatches(b, n_mb), sparse_embedding=True,
            defer_final=True)[0], params, batch)

    def make(overlap):
        ex = ExchangeConfig(sparse_as_dense=False, codec="int8+ef",
                            overlap=overlap)
        opt = DistributedOptimizer(adamw(1e-3), exchange=ex,
                                   axis_name=None)
        step = jax.jit(make_scaled_train_step(
            model, opt, scaler, n_microbatches=n_mb,
            sparse_embedding=True))
        assert step.stateful_exchange
        return opt, step

    results = {}
    for overlap in ("staged", "backward"):
        opt, step = make(overlap)
        st0 = opt.init_exchange_state(g_abs)
        state = (params, opt.init(params), scaler.init(), st0)
        # continuous: two steps back to back
        s1 = step(*state, batch)[:-1]
        cont = step(*s1, b2)[:-1]
        # resumed: checkpoint after step 1, restore, then step 2
        save_checkpoint(str(tmp_path / overlap), 1, s1)
        restored, _ = restore_checkpoint(str(tmp_path / overlap), s1)
        resumed = step(*restored, b2)[:-1]
        assert _bitwise(cont, resumed), overlap
        results[overlap] = cont
    p_a, o_a, sc_a, ex_a = results["staged"]
    p_b, o_b, sc_b, ex_b = results["backward"]
    assert _bitwise(p_a, p_b)
    assert _bitwise(ex_a, ex_b)        # EF residuals bitwise identical
    assert jnp.array_equal(sc_a.scale, sc_b.scale)


# -- 8 emulated workers: shard_map bitwise identity + HLO counts -------------

def test_wait_free_across_workers_bitwise():
    """Acceptance: under shard_map on 8 workers, with per-worker batch
    shards, the wait-free in-backward exchange produces BITWISE the
    fused plan's dense gradients for linear codecs, and its lowered HLO
    contains exactly plan.hlo_collectives(P) collective ops (the model
    forward/backward adds none)."""
    run_with_devices(textwrap.dedent("""
        import functools
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        from repro.configs import get_config
        from repro.core import DistributedOptimizer, ExchangeConfig
        from repro.data import make_pipeline
        from repro.launch import hlo as hlo_lib
        from repro.models import build_model
        from repro.optim import adamw
        from repro.training.gradients import (grad_contributions,
                                              wait_free_grad_exchange)

        cfg = get_config("transformer-big").reduced()
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        P_ = len(jax.devices())
        mesh = Mesh(np.array(jax.devices()), ("data",))
        batch = {k: jnp.asarray(v) for k, v in
                 make_pipeline(cfg, P_, 16).batch_at(0).items()}

        for codec in ("identity", "bf16"):
            for sparse in (True, False):
                ex = ExchangeConfig(sparse_as_dense=not sparse,
                                    codec=codec, overlap="backward")
                opt = DistributedOptimizer(adamw(1e-3), exchange=ex,
                                           axis_name=("data",))

                def wf(p_, b_):
                    return wait_free_grad_exchange(
                        model, opt, p_, b_,
                        sparse_embedding=sparse)[0]

                def fused(p_, b_):
                    g, _, _ = grad_contributions(
                        model, p_, b_, sparse_embedding=sparse)
                    return opt.plan(g).execute_fused(g, ("data",))

                kw = dict(mesh=mesh, in_specs=(P(), P("data")),
                          out_specs=P(), check_vma=False)
                wf_sm = jax.jit(shard_map(wf, **kw))
                hlo = wf_sm.lower(params, batch).compile().as_text()
                out_wf = wf_sm(params, batch)
                out_f = jax.jit(shard_map(fused, **kw))(params, batch)
                la = jax.tree_util.tree_leaves(out_wf)
                lb = jax.tree_util.tree_leaves(out_f)
                assert len(la) == len(lb)
                for a, b in zip(la, lb):
                    assert a.dtype == b.dtype
                    assert jnp.array_equal(a, b), (codec, sparse, a.shape)

                g_abs = jax.eval_shape(
                    lambda p, b: grad_contributions(
                        model, p, b, sparse_embedding=sparse)[0],
                    params,
                    jax.tree_util.tree_map(lambda x: x[:1], batch))
                plan = opt.plan(g_abs)
                counts = hlo_lib.count_collectives(hlo)
                assert sum(counts.values()) == plan.hlo_collectives(P_), (
                    codec, sparse, counts)
        print("ok")
    """))
