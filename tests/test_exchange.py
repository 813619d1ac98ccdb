"""ExchangePlan: static classification, bucketing, byte accounting,
cache behaviour, and plan-vs-eager numerical equivalence (multi-device
cases run in subprocesses with 8 emulated CPU workers, like
test_distributed.py)."""
import os
import subprocess
import sys
import textwrap
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (DistributedOptimizer, ExchangeConfig, IndexedSlices,
                        accumulate_gradients, available_backends,
                        available_codecs, clear_plan_cache, comm,
                        compile_plan, densify, exchange, get_backend,
                        get_codec, plan_cache_info)
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


def _demo_tree(v=24, d=8, n=6, seed=0):
    rng = np.random.default_rng(seed)
    s = IndexedSlices(jnp.asarray(rng.integers(0, v, n, dtype=np.int32)),
                      jnp.asarray(rng.standard_normal((n, d)), jnp.float32),
                      (v, d))
    proj = jnp.asarray(rng.standard_normal((v, d)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((5, 3)), jnp.float32)
    return {"emb": [s, proj], "w": w}


# ---------------------------------------------------------------------------
# classification mirrors the eager accumulation algorithms
# ---------------------------------------------------------------------------

@st.composite
def contribution_specs(draw):
    v = draw(st.integers(2, 40))
    d = draw(st.integers(1, 16))
    n_contrib = draw(st.integers(1, 5))
    kinds = draw(st.lists(st.booleans(), min_size=n_contrib,
                          max_size=n_contrib))
    seed = draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    out = []
    for sparse in kinds:
        if sparse:
            n = int(rng.integers(1, 3 * v))
            out.append(IndexedSlices(
                jnp.asarray(rng.integers(0, v, n).astype(np.int32)),
                jnp.asarray(rng.standard_normal((n, d)), jnp.float32),
                (v, d)))
        else:
            out.append(jnp.asarray(rng.standard_normal((v, d)),
                                   jnp.float32))
    return out


@given(contribution_specs(), st.booleans(),
       st.sampled_from(["tf_algorithm1", "proposed_algorithm2"]))
@settings(max_examples=40, deadline=None)
def test_classification_matches_eager_representation(contribs, sad, alg):
    cfg = ExchangeConfig(algorithm=alg, sparse_as_dense=sad)
    spec = exchange.classify(
        tuple(exchange.contribution_spec(c) for c in contribs), cfg)
    eager = accumulate_gradients(contribs, algorithm=alg,
                                 sparse_as_dense=sad)
    if isinstance(eager, IndexedSlices):
        assert isinstance(spec, exchange.SparseSpec)
        assert spec.rows == int(eager.indices.shape[0])
        assert spec.dense_shape == tuple(eager.dense_shape)
    else:
        assert isinstance(spec, exchange.DenseSpec)
        assert spec.shape == tuple(eager.shape)


# ---------------------------------------------------------------------------
# planned wire/buffer bytes == the comm closed forms
# ---------------------------------------------------------------------------

@st.composite
def shape_mixes(draw):
    """A grad tree with random dense shapes + random sparse leaves."""
    seed = draw(st.integers(0, 2**31 - 1))
    n_dense = draw(st.integers(0, 6))
    n_sparse = draw(st.integers(0, 3))
    rng = np.random.default_rng(seed)
    tree = {}
    for i in range(n_dense):
        shape = tuple(int(x) for x in
                      rng.integers(1, 9, size=rng.integers(1, 4)))
        tree[f"d{i}"] = jnp.asarray(
            rng.standard_normal(shape).astype(np.float32))
    for i in range(n_sparse):
        v, d = int(rng.integers(2, 30)), int(rng.integers(1, 9))
        n = int(rng.integers(1, 2 * v))
        tree[f"s{i}"] = IndexedSlices(
            jnp.asarray(rng.integers(0, v, n).astype(np.int32)),
            jnp.asarray(rng.standard_normal((n, d)), jnp.float32), (v, d))
    if not tree:
        tree["d0"] = jnp.ones((3, 3), jnp.float32)
    return tree


@given(shape_mixes(), st.sampled_from([2, 8, 64]))
@settings(max_examples=40, deadline=None)
def test_planned_wire_bytes_match_comm_formulas(tree, p):
    plan = compile_plan(tree, ExchangeConfig(algorithm="tf_algorithm1"))
    expected_wire = 0
    expected_buf = 0
    for leaf in jax.tree_util.tree_leaves(
            tree, is_leaf=lambda x: isinstance(x, IndexedSlices)):
        if isinstance(leaf, IndexedSlices):
            rows = int(leaf.indices.shape[0])
            row_elems = int(leaf.values.size // max(rows, 1))
            expected_wire += comm.allgather_wire_bytes(
                rows, row_elems, leaf.values.dtype, p)
            expected_buf += comm.gathered_buffer_bytes(
                rows, row_elems, leaf.values.dtype, p)
        else:
            expected_wire += comm.allreduce_wire_bytes(
                leaf.shape, leaf.dtype, p)
            expected_buf += comm.dense_buffer_bytes(leaf.shape, leaf.dtype)
    assert plan.wire_bytes(p) == expected_wire
    assert plan.buffer_bytes(p) == expected_buf
    n_leaves = len(jax.tree_util.tree_leaves(
        tree, is_leaf=lambda x: isinstance(x, IndexedSlices)))
    assert plan.n_collectives == n_leaves          # no fusion: 1 per leaf


def test_bf16_wire_halves_dense_wire_bytes():
    tree = {"w": jnp.ones((64, 64), jnp.float32)}
    f32 = compile_plan(tree, ExchangeConfig(sparse_as_dense=True))
    bf16 = compile_plan(tree, ExchangeConfig(sparse_as_dense=True,
                                             wire_dtype="bf16"))
    assert bf16.wire_bytes(8) == f32.wire_bytes(8) // 2
    # the accumulated representation stays f32 (upcast on unpack)
    assert bf16.buffer_bytes(8) == f32.buffer_bytes(8)


def test_reduce_scatter_wire_equals_allreduce_wire():
    """RS+AG is the ring-allreduce decomposition: same total wire."""
    tree = {"w": jnp.ones((64, 64), jnp.float32)}   # 4096 % 8 == 0
    ar = compile_plan(tree, ExchangeConfig(sparse_as_dense=True))
    rs = compile_plan(tree, ExchangeConfig(sparse_as_dense=True,
                                           reduce_scatter=True))
    assert rs.wire_bytes(8) == ar.wire_bytes(8)
    assert rs.n_collectives == 2 * ar.n_collectives


def test_scalar_leaf_plans_and_executes():
    """Regression: scalar (shape ()) leaves crashed classification."""
    tree = {"temp": jnp.float32(2.5), "w": jnp.ones((3, 3), jnp.float32)}
    for cfg in (ExchangeConfig(sparse_as_dense=True),
                ExchangeConfig()):
        plan = compile_plan(tree, cfg)
        assert all(isinstance(s, exchange.DenseSpec)
                   for s in plan.leaf_specs)
        out = plan.execute(tree, axis_name=None)
        np.testing.assert_allclose(float(out["temp"]), 2.5)


def test_mixed_dtype_buckets_stay_homogeneous():
    """Regression: a fused bucket mixing bf16 and f32 leaves promoted the
    packed buffer to f32 while wire_bytes billed bf16.  Buckets are now
    grouped per wire dtype, so accounting matches the moved bytes."""
    tree = {"a": jnp.ones((1000,), jnp.bfloat16),
            "b": jnp.ones((100,), jnp.float32)}
    plan = compile_plan(tree, ExchangeConfig(sparse_as_dense=True,
                                             fusion_threshold=1 << 20))
    assert len(plan.dense_buckets) == 2           # one per dtype
    dts = sorted(b.wire_dtype for b in plan.dense_buckets)
    assert dts == ["bfloat16", "float32"]
    expected = (comm.allreduce_wire_bytes((1000,), jnp.bfloat16, 8)
                + comm.allreduce_wire_bytes((100,), jnp.float32, 8))
    assert plan.wire_bytes(8) == expected
    out = plan.execute(tree, axis_name=None)
    assert out["a"].dtype == jnp.bfloat16
    assert out["b"].dtype == jnp.float32


def test_hierarchical_accounting_is_per_level():
    """Regression: hierarchical plans billed a flat ring and hard-coded
    2 launches; counts and wire now follow hierarchy_levels and demand
    per-level worker counts."""
    tree = {"w": jnp.ones((64, 64), jnp.float32)}
    plan = compile_plan(tree, ExchangeConfig(sparse_as_dense=True,
                                             hierarchical=True))
    assert plan.n_collectives == 2
    expected = (comm.allreduce_wire_bytes((4096,), jnp.float32, 2)
                + comm.allreduce_wire_bytes((4096,), jnp.float32, 4))
    assert plan.wire_bytes((2, 4)) == expected
    with pytest.raises(ValueError):
        plan.wire_bytes(8)                 # int: ambiguous level split
    with pytest.raises(ValueError):
        plan.execute(tree, axis_name=("data",))   # wrong axis count


def test_fusion_buckets_reduce_collective_count():
    tree = {f"p{i}": jnp.ones((4, 4), jnp.float32) for i in range(64)}
    unfused = compile_plan(tree, ExchangeConfig(sparse_as_dense=True))
    fused = compile_plan(tree, ExchangeConfig(sparse_as_dense=True,
                                              fusion_threshold=1 << 20))
    assert unfused.n_collectives == 64
    assert fused.n_collectives == 1
    # fusion changes launches, not wire bytes
    assert abs(fused.wire_bytes(8) - unfused.wire_bytes(8)) <= 64


# ---------------------------------------------------------------------------
# codecs: registries, round-trip tolerance, wire-byte accounting
# ---------------------------------------------------------------------------

def test_codec_and_backend_registries():
    assert {"identity", "bf16", "int8"} <= set(available_codecs())
    assert {"jax", "hierarchical", "ringsim"} <= set(available_backends())
    # dtype-ish names resolve through the deprecated wire_dtype spelling
    assert get_codec("bfloat16") is get_codec("bf16")
    with pytest.raises(ValueError):
        get_codec("not-a-codec")
    with pytest.raises(ValueError):
        get_backend("not-a-backend")
    with pytest.raises(ValueError):
        ExchangeConfig(codec="not-a-codec")
    with pytest.raises(ValueError):
        ExchangeConfig(backend="not-a-backend")


@given(st.integers(0, 2**31 - 1), st.integers(1, 4000),
       st.floats(0.1, 1e4))
@settings(max_examples=30, deadline=None)
def test_codec_roundtrip_tolerances(seed, n, scale):
    """identity is exact, bf16 within relative eps, int8 within the
    per-bucket absmax scale bound."""
    rng = np.random.default_rng(seed)
    buf = jnp.asarray(rng.standard_normal(n) * scale, jnp.float32)
    for name, tol in (("identity", 0.0),
                      ("bf16", 2 ** -8 * float(jnp.abs(buf).max())),
                      ("f16", 2 ** -10 * float(jnp.abs(buf).max()))):
        codec = get_codec(name)
        wire, side = codec.encode(buf)
        assert side is None and codec.linear
        out = codec.decode(wire, side, jnp.float32)
        err = float(jnp.abs(out - buf).max())
        assert err <= tol, (name, err, tol)
    int8 = get_codec("int8")
    wire, side = int8.encode(buf)
    assert wire.dtype == jnp.int8 and side.shape == (1,)
    out = int8.decode(wire, side, jnp.float32)
    err = float(jnp.abs(out - buf).max())
    assert err <= int8.max_error(buf), (err, int8.max_error(buf))


def test_codec_wire_bytes_accounting():
    n = 1000
    assert get_codec("identity").wire_bytes(n, "float32") == 4 * n
    assert get_codec("bf16").wire_bytes(n, "float32") == 2 * n
    assert get_codec("int8").wire_bytes(n, "float32") == n + 4


def test_int8_codec_wire_bytes_quarters_dense_wire():
    tree = {"w": jnp.ones((64, 64), jnp.float32)}     # 4096 elems
    f32 = compile_plan(tree, ExchangeConfig(sparse_as_dense=True))
    q8 = compile_plan(tree, ExchangeConfig(sparse_as_dense=True,
                                           codec="int8"))
    # non-linear codecs exchange via allgather of (values, scales):
    # (P-1) * (n * 1B + 4B scale) per worker.  That grows ~(P-1)n vs the
    # ring allreduce's 2(P-1)/P * 4n, so the quantised-gather advantage
    # holds for P < 8 and the accounting must expose the crossover
    # honestly rather than billing a phantom 4x saving.
    for p in (2, 4, 8, 16):
        assert q8.wire_bytes(p) == (p - 1) * (64 * 64 + 4)
    assert q8.wire_bytes(4) < f32.wire_bytes(4)        # below crossover
    assert q8.wire_bytes(16) > f32.wire_bytes(16)      # beyond crossover
    # the accumulated representation stays f32 (decode after exchange)
    assert q8.buffer_bytes(8) == f32.buffer_bytes(8)


def test_int8_codec_gather_leaf_accounting():
    """Sparse gather buckets bill the codec's value payload + native
    indices + the per-worker side scale."""
    v, d, n = 24, 8, 6
    tree = {"s": IndexedSlices(jnp.arange(n, dtype=jnp.int32),
                               jnp.ones((n, d), jnp.float32), (v, d))}
    plan = compile_plan(tree, ExchangeConfig(codec="int8"))
    p = 8
    payload = (n * d) * 1 + 4 + n * 4          # int8 rows + scale + idx
    assert plan.wire_bytes(p) == (p - 1) * payload
    assert plan.buffer_bytes(p) == p * (n * (d * 1 + 4) + 4)


def test_int8_codec_rejects_reduce_scatter():
    with pytest.raises(ValueError):
        ExchangeConfig(sparse_as_dense=True, codec="int8",
                       reduce_scatter=True)
    with pytest.raises(ValueError):
        ExchangeConfig(sparse_as_dense=True, reduce_scatter=True,
                       backend="hierarchical")


def test_int8_codec_plan_executes_locally_within_scale_bound():
    """The local (axis_name=None) path still runs the quantise/decode
    round-trip so single-device tests see the wire precision."""
    tree = _demo_tree()
    ref = densify(accumulate_gradients(tree["emb"], sparse_as_dense=True))
    for use_kernel in (False, True):
        opt = DistributedOptimizer(adamw(1e-3), exchange=ExchangeConfig(
            sparse_as_dense=True, codec="int8", use_kernel=use_kernel))
        out = opt.exchange(tree)
        assert out["emb"].dtype == jnp.float32
        bound = float(jnp.abs(ref).max()) / 127 + 1e-6
        assert float(jnp.abs(out["emb"] - ref).max()) <= bound
        assert float(jnp.abs(out["w"] - tree["w"]).max()) <= \
            float(jnp.abs(tree["w"]).max()) / 127 + 1e-6


def test_pallas_quantize_kernel_matches_xla_codec_path():
    from repro.kernels import ops as kops
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(1000) * 3.7, jnp.float32)
    qp, sp = kops.quantize_int8(x, impl="pallas")
    qx, sx = kops.quantize_int8(x, impl="xla")
    np.testing.assert_array_equal(np.asarray(qp), np.asarray(qx))
    np.testing.assert_allclose(float(sp[0]), float(sx[0]), rtol=1e-7)
    assert qp.dtype == jnp.int8


def test_ringsim_backend_wire_accounting_matches_ring_formula():
    """The ring sim bills the explicit 2(P-1) chunk hops — equal to the
    classic ring-allreduce formula up to chunk padding."""
    tree = {"w": jnp.ones((64, 64), jnp.float32)}     # 4096 % 8 == 0
    flat = compile_plan(tree, ExchangeConfig(sparse_as_dense=True))
    ring = compile_plan(tree, ExchangeConfig(sparse_as_dense=True,
                                             backend="ringsim"))
    assert ring.wire_bytes(8) == flat.wire_bytes(8)
    # padding shows up when P does not divide the bucket
    assert ring.wire_bytes(7) >= flat.wire_bytes(7)
    assert ring.n_collectives == flat.n_collectives
    assert ring.hlo_collectives(8) == 2 * 7


# ---------------------------------------------------------------------------
# deprecation shims: old-style flags == new-style ExchangeConfig
# ---------------------------------------------------------------------------

def test_deprecated_optimizer_flags_map_onto_exchange_config():
    clear_plan_cache()
    tree = _demo_tree()
    with pytest.warns(DeprecationWarning):
        old = DistributedOptimizer(adamw(1e-3), sparse_as_dense=True,
                                   reduce_scatter=True, wire_dtype="bf16",
                                   use_kernel=False,
                                   fusion_threshold=1 << 20)
    new = DistributedOptimizer(adamw(1e-3), exchange=ExchangeConfig(
        sparse_as_dense=True, reduce_scatter=True, codec="bf16",
        fusion_threshold=1 << 20))
    assert old.exchange_config == new.exchange_config
    assert old.plan(tree) is new.plan(tree)        # identical cached plan
    with pytest.warns(DeprecationWarning):
        hier = DistributedOptimizer(adamw(1e-3), hierarchical=True)
    assert hier.exchange_config.backend == "hierarchical"
    # mixing both styles is an error, as is an unknown kwarg
    with pytest.raises(TypeError):
        DistributedOptimizer(adamw(1e-3), exchange=ExchangeConfig(),
                             sparse_as_dense=True)
    with pytest.raises(TypeError):
        DistributedOptimizer(adamw(1e-3), sparse_az_dense=True)
    # no warning for pure new-style construction
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        DistributedOptimizer(adamw(1e-3), exchange=ExchangeConfig())
        DistributedOptimizer(adamw(1e-3))


def test_exchange_config_normalises_deprecated_fields():
    assert ExchangeConfig(wire_dtype="bf16") == ExchangeConfig(codec="bf16")
    assert ExchangeConfig(hierarchical=True) == \
        ExchangeConfig(backend="hierarchical")
    with pytest.raises(ValueError):
        ExchangeConfig(wire_dtype="bf16", codec="int8")
    with pytest.raises(ValueError):
        ExchangeConfig(hierarchical=True, backend="ringsim")


def test_describe_and_stats_name_codec_and_backend():
    tree = _demo_tree()
    opt = DistributedOptimizer(adamw(1e-3), exchange=ExchangeConfig(
        sparse_as_dense=True, codec="int8", backend="ringsim"))
    stats = opt.exchange_stats(tree, n_workers=8)
    assert "codec:int8" in stats.strategy
    assert "backend:ringsim" in stats.strategy
    table = opt.plan(tree).describe()
    assert "int8" in table and "ringsim" in table
    # bf16 and int8 runs must be distinguishable in benchmark CSVs
    bf = DistributedOptimizer(adamw(1e-3), exchange=ExchangeConfig(
        sparse_as_dense=True, codec="bf16"))
    assert bf.exchange_stats(tree, 8).strategy != stats.strategy


# ---------------------------------------------------------------------------
# plan cache
# ---------------------------------------------------------------------------

def test_plan_cache_hits_on_same_structure():
    clear_plan_cache()
    cfg = ExchangeConfig(sparse_as_dense=True)
    t1 = _demo_tree(seed=0)
    t2 = _demo_tree(seed=1)           # same structure, different values
    p1 = compile_plan(t1, cfg)
    p2 = compile_plan(t2, cfg)
    assert p1 is p2
    info = plan_cache_info()
    assert info["hits"] == 1 and info["misses"] == 1

    # different shapes -> new plan
    t3 = _demo_tree(v=30, seed=2)
    p3 = compile_plan(t3, cfg)
    assert p3 is not p1
    # different config -> new plan
    p4 = compile_plan(t1, ExchangeConfig(sparse_as_dense=True,
                                         wire_dtype="bf16"))
    assert p4 is not p1
    assert plan_cache_info()["misses"] == 3


def test_exchange_stats_and_optimizer_share_one_plan():
    clear_plan_cache()
    opt = DistributedOptimizer(adamw(1e-3), sparse_as_dense=True)
    tree = _demo_tree()
    opt.exchange_stats(tree, n_workers=8)
    opt.exchange(tree)
    info = plan_cache_info()
    assert info["misses"] == 1 and info["hits"] >= 1


# ---------------------------------------------------------------------------
# local (axis_name=None) execution semantics
# ---------------------------------------------------------------------------

def test_plan_execute_matches_eager_accumulate_locally():
    tree = _demo_tree()
    ref = densify(accumulate_gradients(tree["emb"],
                                       sparse_as_dense=True))
    for kwargs in (dict(sparse_as_dense=True),
                   dict(sparse_as_dense=False),
                   dict(algorithm="proposed_algorithm2"),
                   dict(sparse_as_dense=True, fusion_threshold=1 << 20),
                   dict(sparse_as_dense=True, use_kernel=True)):
        opt = DistributedOptimizer(adamw(1e-3), **kwargs)
        out = opt.exchange(tree)
        np.testing.assert_allclose(np.asarray(out["emb"]),
                                   np.asarray(ref), rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(out["w"]),
                                      np.asarray(tree["w"]))
        assert out["emb"].dtype == jnp.float32


def test_wire_dtype_roundtrip_restores_leaf_dtype():
    tree = _demo_tree()
    opt = DistributedOptimizer(adamw(1e-3), sparse_as_dense=True,
                               wire_dtype="bf16")
    out = opt.exchange(tree)
    assert out["emb"].dtype == jnp.float32
    assert out["w"].dtype == jnp.float32
    ref = densify(accumulate_gradients(tree["emb"], sparse_as_dense=True))
    np.testing.assert_allclose(np.asarray(out["emb"]), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)   # bf16 tolerance


def test_plan_rejects_structure_change():
    opt = DistributedOptimizer(adamw(1e-3), sparse_as_dense=True)
    plan = opt.plan(_demo_tree())
    with pytest.raises(ValueError):
        plan.execute({"other": jnp.ones((3,))}, axis_name=None)


# ---------------------------------------------------------------------------
# multi-worker: plan-vs-eager equivalence, RS+bf16 vs fused allreduce,
# and the lowered-HLO collective audit
# ---------------------------------------------------------------------------

def test_plan_equals_eager_exchange_across_workers():
    """The planned exchange must produce exactly what the eager per-leaf
    loop (psum / allgather+densify) produces, for both strategies."""
    out = run_with_devices(textwrap.dedent("""
        import functools
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        from repro.core import (DistributedOptimizer, IndexedSlices,
                                accumulation, comm)
        from repro.optim import adamw

        V, D, N = 32, 16, 10
        P_ = len(jax.devices())
        mesh = Mesh(np.array(jax.devices()), ('data',))
        rng = np.random.default_rng(0)
        idx = jnp.asarray(rng.integers(0, V, (P_, N), dtype=np.int32))
        vals = jnp.asarray(rng.standard_normal((P_, N, D)), jnp.float32)
        dense = jnp.asarray(rng.standard_normal((P_, V, D)), jnp.float32)

        def eager_reduce(i, v, d):
            acc = accumulation.accumulate_gradients(
                [IndexedSlices(i[0], v[0], (V, D)), d[0]],
                sparse_as_dense=True)
            return comm.all_reduce_dense(acc, 'data')[None]

        def eager_gather(i, v, d):
            acc = accumulation.accumulate_gradients(
                [IndexedSlices(i[0], v[0], (V, D)), d[0]],
                algorithm='tf_algorithm1')
            g = comm.all_gather_slices(acc, 'data')
            return (accumulation.densify(g) / P_)[None]

        def planned(i, v, d, opt):
            g = {'e': [IndexedSlices(i[0], v[0], (V, D)), d[0]]}
            return opt.exchange(g)['e'][None]

        def run(fn):
            sm = jax.jit(shard_map(fn, mesh=mesh,
                                   in_specs=(P('data'),) * 3,
                                   out_specs=P('data'), check_vma=False))
            return np.asarray(sm(idx, vals, dense)[0])

        for sad, eager in [(True, eager_reduce), (False, eager_gather)]:
            opt = DistributedOptimizer(adamw(1e-3), sparse_as_dense=sad,
                                       axis_name=('data',))
            a = run(functools.partial(planned, opt=opt))
            b = run(eager)
            err = np.abs(a - b).max()
            assert err < 1e-6, (sad, err)
        print('OK')
    """))
    assert "OK" in out


def test_reduce_scatter_bf16_matches_fused_allreduce():
    """Acceptance: the RS+AG bf16-wire path equals the fused f32
    allreduce path within bf16 tolerance."""
    out = run_with_devices(textwrap.dedent("""
        import functools
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        from repro.core import DistributedOptimizer, IndexedSlices
        from repro.optim import adamw

        V, D, N = 32, 16, 10
        P_ = len(jax.devices())
        mesh = Mesh(np.array(jax.devices()), ('data',))
        rng = np.random.default_rng(0)
        idx = jnp.asarray(rng.integers(0, V, (P_, N), dtype=np.int32))
        vals = jnp.asarray(rng.standard_normal((P_, N, D)), jnp.float32)
        dense = jnp.asarray(rng.standard_normal((P_, V, D)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((P_, 37)), jnp.float32)

        def f(i, v, d, ww, opt):
            g = {'e': [IndexedSlices(i[0], v[0], (V, D)), d[0]],
                 'w': ww[0]}
            out = opt.exchange(g)
            return out['e'][None], out['w'][None]

        def run(opt):
            sm = jax.jit(shard_map(functools.partial(f, opt=opt),
                                   mesh=mesh, in_specs=(P('data'),) * 4,
                                   out_specs=P('data'), check_vma=False))
            e, ww = sm(idx, vals, dense, w)
            return np.asarray(e[0]), np.asarray(ww[0])

        base = DistributedOptimizer(adamw(1e-3), sparse_as_dense=True,
                                    axis_name=('data',),
                                    fusion_threshold=1 << 20)
        rs = DistributedOptimizer(adamw(1e-3), sparse_as_dense=True,
                                  axis_name=('data',),
                                  fusion_threshold=1 << 20,
                                  reduce_scatter=True, wire_dtype='bf16')
        (e0, w0), (e1, w1) = run(base), run(rs)
        scale = max(np.abs(e0).max(), 1.0)
        err = max(np.abs(e1 - e0).max(), np.abs(w1 - w0).max())
        assert err < 0.02 * scale, err           # bf16 tolerance
        assert e1.dtype == np.float32
        print('OK')
    """))
    assert "OK" in out


def test_hierarchical_two_level_psum_matches_flat():
    out = run_with_devices(textwrap.dedent("""
        import functools
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        from repro.core import DistributedOptimizer
        from repro.optim import adamw

        mesh = Mesh(np.array(jax.devices()).reshape(2, 4),
                    ('pod', 'data'))
        x = jnp.asarray(np.random.default_rng(0).standard_normal(
            (2, 4, 16, 8)), jnp.float32)

        def f(xx, opt):
            return opt.exchange({'w': xx[0, 0]})['w'][None, None]

        outs = {}
        for name, kw in [('flat', {}), ('two_level',
                                        dict(hierarchical=True))]:
            opt = DistributedOptimizer(adamw(1e-3), sparse_as_dense=True,
                                       axis_name=('pod', 'data'), **kw)
            sm = jax.jit(shard_map(functools.partial(f, opt=opt),
                                   mesh=mesh,
                                   in_specs=(P('pod', 'data'),),
                                   out_specs=P('pod', 'data'),
                                   check_vma=False))
            outs[name] = np.asarray(sm(x)[0, 0])
        err = np.abs(outs['flat'] - outs['two_level']).max()
        assert err < 1e-6, err
        np.testing.assert_allclose(outs['flat'],
                                   np.asarray(x.reshape(8, 16, 8)).mean(0),
                                   rtol=1e-5, atol=1e-6)
        print('OK')
    """))
    assert "OK" in out


def test_plan_collective_count_matches_lowered_hlo():
    """Planned n_collectives == collective launches in the lowered HLO
    (the dry-run audit contract, on a small synthetic tree)."""
    out = run_with_devices(textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        from repro.core import DistributedOptimizer, IndexedSlices
        from repro.launch import hlo as hlo_lib
        from repro.optim import adamw

        V, D, N = 32, 16, 10
        mesh = Mesh(np.array(jax.devices()), ('data',))
        rng = np.random.default_rng(0)
        tree = {'e': [IndexedSlices(
                    jnp.asarray(rng.integers(0, V, N, dtype=np.int32)),
                    jnp.ones((N, D), jnp.float32), (V, D))],
                'a': jnp.ones((8, 8), jnp.float32),
                'b': jnp.ones((3, 3), jnp.float32)}

        for kw, n_gather in [(dict(sparse_as_dense=True), 0),
                             (dict(sparse_as_dense=False), 1),
                             (dict(sparse_as_dense=True,
                                   fusion_threshold=1 << 20), 0)]:
            opt = DistributedOptimizer(adamw(1e-3), axis_name=('data',),
                                       **kw)
            plan = opt.plan(tree)
            sm = shard_map(opt.exchange, mesh=mesh, in_specs=(P(),),
                           out_specs=P(), check_vma=False)
            hlo = jax.jit(sm).lower(tree).compile().as_text()
            counts = hlo_lib.count_collectives(hlo)
            # one gather bucket lowers to TWO all-gathers (idx + values)
            expected = plan.n_collectives + n_gather
            assert sum(counts.values()) == expected, (kw, counts,
                                                      plan.n_collectives)
        print('OK')
    """))
    assert "OK" in out


def test_plan_equals_eager_for_every_codec_backend_pair():
    """Acceptance: the planned exchange matches the eager dense-reduce
    reference for EVERY (codec, backend) pair in the registries, under
    shard_map, within each codec's tolerance — and the lowered HLO
    contains exactly ``plan.hlo_collectives(P)`` collective ops."""
    out = run_with_devices(textwrap.dedent("""
        import functools
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        from repro.core import (DistributedOptimizer, ExchangeConfig,
                                IndexedSlices, available_backends,
                                available_codecs)
        from repro.launch import hlo as hlo_lib
        from repro.optim import adamw

        V, D, N = 32, 16, 10
        P_ = len(jax.devices())
        rng = np.random.default_rng(0)
        idx = jnp.asarray(rng.integers(0, V, (P_, N), dtype=np.int32))
        vals = jnp.asarray(rng.standard_normal((P_, N, D)), jnp.float32)
        dense = jnp.asarray(rng.standard_normal((P_, V, D)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((P_, 37)), jnp.float32)

        def f(i, v, d, ww, opt):
            g = {'e': [IndexedSlices(i[0], v[0], (V, D)), d[0]],
                 'w': ww[0]}
            out = opt.exchange(g)
            return out['e'][None], out['w'][None]

        def run(opt, mesh, spec):
            sm = jax.jit(shard_map(functools.partial(f, opt=opt),
                                   mesh=mesh, in_specs=(spec,) * 4,
                                   out_specs=spec, check_vma=False))
            hlo = sm.lower(idx, vals, dense, w).compile().as_text()
            e, ww = sm(idx, vals, dense, w)
            return np.asarray(e)[0], np.asarray(ww)[0], hlo

        flat = Mesh(np.array(jax.devices()), ('data',))
        ref = DistributedOptimizer(
            adamw(1e-3), exchange=ExchangeConfig(sparse_as_dense=True),
            axis_name=('data',))
        e_ref, w_ref, _ = run(ref, flat, P('data'))
        tols = {'identity': 1e-5, 'bf16': 2e-2, 'f16': 2e-2,
                'int8': 2e-2,
                # fp8 casts: 3 / 2 mantissa bits -> rel eps 2^-4 / 2^-3
                # of the O(1) test values, absolute bound with margin
                'f8e4m3': 0.5, 'f8e5m2': 1.0}

        n_pairs = 0
        for codec in available_codecs():
            for be in available_backends():
                if be == 'hierarchical':
                    mesh = Mesh(np.array(jax.devices()).reshape(2, 4),
                                ('pod', 'data'))
                    axis, spec = ('pod', 'data'), P(('pod', 'data'))
                    workers = (2, 4)
                else:
                    mesh, axis, spec, workers = (flat, ('data',),
                                                 P('data'), P_)
                opt = DistributedOptimizer(
                    adamw(1e-3),
                    exchange=ExchangeConfig(sparse_as_dense=True,
                                            codec=codec, backend=be,
                                            fusion_threshold=1 << 20),
                    axis_name=axis)
                e, ww, hlo = run(opt, mesh, spec)
                err = max(np.abs(e - e_ref).max(),
                          np.abs(ww - w_ref).max())
                assert err < tols[codec], (codec, be, err)
                plan = opt.plan({'e': [IndexedSlices(idx[0], vals[0],
                                                     (V, D)), dense[0]],
                                 'w': w[0]})
                counts = hlo_lib.count_collectives(hlo)
                assert sum(counts.values()) == \
                    plan.hlo_collectives(workers), (codec, be, counts)
                n_pairs += 1
        assert n_pairs >= 9, n_pairs
        print('PAIRS_OK', n_pairs)
    """))
    assert "PAIRS_OK" in out


def test_broadcast_params_backend_hot_swap_across_workers():
    """Serving weight hot-swap: params broadcast from worker 0 through
    the plan bucketing lands on every worker, for a codec/backend mix."""
    out = run_with_devices(textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        from repro.serving import broadcast_params, broadcast_plan

        rng = np.random.default_rng(0)
        params = {'w1': jnp.asarray(rng.standard_normal((32, 16)),
                                    jnp.float32),
                  'w2': jnp.asarray(rng.standard_normal((7,)),
                                    jnp.float32)}
        stale = jax.tree_util.tree_map(jnp.zeros_like, params)
        mesh = Mesh(np.array(jax.devices()), ('data',))
        P_ = len(jax.devices())
        flags = jnp.asarray([1] + [0] * (P_ - 1), jnp.int32)[:, None]

        for codec, be in [('identity', 'jax'), ('bf16', 'ringsim'),
                          ('int8', 'jax')]:
            plan = broadcast_plan(params, codec=codec, backend=be)
            def f(root_flag, fresh, stale):
                mine = jax.tree_util.tree_map(
                    lambda a, b: jnp.where(root_flag[0] > 0, a, b),
                    fresh, stale)
                out = broadcast_params(mine, plan=plan,
                                       axis_name=('data',))
                return jax.tree_util.tree_map(lambda x: x[None], out)
            sm = jax.jit(shard_map(f, mesh=mesh,
                                   in_specs=(P('data'), P(), P()),
                                   out_specs=P('data'), check_vma=False))
            got = sm(flags, params, stale)
            tol = {'identity': 0.0, 'bf16': 2e-2, 'int8': 2e-2}[codec]
            for k in params:
                g = np.asarray(got[k])
                want = np.broadcast_to(np.asarray(params[k])[None],
                                       g.shape)
                assert np.abs(g - want).max() <= tol, (codec, be, k)
        print('OK')
    """))
    assert "OK" in out


def test_broadcast_params_rejects_codec_backend_plan_mismatch():
    from repro.serving import broadcast_params, broadcast_plan
    params = {"w": jnp.ones((8, 8), jnp.float32)}
    plan = broadcast_plan(params, codec="int8")
    with pytest.raises(ValueError):
        broadcast_params(params, plan=plan, codec="identity")
    with pytest.raises(ValueError):
        broadcast_params(params, plan=plan, backend="ringsim")
    out = broadcast_params(params, plan=plan)          # local round-trip
    assert float(jnp.abs(out["w"] - params["w"]).max()) <= 1.0 / 127


def test_int8_codec_n_collectives_counts_values_and_scales():
    tree = {"a": jnp.ones((16, 16), jnp.float32),
            "b": jnp.ones((4, 4), jnp.float32)}
    lin = compile_plan(tree, ExchangeConfig(sparse_as_dense=True))
    q8 = compile_plan(tree, ExchangeConfig(sparse_as_dense=True,
                                           codec="int8"))
    assert lin.n_collectives == 2              # one psum per bucket
    assert q8.n_collectives == 4               # values + scales each
    assert q8.hlo_collectives(8) == 4


def test_gspmd_audit_backend_reports_compiler_collectives():
    """ROADMAP item: the exchange audit runs on the GSPMD (non-shard_map)
    path and the partitioner's chosen collectives are reported next to
    the plan's schedule."""
    out = run_with_devices(textwrap.dedent("""
        from repro.launch.dryrun import audit_exchange_gspmd
        r = audit_exchange_gspmd(arch='transformer-big', n_workers=8)
        assert r['audit_mode'] == 'gspmd', r
        assert r['collectives_found'], r
        assert r['counts_match'], r
        # on the reduced config the partitioner picks exactly the
        # planned per-leaf all-reduces
        assert r['collective_delta'] == 0, r
        assert abs(r['wire_ratio'] - 1.0) < 1e-6, r
        print('OK')
    """), n=8)
    assert "OK" in out


# ---------------------------------------------------------------------------
# BucketSchedule: staged execution, readiness/ordering, overlap
# ---------------------------------------------------------------------------

def _multi_bucket_tree(seed=0, n_dense=6):
    """A tree the fusion planner splits into several buckets (per-leaf
    bucketing) plus one sparse gather leaf."""
    rng = np.random.default_rng(seed)
    tree = {f"w{i}": jnp.asarray(rng.standard_normal((16 + i, 8)),
                                 jnp.float32)
            for i in range(n_dense)}
    tree["emb"] = [IndexedSlices(
        jnp.asarray(rng.integers(0, 24, 6, dtype=np.int32)),
        jnp.asarray(rng.standard_normal((6, 8)), jnp.float32), (24, 8)),
        jnp.asarray(rng.standard_normal((24, 8)), jnp.float32)]
    return tree


def test_schedule_stages_partition_leaves_in_reverse_layer_order():
    """Every bucket is exactly one stage; stage leaf sets partition the
    grad tree; launch order is descending readiness key; per-stage
    accounting sums to the fused plan totals."""
    tree = _multi_bucket_tree()
    for cfg in (ExchangeConfig(sparse_as_dense=True),
                ExchangeConfig(),                       # gather leaf
                ExchangeConfig(sparse_as_dense=True, codec="int8"),
                ExchangeConfig(sparse_as_dense=True,
                               fusion_threshold=1 << 20)):
        plan = compile_plan(tree, cfg)
        sch = plan.schedule
        assert sch.n_stages == plan.n_buckets
        covered = sorted(i for st in sch.stages for i in st.leaf_ids)
        assert covered == list(range(plan.n_leaves))
        keys = [st.ready_key for st in sch.stages]
        assert keys == sorted(keys, reverse=True)       # reverse-layer
        assert sum(plan.stage_collectives(st) for st in sch.stages) \
            == plan.n_collectives
        assert sum(plan.stage_wire_bytes(st, 8) for st in sch.stages) \
            == plan.wire_bytes(8)
        assert sum(plan.stage_hlo_collectives(st, 8)
                   for st in sch.stages) == plan.hlo_collectives(8)


@given(shape_mixes())
@settings(max_examples=30, deadline=None)
def test_schedule_properties_hold_for_random_trees(tree):
    plan = compile_plan(tree, ExchangeConfig(algorithm="tf_algorithm1"))
    sch = plan.schedule
    covered = sorted(i for st in sch.stages for i in st.leaf_ids)
    assert covered == list(range(plan.n_leaves))
    keys = [st.ready_key for st in sch.stages]
    assert keys == sorted(keys, reverse=True)
    assert sum(plan.stage_collectives(st) for st in sch.stages) \
        == plan.n_collectives
    assert sum(plan.stage_wire_bytes(st, 8) for st in sch.stages) \
        == plan.wire_bytes(8)


def test_staged_execute_is_bitwise_identical_locally():
    """Acceptance: overlap=True must produce numerically IDENTICAL
    updates — bitwise for linear codecs (identity / bf16 / fp8), within
    the quantisation bound for int8."""
    tree = _multi_bucket_tree()
    cast_codecs = ["identity", "bf16"]
    if "f8e4m3" in available_codecs():       # fp8 needs native jax float8
        cast_codecs.append("f8e4m3")
    for codec in cast_codecs:
        fused = DistributedOptimizer(adamw(1e-3), exchange=ExchangeConfig(
            sparse_as_dense=True, codec=codec)).exchange(tree)
        staged = DistributedOptimizer(adamw(1e-3), exchange=ExchangeConfig(
            sparse_as_dense=True, codec=codec, overlap=True)
        ).exchange(tree)
        for a, b in zip(jax.tree_util.tree_leaves(fused),
                        jax.tree_util.tree_leaves(staged)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    q_f = DistributedOptimizer(adamw(1e-3), exchange=ExchangeConfig(
        sparse_as_dense=True, codec="int8")).exchange(tree)
    q_s = DistributedOptimizer(adamw(1e-3), exchange=ExchangeConfig(
        sparse_as_dense=True, codec="int8", overlap=True)).exchange(tree)
    for a, b in zip(jax.tree_util.tree_leaves(q_f),
                    jax.tree_util.tree_leaves(q_s)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_execute_scheduled_and_fused_methods_share_one_schedule():
    """execute()/execute_fused()/execute_scheduled() are all the same
    per-stage ops; overlap only changes the launch/finish interleaving,
    so all three agree bitwise on the local path."""
    tree = _multi_bucket_tree()
    opt = DistributedOptimizer(adamw(1e-3),
                               exchange=ExchangeConfig(sparse_as_dense=True))
    a = opt.exchange(tree)
    b = opt.exchange_scheduled(tree)
    c = opt.exchange_fused(tree)
    for x, y, z in zip(*(jax.tree_util.tree_leaves(t) for t in (a, b, c))):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        np.testing.assert_array_equal(np.asarray(x), np.asarray(z))


def test_exchange_stats_describe_reports_schedule():
    tree = _multi_bucket_tree()
    opt = DistributedOptimizer(adamw(1e-3), exchange=ExchangeConfig(
        sparse_as_dense=True, overlap=True))
    stats = opt.exchange_stats(tree, n_workers=8)
    assert stats.n_stages == opt.plan(tree).n_buckets
    assert stats.overlap
    assert "+overlap" in stats.strategy
    text = stats.describe()
    assert "overlap=on" in text
    assert f"{stats.n_stages} stages" in text
    assert "ready@" in text and "wire B" in text
    fused = DistributedOptimizer(adamw(1e-3), exchange=ExchangeConfig(
        sparse_as_dense=True))
    assert "overlap=off" in fused.exchange_stats(tree, 8).describe()


def test_overlap_equals_fused_across_workers_bitwise():
    """Acceptance: under shard_map on 8 workers the staged schedule
    produces BITWISE the fused result for linear codecs, lowers to
    exactly plan.hlo_collectives(P) collective ops, and its per-stage
    collective counts sum to the fused plan's n_collectives."""
    out = run_with_devices(textwrap.dedent("""
        import functools
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        from repro.core import (DistributedOptimizer, ExchangeConfig,
                                IndexedSlices)
        from repro.launch import hlo as hlo_lib
        from repro.optim import adamw

        V, D, N = 32, 16, 10
        P_ = len(jax.devices())
        mesh = Mesh(np.array(jax.devices()), ('data',))
        rng = np.random.default_rng(0)
        idx = jnp.asarray(rng.integers(0, V, (P_, N), dtype=np.int32))
        vals = jnp.asarray(rng.standard_normal((P_, N, D)), jnp.float32)
        dense = jnp.asarray(rng.standard_normal((P_, V, D)), jnp.float32)
        ws = jnp.asarray(rng.standard_normal((P_, 6, 40, 8)), jnp.float32)

        def f(i, v, d, w, opt):
            g = {'e': [IndexedSlices(i[0], v[0], (V, D)), d[0]]}
            for k in range(6):
                g['w%d' % k] = w[0, k]
            out = opt.exchange(g)
            return out['e'][None], jnp.stack(
                [out['w%d' % k] for k in range(6)])[None]

        def run(opt):
            sm = jax.jit(shard_map(functools.partial(f, opt=opt),
                                   mesh=mesh, in_specs=(P('data'),) * 4,
                                   out_specs=P('data'), check_vma=False))
            hlo = sm.lower(idx, vals, dense, ws).compile().as_text()
            e, w = sm(idx, vals, dense, ws)
            return np.asarray(e)[0], np.asarray(w)[0], hlo

        tree = {'e': [IndexedSlices(idx[0], vals[0], (V, D)), dense[0]]}
        for k in range(6):
            tree['w%d' % k] = ws[0, k]

        for codec in ('identity', 'bf16'):
            for sad in (True, False):
                base = ExchangeConfig(sparse_as_dense=sad, codec=codec)
                ov = ExchangeConfig(sparse_as_dense=sad, codec=codec,
                                    overlap=True)
                o_f = DistributedOptimizer(adamw(1e-3), exchange=base,
                                           axis_name=('data',))
                o_s = DistributedOptimizer(adamw(1e-3), exchange=ov,
                                           axis_name=('data',))
                e0, w0, _ = run(o_f)
                e1, w1, hlo = run(o_s)
                assert np.array_equal(e0, e1), (codec, sad)
                assert np.array_equal(w0, w1), (codec, sad)
                plan = o_s.plan(tree)
                counts = hlo_lib.count_collectives(hlo)
                assert sum(counts.values()) == plan.hlo_collectives(P_), \
                    (codec, sad, counts)
                fused_plan = o_f.plan(tree)
                stage_sum = sum(plan.stage_collectives(s)
                                for s in plan.schedule.stages)
                assert stage_sum == fused_plan.n_collectives, (codec, sad)
        print('OK')
    """))
    assert "OK" in out


# ---------------------------------------------------------------------------
# fp8 codecs (f8e4m3 / f8e5m2 on the cast-codec path)
# ---------------------------------------------------------------------------

def _require_fp8():
    """fp8 codecs register only when the installed jax exposes native
    float8 dtypes (the codecs.py graceful-degradation contract)."""
    if "f8e4m3" not in available_codecs():
        pytest.skip("installed jax has no native float8 dtypes")


def test_fp8_codec_roundtrip_error_bounds():
    """e4m3 (3 mantissa bits) and e5m2 (2 bits) round-trip within their
    per-element relative eps; both are linear (no side scales) and bill
    1 byte/element on the wire."""
    _require_fp8()
    assert {"f8e4m3", "f8e5m2"} <= set(available_codecs())
    rng = np.random.default_rng(0)
    buf = np.asarray(rng.standard_normal(4000) * 3.0, np.float32)
    for name, rel, floor in (("f8e4m3", 2.0 ** -4, 2.0 ** -9),
                             ("f8e5m2", 2.0 ** -3, 2.0 ** -16)):
        codec = get_codec(name)
        assert codec.linear and codec.scale_bytes == 0
        assert codec.wire_bytes(1000, "float32") == 1000
        wire, side = codec.encode(jnp.asarray(buf))
        assert side is None
        assert jnp.dtype(wire.dtype).itemsize == 1
        out = np.asarray(codec.decode(wire, None, jnp.float32))
        err = np.abs(out - buf)
        assert (err <= rel * np.abs(buf) + floor).all(), \
            (name, float(err.max()))
    # dtype-ish spellings resolve to the same registered codec
    assert get_codec("float8_e4m3fn") is get_codec("f8e4m3")
    assert get_codec("f8e5m2") is get_codec("fp8e5m2")


def test_fp8_codec_quarters_dense_wire_and_executes():
    _require_fp8()
    tree = {"w": jnp.ones((64, 64), jnp.float32)}
    f32 = compile_plan(tree, ExchangeConfig(sparse_as_dense=True))
    f8 = compile_plan(tree, ExchangeConfig(sparse_as_dense=True,
                                           codec="f8e4m3"))
    assert f8.wire_bytes(8) == f32.wire_bytes(8) // 4
    # the accumulated representation stays f32 (upcast on unpack)
    assert f8.buffer_bytes(8) == f32.buffer_bytes(8)
    tree = _demo_tree()
    opt = DistributedOptimizer(adamw(1e-3), exchange=ExchangeConfig(
        sparse_as_dense=True, codec="f8e4m3"))
    out = opt.exchange(tree)
    ref = densify(accumulate_gradients(tree["emb"], sparse_as_dense=True))
    assert out["emb"].dtype == jnp.float32
    bound = float(jnp.abs(ref).max()) * 2.0 ** -3 + 2.0 ** -8
    assert float(jnp.abs(out["emb"] - ref).max()) <= bound


@pytest.mark.slow
def test_dryrun_exchange_audit_reduced_transformer_big():
    """Acceptance: the full audit on the reduced transformer-big config
    — planned wire_bytes / n_collectives agree with the HLO audit."""
    out = run_with_devices(textwrap.dedent("""
        import json
        from repro.launch.dryrun import audit_exchange_plan
        r = audit_exchange_plan(arch='transformer-big', n_workers=8)
        assert r['counts_match'], r
        assert abs(r['wire_ratio'] - 1.0) < 1e-6, r
        r2 = audit_exchange_plan(arch='transformer-big', n_workers=8,
                                 sparse_as_dense=False)
        assert r2['counts_match'], r2
        assert abs(r2['wire_ratio'] - 1.0) < 1e-6, r2
        # acceptance: int8 codec on the hierarchical backend — planned
        # wire must match the codec's accounting exactly
        r3 = audit_exchange_plan(arch='transformer-big', n_workers=8,
                                 codec='int8', backend='hierarchical')
        assert r3['counts_match'], r3
        assert abs(r3['wire_ratio'] - 1.0) < 1e-6, r3
        # acceptance: the staged overlap path lowers to the SAME HLO
        # collective count and its per-stage counts sum to the fused
        # plan's n_collectives
        r4 = audit_exchange_plan(arch='transformer-big', n_workers=8,
                                 overlap=True)
        assert r4['overlap'] and r4['counts_match'], r4
        assert r4['schedule']['stage_sum_matches_fused'], r4
        assert r4['schedule']['n_stages'] > 1, r4
        assert r4['hlo_ops'] == r['hlo_ops'], (r4['hlo_ops'], r['hlo_ops'])
        assert abs(r4['wire_ratio'] - 1.0) < 1e-6, r4
        print('OK')
    """), n=8)
    assert "OK" in out
