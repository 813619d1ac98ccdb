"""Multi-worker collective semantics, on 8 emulated CPU devices.

These run in a SUBPROCESS because device count must be fixed before jax
initializes (the main test process keeps 1 device, per the dry-run-only
rule for multi-device flags).
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with_devices(code: str, n: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_sparse_gather_equals_dense_reduce_across_workers():
    """The paper's central claim: switching the collective from gather to
    reduce changes memory/time but NOT the resulting update."""
    out = run_with_devices(textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        from repro.configs import get_config
        from repro.models import build_model
        from repro.core import DistributedOptimizer
        from repro.optim import adamw
        from repro.training import make_train_step
        from repro.data import make_pipeline

        cfg = get_config('llama3.2-1b').reduced()   # tied embeddings
        m = build_model(cfg)
        params = m.init(jax.random.PRNGKey(0))
        mesh = Mesh(np.array(jax.devices()), ('data',))
        pipe = make_pipeline(cfg, batch_per_host=16, seq_len=16)
        batch = {k: jnp.asarray(v) for k, v in pipe.batch_at(0).items()}

        results = {}
        for name, sad in [('sparse_gather', False), ('dense_reduce', True)]:
            opt = DistributedOptimizer(adamw(1e-2), sparse_as_dense=sad,
                                       algorithm='tf_algorithm1',
                                       axis_name=('data',))
            step = make_train_step(m, opt, sparse_embedding=True)
            sm = shard_map(step, mesh=mesh,
                           in_specs=(P(), P(), P('data')),
                           out_specs=(P(), P(), P()), check_vma=False)
            p, s, met = jax.jit(sm)(params, opt.init(params), batch)
            results[name] = p
        diffs = [float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                       - b.astype(jnp.float32))))
                 for a, b in zip(
                     jax.tree_util.tree_leaves(results['sparse_gather']),
                     jax.tree_util.tree_leaves(results['dense_reduce']))]
        print('MAXDIFF', max(diffs))
    """))
    maxdiff = float(out.split("MAXDIFF")[1].strip())
    assert maxdiff < 1e-5


def test_allgather_slices_concatenates_across_workers():
    out = run_with_devices(textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        from repro.core import comm, IndexedSlices

        mesh = Mesh(np.array(jax.devices()), ('data',))
        def f(idx, vals):
            s = IndexedSlices(idx[0], vals[0], (16, 2))
            g = comm.all_gather_slices(s, 'data')
            return g.indices[None], g.values[None]
        idx = jnp.tile(jnp.arange(3, dtype=jnp.int32)[None], (8, 1))
        idx = idx + 2 * jnp.arange(8, dtype=jnp.int32)[:, None]
        vals = jnp.ones((8, 3, 2)) * jnp.arange(8.)[:, None, None]
        gi, gv = jax.jit(shard_map(f, mesh=mesh,
                                   in_specs=(P('data'), P('data')),
                                   out_specs=P('data'),
                                   check_vma=False))(idx, vals)
        print('ROWS', gi.shape, gv.shape)
        # every worker holds all 8*3 rows
        assert gi.shape == (8, 24) and gv.shape == (8, 24, 2)
        np.testing.assert_array_equal(np.asarray(gi[0]), np.asarray(gi[5]))
        print('OK')
    """))
    assert "OK" in out


def test_psum_matches_local_sum():
    out = run_with_devices(textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        from repro.core import comm

        mesh = Mesh(np.array(jax.devices()), ('data',))
        x = jnp.arange(8.0 * 4).reshape(8, 4)
        def f(xx):
            return comm.all_reduce_dense(xx[0], 'data', average=False)[None]
        out = jax.jit(shard_map(f, mesh=mesh, in_specs=(P('data'),),
                                out_specs=P('data'), check_vma=False))(x)
        np.testing.assert_allclose(np.asarray(out[0]),
                                   np.asarray(x.sum(0)), rtol=1e-6)
        print('OK')
    """))
    assert "OK" in out


def test_fused_allreduce_multi_device():
    out = run_with_devices(textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        from repro.core import fusion

        mesh = Mesh(np.array(jax.devices()), ('data',))
        tree = {'a': jnp.ones((8, 3, 3)), 'b': jnp.ones((8, 7))}
        def f(t):
            local = {k: v[0] for k, v in t.items()}
            out = fusion.fused_all_reduce(local, 'data',
                                          threshold_bytes=1 << 16,
                                          average=True)
            return {k: v[None] for k, v in out.items()}
        out = jax.jit(shard_map(f, mesh=mesh, in_specs=(P('data'),),
                                out_specs=P('data'), check_vma=False))(tree)
        np.testing.assert_allclose(np.asarray(out['a'][0]),
                                   np.ones((3, 3)), rtol=1e-6)
        print('OK')
    """))
    assert "OK" in out
