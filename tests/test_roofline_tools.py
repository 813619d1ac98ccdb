"""Roofline tooling: jaxpr flop counter + HLO collective analyzer."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import flops as flops_lib
from repro.launch import hlo as hlo_lib

jax.config.update("jax_platform_name", "cpu")


def test_dot_flops_exact():
    def f(a, b):
        return a @ b
    a = jax.ShapeDtypeStruct((64, 32), jnp.float32)
    b = jax.ShapeDtypeStruct((32, 128), jnp.float32)
    c = flops_lib.count_fn_flops(f, a, b)
    assert c["flops"] == 2 * 64 * 32 * 128


def test_scan_multiplies_trip_count():
    def f(x, w):
        def body(c, wi):
            return c @ wi, None
        y, _ = jax.lax.scan(body, x, w)
        return y
    x = jax.ShapeDtypeStruct((8, 16), jnp.float32)
    w = jax.ShapeDtypeStruct((12, 16, 16), jnp.float32)
    c = flops_lib.count_fn_flops(f, x, w)
    assert c["flops"] >= 12 * 2 * 8 * 16 * 16
    assert c["flops"] < 13 * 2 * 8 * 16 * 16


def test_nested_scan_multiplies():
    def f(x, w):
        def outer(c, wo):
            def inner(ci, wi):
                return ci @ wi, None
            c, _ = jax.lax.scan(inner, c, wo)
            return c, None
        y, _ = jax.lax.scan(outer, x, w)
        return y
    x = jax.ShapeDtypeStruct((4, 8), jnp.float32)
    w = jax.ShapeDtypeStruct((3, 5, 8, 8), jnp.float32)
    c = flops_lib.count_fn_flops(f, x, w)
    base = 2 * 4 * 8 * 8
    assert c["flops"] == pytest.approx(15 * base, rel=0.01)


def test_remat_counted():
    def f(w, x):
        def blk(wi, c):
            return jnp.tanh(c @ wi)

        def body(c, wi):
            return jax.checkpoint(blk)(wi, c), None
        y, _ = jax.lax.scan(body, x, w)
        return y.sum()
    w = jax.ShapeDtypeStruct((4, 64, 64), jnp.float32)
    x = jax.ShapeDtypeStruct((8, 64), jnp.float32)
    c = flops_lib.count_fn_flops(lambda w, x: jax.grad(f)(w, x), w, x)
    fwd = 2 * 8 * 64 * 64 * 4
    # fwd + remat recompute + 2 bwd matmuls ~= 4x fwd
    assert 3.5 * fwd < c["flops"] < 4.6 * fwd


def test_grad_flops_approx_3x_forward():
    def f(w, x):
        return jnp.sum(jnp.tanh(x @ w))
    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    x = jax.ShapeDtypeStruct((32, 64), jnp.float32)
    fwd = flops_lib.count_fn_flops(f, w, x)["flops"]
    bwd = flops_lib.count_fn_flops(
        lambda w, x: jax.grad(f, argnums=(0, 1))(w, x), w, x)["flops"]
    assert 2.5 < bwd / fwd < 3.6


def test_model_flops_close_to_6nd():
    """End-to-end sanity: jaxpr count vs 6*N*D for a dense reduced arch."""
    from repro.configs import get_config
    from repro.models import build_model
    from repro.launch.dryrun import param_counts

    cfg = get_config("deepseek-7b").reduced()
    model = build_model(cfg)
    params = jax.eval_shape(model.init,
                            jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    b, s = 4, 64
    batch = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32),
             "labels": jax.ShapeDtypeStruct((b, s), jnp.int32)}

    def loss_grads(p, b):
        return jax.grad(lambda pp: model.loss(pp, b)[0])(p)

    counted = flops_lib.count_fn_flops(loss_grads, params, batch)["flops"]
    n_total, n_active = param_counts(cfg)
    expected = 6 * n_active * b * s
    # embedding rows are lookups not matmuls, attention adds quadratic
    # terms: allow a factor-2 band
    assert 0.5 < counted / expected < 2.2, (counted, expected)


# ---------------------------------------------------------------------------
# HLO analyzer
# ---------------------------------------------------------------------------

def test_hlo_collective_bytes_psum():
    import subprocess, sys, os, textwrap
    # needs >1 device -> subprocess
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        from repro.launch import hlo as hlo_lib
        mesh = Mesh(np.array(jax.devices()), ('d',))
        def f(x):
            return jax.lax.psum(x, 'd')
        sm = shard_map(f, mesh=mesh, in_specs=P('d'), out_specs=P())
        lowered = jax.jit(sm).lower(
            jax.ShapeDtypeStruct((8, 128), jnp.float32))
        hlo = lowered.compile().as_text()
        stats = hlo_lib.analyze_collectives(hlo)
        stats.pop('__bytes__', None)
        print('AR', stats.get('all-reduce', 0))
    """)
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(
                   os.path.dirname(os.path.dirname(
                       os.path.abspath(__file__))), "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    ar = float(out.stdout.split("AR")[1].strip())
    # per-device shard is (1,128) f32 -> 512B result per all-reduce
    assert ar >= 512


def test_hlo_while_trip_count_multiplication():
    hlo = """
HloModule test

%body_1 (p: (s32[], f32[128])) -> (s32[], f32[128]) {
  %ar = f32[128]{0} all-reduce(%x), to_apply=%add_0
  ROOT %t = (s32[], f32[128]) tuple(%i, %ar)
}

%cond_1 (p: (s32[], f32[128])) -> pred[] {
  %limit = s32[] constant(16)
  ROOT %cmp = pred[] compare(%i, %limit), direction=LT
}

ENTRY %main (a: f32[128]) -> f32[128] {
  %w = (s32[], f32[128]) while(%init), condition=%cond_1, body=%body_1
  ROOT %out = f32[128]{0} get-tuple-element(%w), index=1
}
"""
    stats = hlo_lib.analyze_collectives(hlo)
    assert stats.get("all-reduce", 0) == 16 * 128 * 4


# one combined all-reduce in the layout syntax of a compiled v5e program,
# then two plain all-gathers and an async pair
_COMBINED_TPU_HLO = """
ENTRY %main (p0: bf16[1024], p1: bf16[256], p2: bf16[256]) -> bf16[1024] {
  %all-reduce.2 = (bf16[1024]{0:T(1024)(128)(2,1)S(1)}, bf16[256]{0:T(256)}, bf16[256]{0:T(256)}) all-reduce(%p0, %p1, %p2), channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%add
  %all-gather.1 = s32[16]{0:T(256)} all-gather(%i0), dimensions={0}
  %all-gather.2 = f32[16,8]{1,0:T(8,128)} all-gather(%v0), dimensions={0}
  %ars = f32[8]{0} all-reduce-start(%x), to_apply=%add
  %ard = f32[8]{0} all-reduce-done(%ars)
  ROOT %gte = bf16[1024]{0} get-tuple-element(%all-reduce.2), index=0
}
"""


def test_count_collectives_counts_combined_operands():
    """A combined collective counts once per operand, so the count is
    what the program emitted whether or not XLA merged it; TPU tuple
    layouts with their own parentheses parse."""
    counts = hlo_lib.count_collectives(_COMBINED_TPU_HLO)
    assert counts == {"all-reduce": 4, "all-gather": 2}
    coll = hlo_lib.analyze_collectives(_COMBINED_TPU_HLO)
    assert coll["all-reduce"] == (1024 + 256 + 256) * 2 + 8 * 4
    assert coll["all-gather"] == 16 * 4 + 16 * 8 * 4
