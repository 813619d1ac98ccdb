"""Compile-only checks: the Pallas kernels lower to Mosaic for a TPU v5e.

Nothing runs.  Each kernel is compiled natively (``interpret=False``)
for one chip of a described ``v5e:2x2`` topology at transformer-big
shapes, and the compiled program must hold a ``tpu_custom_call`` — the
refusals interpret mode cannot show (tile alignment, block layouts,
unsupported primitives) fail here instead of on the chip.  The topology
is described inside a fixture, so a process that cannot load the TPU
compiler skips these tests and every other process collects the same
tests.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.densify import densify_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.quantize import quantize_pallas
from repro.kernels.ssd import ssd_pallas

# transformer-big: 16 x 256 tokens per chip, 16 heads of 64, vocab 33708
# padded by ops.densify to its 512-row tile (34304), d_model 1024
TOKENS, VOCAB_PADDED, D_MODEL = 4096, 34304, 1024
BATCH, HEADS, SEQ, HEAD_DIM, FRAMES = 16, 16, 256, 64, 256
BH = BATCH * HEADS


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but never read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_has_kernel(fn, *shapes):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_densify_compiles_for_v5e(one_chip, dtype):
    _compile_has_kernel(
        lambda i, v: densify_pallas(i, v, (VOCAB_PADDED, D_MODEL),
                                    interpret=False),
        jax.ShapeDtypeStruct((TOKENS,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((TOKENS, D_MODEL), dtype, sharding=one_chip))


def test_quantize_compiles_for_v5e(one_chip):
    _compile_has_kernel(
        lambda x, s: quantize_pallas(x, s, interpret=False),
        jax.ShapeDtypeStruct((1 << 22,), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip))


@pytest.mark.parametrize("causal", [True, False],
                         ids=["causal_self", "cross"])
def test_flash_attention_compiles_for_v5e(one_chip, causal):
    qkv = jax.ShapeDtypeStruct((BH, SEQ, HEAD_DIM), jnp.bfloat16,
                               sharding=one_chip)
    _compile_has_kernel(
        lambda q, k, v: flash_attention_pallas(q, k, v, causal=causal,
                                               interpret=False),
        qkv, qkv, qkv)


def test_ssd_compiles_for_v5e(one_chip):
    bh, s, p, n, chunk = 16, 512, 64, 128, 128
    f32 = jnp.float32
    _compile_has_kernel(
        lambda x, dt, a, b, c: ssd_pallas(x, dt, a, b, c, chunk,
                                          interpret=False),
        jax.ShapeDtypeStruct((bh, s, p), f32, sharding=one_chip),
        jax.ShapeDtypeStruct((bh, s), f32, sharding=one_chip),
        jax.ShapeDtypeStruct((bh,), f32, sharding=one_chip),
        jax.ShapeDtypeStruct((bh, s, n), f32, sharding=one_chip),
        jax.ShapeDtypeStruct((bh, s, n), f32, sharding=one_chip))


def test_one_block_attention_moves_fewer_bytes_for_v5e(one_chip):
    """The gradient of one causal self-attention and one cross-attention
    call at transformer-big shapes compiles for v5e on the one-block
    path, and moves fewer bytes (XLA's cost analysis) than the same
    calls forced onto the kv scan.  Relative, so no libtpu version's
    absolute count is pinned."""
    def grad_bytes(block_k):
        def loss(q, k, v, kx, vx):
            a = ops.flash_attention(q, k, v, causal=True,
                                    impl="xla_chunked", block_k=block_k)
            c = ops.flash_attention(a, kx, vx, causal=False,
                                    impl="xla_chunked", block_k=block_k)
            return jnp.sum(c.astype(jnp.float32))

        x = jax.ShapeDtypeStruct((BATCH, SEQ, HEADS, HEAD_DIM),
                                 jnp.bfloat16, sharding=one_chip)
        xf = jax.ShapeDtypeStruct((BATCH, FRAMES, HEADS, HEAD_DIM),
                                  jnp.bfloat16, sharding=one_chip)
        compiled = jax.jit(jax.grad(loss, argnums=range(5))).lower(
            x, x, x, xf, xf).compile()
        cost = compiled.cost_analysis()
        cost = cost[0] if isinstance(cost, list) else cost
        return cost["bytes accessed"]

    assert grad_bytes(None) < grad_bytes(128)
