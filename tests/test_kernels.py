"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs ref oracle."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.telemetry import hooks

jax.config.update("jax_platform_name", "cpu")


# ---------------------------------------------------------------------------
# densify
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,v,d", [
    (1, 1, 1), (7, 13, 5), (64, 100, 32), (128, 64, 128),
    (300, 1000, 257), (512, 512, 128), (33, 8, 640),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_densify_matches_ref(n, v, d, dtype):
    rng = np.random.default_rng(n * 1000 + v + d)
    idx = jnp.asarray(rng.integers(0, v, size=(n,)).astype(np.int32))
    vals = jnp.asarray(rng.standard_normal((n, d))).astype(dtype)
    out = ops.densify(idx, vals, (v, d))
    exp = ref.densify_ref(idx, vals, (v, d))
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32),
                               rtol=tol, atol=tol)
    assert out.dtype == vals.dtype


def test_densify_drops_out_of_range():
    idx = jnp.array([-1, 0, 5, 2], jnp.int32)     # -1 and 5 out of range
    vals = jnp.ones((4, 3), jnp.float32)
    out = ops.densify(idx, vals, (4, 3))
    exp = jnp.zeros((4, 3)).at[0].set(1.0).at[2].set(1.0)
    np.testing.assert_allclose(out, exp)


@given(st.integers(1, 200), st.integers(1, 50), st.integers(1, 40),
       st.integers(0, 2**31 - 1))
@settings(max_examples=25, deadline=None)
def test_densify_property(n, v, d, seed):
    rng = np.random.default_rng(seed)
    idx = jnp.asarray(rng.integers(0, v, size=(n,)).astype(np.int32))
    vals = jnp.asarray(rng.standard_normal((n, d)).astype(np.float32))
    np.testing.assert_allclose(ops.densify(idx, vals, (v, d)),
                               ref.densify_ref(idx, vals, (v, d)),
                               rtol=1e-4, atol=1e-4)


def test_densify_sums_duplicates():
    idx = jnp.zeros((100,), jnp.int32)
    vals = jnp.ones((100, 8), jnp.float32)
    out = ops.densify(idx, vals, (4, 8))
    np.testing.assert_allclose(out[0], 100.0 * jnp.ones(8))
    np.testing.assert_allclose(out[1:], 0.0)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

CASES = [
    # b, sq, sk, h, hkv, d, window, causal
    (2, 16, 16, 4, 2, 32, None, True),
    (1, 64, 64, 2, 2, 64, 16, True),
    (2, 8, 40, 4, 4, 32, None, True),       # decode-style alignment
    (1, 32, 32, 4, 1, 16, 8, True),         # MQA + window
    (2, 24, 24, 2, 2, 128, None, False),    # bidirectional (cross-attn)
    (1, 17, 23, 3, 3, 48, None, True),      # ragged, non-multiple shapes
]


@pytest.mark.parametrize("b,sq,sk,h,hkv,d,window,causal", CASES)
def test_flash_pallas_matches_ref(b, sq, sk, h, hkv, d, window, causal):
    key = jax.random.PRNGKey(b * 100 + sq + sk)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, sq, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, sk, hkv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, sk, hkv, d), jnp.float32)
    exp = ops.flash_attention(q, k, v, causal=causal, window=window,
                              impl="xla")
    pal = ops.flash_attention(q, k, v, causal=causal, window=window,
                              impl="pallas", block_q=8, block_k=8)
    np.testing.assert_allclose(pal, exp, rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("b,sq,sk,h,hkv,d,window,causal", CASES)
def test_flash_chunked_matches_ref(b, sq, sk, h, hkv, d, window, causal):
    key = jax.random.PRNGKey(b * 77 + sq)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, sq, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, sk, hkv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, sk, hkv, d), jnp.float32)
    exp = ops.flash_attention(q, k, v, causal=causal, window=window,
                              impl="xla")
    chk = ops.flash_attention(q, k, v, causal=causal, window=window,
                              impl="xla_chunked", block_k=8)
    np.testing.assert_allclose(chk, exp, rtol=3e-5, atol=3e-5)


ONE_BLOCK_CASES = [
    # b, sq, sk, h, hkv, d, dv, window, causal
    (2, 16, 16, 4, 2, 64, 64, None, True),      # causal self, GQA
    (2, 24, 40, 2, 2, 32, 32, None, False),     # bidirectional cross
    (1, 32, 32, 4, 1, 16, 16, 8, True),         # MQA + window
    (1, 17, 23, 3, 3, 48, 48, None, True),      # ragged, non-multiple shapes
    (1, 16, 16, 2, 2, 48, 32, None, True),      # MLA: dv != d
    (1, 12, 5, 2, 2, 16, 16, None, True),       # 7 rows see no key
]


def _qkv(key, b, sq, sk, h, hkv, d, dv, dtype):
    ks = jax.random.split(key, 4)
    return (jax.random.normal(ks[0], (b, sq, h, d)).astype(dtype),
            jax.random.normal(ks[1], (b, sk, hkv, d)).astype(dtype),
            jax.random.normal(ks[2], (b, sk, hkv, dv)).astype(dtype),
            jax.random.normal(ks[3], (b, sq, h, dv)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("b,sq,sk,h,hkv,d,dv,window,causal",
                         ONE_BLOCK_CASES)
def test_flash_one_block_matches_ref(b, sq, sk, h, hkv, d, dv, window,
                                     causal, dtype):
    """``xla_chunked`` at its default block_k holds these kv lengths in
    one block: its output and its recomputing backward's gradients in
    q, k and v match the full-softmax reference, taken in f32 on the
    same (bf16-rounded) inputs."""
    q, k, v, w = _qkv(jax.random.PRNGKey(sq * 31 + sk), b, sq, sk, h, hkv,
                      d, dv, dtype)

    def loss(impl):
        def f(q, k, v):
            o = ops.flash_attention(q, k, v, causal=causal, window=window,
                                    impl=impl)
            return jnp.sum(o.astype(jnp.float32) * w), o
        return jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)

    (_, out), grads = loss("xla_chunked")(q, k, v)
    (_, exp), exp_grads = loss("xla")(*(x.astype(jnp.float32)
                                        for x in (q, k, v)))
    assert out.dtype == dtype
    assert [g.dtype for g in grads] == [dtype] * 3
    # bf16: the output and each gradient are rounded once to bf16 (2^-8
    # relative), and p and ds enter the MXU in bf16
    tol = 3e-5 if dtype == jnp.float32 else 2e-2
    for got, want in zip((out,) + grads, (exp,) + exp_grads):
        want = np.asarray(want, np.float32)
        np.testing.assert_allclose(np.asarray(got, np.float32), want,
                                   rtol=tol,
                                   atol=tol * max(1.0, np.abs(want).max()))


def test_flash_one_block_empty_rows_are_zero():
    """A query row with no visible key (causal, sq > sk) returns zeros,
    not an average over the masked keys, and has finite gradients."""
    q, k, v, _ = _qkv(jax.random.PRNGKey(4), 1, 12, 5, 2, 2, 16, 16,
                      jnp.float32)

    def f(q, k, v):
        return ops.flash_attention(q, k, v, causal=True, impl="xla_chunked")

    out = f(q, k, v)
    np.testing.assert_array_equal(out[:, :7], 0.0)
    assert np.all(np.abs(np.asarray(out[:, 7:])) > 0)
    grads = jax.grad(lambda *a: jnp.sum(f(*a) ** 2), argnums=(0, 1, 2))(
        q, k, v)
    assert all(np.all(np.isfinite(g)) for g in grads)
    np.testing.assert_array_equal(grads[0][:, :7], 0.0)


@pytest.mark.parametrize("sk,block_k,path", [
    (16, None, hooks.ONE_BLOCK), (16, 16, hooks.ONE_BLOCK),
    (17, 16, hooks.KV_SCAN), (40, 8, hooks.KV_SCAN)])
def test_flash_chunked_path_follows_kv_length(sk, block_k, path):
    """The one-block path runs exactly where the kv fits one block
    (``sk <= block_k``); longer kv runs the online-softmax scan."""
    q, k, v, _ = _qkv(jax.random.PRNGKey(0), 1, 8, sk, 2, 2, 16, 16,
                      jnp.float32)
    fn = jax.jit(lambda q, k, v: ops.flash_attention(
        q, k, v, causal=True, impl="xla_chunked", block_k=block_k))
    names = set(re.findall(r'op_name="([^"]*)"',
                           fn.lower(q, k, v).compile().as_text()))
    other = hooks.KV_SCAN if path == hooks.ONE_BLOCK else hooks.ONE_BLOCK
    assert any(path in n for n in names)
    assert not any(other in n for n in names)
    np.testing.assert_allclose(fn(q, k, v), ops.flash_attention(
        q, k, v, causal=True, impl="xla"), rtol=3e-5, atol=3e-5)


def test_flash_bf16():
    key = jax.random.PRNGKey(5)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (1, 16, 2, 32), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 16, 2, 32), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 16, 2, 32), jnp.bfloat16)
    exp = ops.flash_attention(q, k, v, impl="xla")
    pal = ops.flash_attention(q, k, v, impl="pallas", block_q=8, block_k=8)
    np.testing.assert_allclose(np.asarray(pal, np.float32),
                               np.asarray(exp, np.float32),
                               rtol=3e-2, atol=3e-2)
    assert pal.dtype == jnp.bfloat16


def test_flash_mla_mixed_head_dims_falls_back():
    """MLA: v head dim != qk head dim is refused by the Pallas kernel and
    correct on the ``xla_chunked`` path its callers fall back to."""
    key = jax.random.PRNGKey(9)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (1, 16, 2, 48), jnp.float32)
    k = jax.random.normal(ks[1], (1, 16, 2, 48), jnp.float32)
    v = jax.random.normal(ks[2], (1, 16, 2, 32), jnp.float32)
    exp = ref.attention_ref(q, k, v, causal=True)
    with pytest.raises(ValueError, match="equal q/v head dims"):
        ops.flash_attention(q, k, v, impl="pallas")
    out = ops.flash_attention(q, k, v, impl="xla_chunked")
    np.testing.assert_allclose(out, exp, rtol=3e-5, atol=3e-5)


def test_pallas_interpret_follows_backend(monkeypatch):
    """Interpreted on the CPU, native on the TPU, refused elsewhere."""
    for backend, want in (("cpu", True), ("tpu", False)):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert ops.pallas_interpret() is want
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(NotImplementedError, match="gpu"):
        ops.pallas_interpret()


def test_window_equals_full_when_window_large():
    key = jax.random.PRNGKey(11)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (1, 32, 2, 16))
    k = jax.random.normal(ks[1], (1, 32, 2, 16))
    v = jax.random.normal(ks[2], (1, 32, 2, 16))
    full = ops.flash_attention(q, k, v, causal=True, window=None,
                               impl="pallas", block_q=8, block_k=8)
    wide = ops.flash_attention(q, k, v, causal=True, window=32,
                               impl="pallas", block_q=8, block_k=8)
    np.testing.assert_allclose(full, wide, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# ssd (Mamba2 chunked scan kernel)
# ---------------------------------------------------------------------------

SSD_CASES = [
    # b, s, h, p, n, chunk
    (1, 16, 1, 4, 4, 8),
    (2, 64, 3, 8, 4, 16),
    (2, 50, 3, 8, 4, 16),     # ragged (padding path)
    (1, 128, 2, 16, 8, 32),
]


@pytest.mark.parametrize("b,s,h,p,n,chunk", SSD_CASES)
def test_ssd_pallas_matches_sequential_oracle(b, s, h, p, n, chunk):
    from repro.kernels import ops as kops
    key = jax.random.PRNGKey(b * 100 + s)
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)) - 4.0)
    a = -jnp.exp(jax.random.uniform(ks[4], (h,), maxval=2.5))
    bb = jax.random.normal(ks[2], (b, s, n))
    cc = jax.random.normal(ks[3], (b, s, n))
    y1, s1 = kops.ssd(x, dt, a, bb, cc, chunk=chunk, impl="pallas")
    y2, s2 = kops.ssd(x, dt, a, bb, cc, chunk=chunk, impl="xla")
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=2e-5, atol=2e-5)


def test_ssd_pallas_matches_model_path():
    """Kernel vs the model's XLA ssd_chunked (separable) — same math."""
    from repro.kernels import ops as kops
    from repro.models.ssm import ssd_chunked
    key = jax.random.PRNGKey(3)
    ks = jax.random.split(key, 5)
    b, s, h, p, n, chunk = 2, 64, 4, 8, 8, 16
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)) - 4.0)
    a = -jnp.exp(jnp.log(jnp.linspace(1.0, 16.0, h)))
    bb = jax.random.normal(ks[2], (b, s, n))
    cc = jax.random.normal(ks[3], (b, s, n))
    y1, s1 = kops.ssd(x, dt, a, bb, cc, chunk=chunk, impl="pallas")
    y2, s2 = ssd_chunked(x, dt, a, bb, cc, chunk)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=2e-5, atol=2e-5)
