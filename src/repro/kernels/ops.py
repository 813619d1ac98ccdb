"""Jit'd public wrappers around the Pallas kernels.

Handle padding to block multiples, GQA head expansion and layout
(B, S, H, D) <-> (B*H, S, D); dispatch between the Pallas kernel
(``impl="pallas"``) and the pure-JAX oracle-equivalent paths used by the
512-device dry-run (``impl="xla"`` / ``impl="xla_chunked"``).

Whether a Pallas kernel runs natively or in the interpreter is decided in
one place, ``pallas_interpret``, from the backend: interpreted on the CPU
(the test mode), native on the TPU, and an error anywhere else.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.densify import densify_pallas, DEFAULT_BLOCK_N, \
    DEFAULT_BLOCK_V, DEFAULT_BLOCK_D
from repro.kernels.flash_attention import flash_attention_pallas, \
    DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K
from repro.kernels.quantize import quantize_pallas, QMAX
from repro.kernels.ssd import ssd_pallas
from repro.telemetry import hooks as scopes


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def pallas_interpret() -> bool:
    """``interpret=`` for every Pallas call: True on the CPU, False on
    the TPU.  Any other backend has neither a Mosaic compiler nor a
    reason to pay for the interpreter, so it is refused."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise NotImplementedError(
        f"Pallas kernels run natively on TPU or interpreted on CPU; "
        f"backend {backend!r} has neither — use impl='xla'")


# ---------------------------------------------------------------------------
# densify
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("dense_shape", "impl"))
def densify(indices: jax.Array, values: jax.Array,
            dense_shape: Tuple[int, ...], impl: str = "pallas") -> jax.Array:
    """Scatter-add ``values`` rows at ``indices`` into zeros(dense_shape).

    Negative / out-of-range indices are dropped (padding convention).
    """
    if impl == "xla":
        return ref.densify_ref(indices, values, dense_shape)
    vocab, d = dense_shape
    n = indices.shape[0]
    block_n = min(DEFAULT_BLOCK_N, _round_up(n, 8))
    block_v = min(DEFAULT_BLOCK_V, _round_up(vocab, 8))
    block_d = min(DEFAULT_BLOCK_D, _round_up(d, 128))
    np_, vp, dp = (_round_up(n, block_n), _round_up(vocab, block_v),
                   _round_up(d, block_d))
    idx = jnp.full((np_,), -1, jnp.int32).at[:n].set(indices.astype(jnp.int32))
    # out-of-range ids (padding) must not land in the padded vocab rows
    idx = jnp.where((idx >= 0) & (idx < vocab), idx, -1)
    vals = jnp.zeros((np_, dp), values.dtype).at[:n, :d].set(values)
    out = densify_pallas(idx, vals, (vp, dp), block_v=block_v,
                         block_d=block_d, block_n=block_n,
                         interpret=pallas_interpret())
    return out[:vocab, :d]


# ---------------------------------------------------------------------------
# int8 wire quantisation (the int8 WireCodec's encode hot loop)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("impl",))
def quantize_int8(x: jax.Array, impl: str = "pallas"):
    """Quantise ``x`` to (int8 values, f32 absmax scale ``(1,)``).

    ``q = clip(round(x / scale), -127, 127)`` with
    ``scale = absmax(x) / 127``; ``impl="pallas"`` runs the fused
    scale/round/clip/cast chain as one VPU pass, ``impl="xla"`` is the
    pure-jax fallback.  Dequantise with ``q.astype(f32) * scale``.
    """
    flat = x.reshape(-1).astype(jnp.float32)
    absmax = jnp.max(jnp.abs(flat)) if flat.size else jnp.float32(0)
    scale = jnp.maximum(absmax, jnp.float32(1e-30)) / QMAX
    if impl == "xla":
        q = jnp.clip(jnp.round(flat / scale), -QMAX, QMAX).astype(jnp.int8)
    else:
        q = quantize_pallas(flat, 1.0 / scale,
                            interpret=pallas_interpret())
    return q.reshape(x.shape), scale.reshape(1)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

def _expand_kv(k: jax.Array, n_heads: int) -> jax.Array:
    """GQA: repeat kv heads to match query heads. (B, S, Hkv, D) -> (B, S, H, D)."""
    b, s, hkv, d = k.shape
    if hkv == n_heads:
        return k
    rep = n_heads // hkv
    return jnp.repeat(k, rep, axis=2)


@functools.partial(jax.jit,
                   static_argnames=("causal", "window", "impl", "block_q",
                                    "block_k"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, window: Optional[int] = None,
                    impl: str = "pallas",
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: Optional[int] = None) -> jax.Array:
    """Multi-head attention, shapes q (B,Sq,H,D), k/v (B,Sk,Hkv,D) (GQA ok).

    impl:
      pallas       Pallas kernel; needs equal q/v head dims; ``block_k``
                   defaults to ``DEFAULT_BLOCK_K``
      xla          full-softmax reference (small shapes only)
      xla_chunked  pure JAX, takes unequal q/v head dims (MLA).  Where the
                   whole kv fits one block (``Sk <= block_k``, default
                   ``XLA_BLOCK_K``) it is one exact softmax with a
                   recomputing backward that saves q, k, v, the output
                   and the f32 log-sum-exp; longer kv runs the
                   online-softmax scan over ``block_k`` chunks.  Both
                   hand the MXU q, k and v in their own dtype and keep
                   the softmax and every accumulation in f32
                   (``_chunked_attention``).
    """
    h = q.shape[2]
    k = _expand_kv(k, h)
    v = _expand_kv(v, h)
    if impl == "pallas" and v.shape[-1] != q.shape[-1]:
        raise ValueError(
            f"impl='pallas' needs equal q/v head dims, got "
            f"{q.shape[-1]} and {v.shape[-1]}; ask for 'xla_chunked'")
    if impl == "xla":
        return ref.attention_ref(q, k, v, causal=causal, window=window)
    if impl == "xla_chunked":
        return _chunked_attention(q, k, v, causal=causal, window=window,
                                  block_k=block_k or XLA_BLOCK_K)
    b, sq, _, d = q.shape
    sk = k.shape[1]
    bq = min(block_q, _round_up(sq, 8))
    bk = min(block_k or DEFAULT_BLOCK_K, _round_up(sk, 8))
    sqp, skp = _round_up(sq, bq), _round_up(sk, bk)
    scale = d ** -0.5

    def pad(x, s_to):
        return jnp.pad(x, ((0, 0), (0, s_to - x.shape[1]), (0, 0), (0, 0)))

    qp = pad(q, sqp).transpose(0, 2, 1, 3).reshape(b * h, sqp, d)
    kp = pad(k, skp).transpose(0, 2, 1, 3).reshape(b * h, skp, d)
    vp = pad(v, skp).transpose(0, 2, 1, 3).reshape(b * h, skp, d)
    # explicit alignment: query i sits at REAL position i + (sk - sq);
    # kv_len masks the padded trailing keys (essential when causal=False)
    out = flash_attention_pallas(qp, kp, vp, causal=causal, window=window,
                                 scale=scale, block_q=bq, block_k=bk,
                                 q_offset=sk - sq, kv_len=sk,
                                 interpret=pallas_interpret())
    out = out.reshape(b, h, sqp, d).transpose(0, 2, 1, 3)
    return out[:, :sq]


NEG_INF = -1e30
# The XLA path's kv block.  DEFAULT_BLOCK_K (128) is the Pallas kernel's
# MXU tile; the XLA scan wants far larger chunks, since each scan step
# spills the (B,H,Sq,D) accumulator to HBM (measured 1.7x prefill
# memory-term win at 4096 -- EXPERIMENTS.md section Perf H5).
XLA_BLOCK_K = 4096


def _chunked_attention(q, k, v, causal: bool, window: Optional[int],
                       block_k: int = XLA_BLOCK_K) -> jax.Array:
    """Attention in pure JAX, mathematically identical to the Pallas
    kernel.  This is what the training step and the production dry-run
    lower (Pallas-TPU cannot compile on the CPU-only container).

    ``Sk <= block_k``: one block (``_one_block_attention``): the exact
    softmax in f32 over all of kv, under a ``custom_vjp`` that saves only
    q, k, v, the output and the f32 log-sum-exp, and recomputes the
    scores in the backward, so no score-sized f32 residual reaches HBM
    or the layer scan's stores.  ``Sk > block_k``: an online-softmax
    ``lax.scan`` over kv chunks, O(Sq * block_k) live memory.  Both feed
    the MXU q, k and v in their own dtype (bf16 in training) with f32
    accumulation.
    """
    if k.shape[1] <= block_k:
        with jax.named_scope(scopes.ONE_BLOCK):
            return _one_block_attention(q, k, v, causal, window)
    with jax.named_scope(scopes.KV_SCAN):
        return _kv_scan_attention(q, k, v, causal, window, block_k)


def _kv_scan_attention(q, k, v, causal: bool, window: Optional[int],
                       block_k: int) -> jax.Array:
    """Online-softmax scan over kv chunks of ``block_k`` (lax.scan)."""
    b, sq, h, d = q.shape
    dv = v.shape[-1]                      # may differ from d (MLA)
    sk = k.shape[1]
    nchunks = -(-sk // block_k)
    skp = nchunks * block_k
    kp = jnp.pad(k, ((0, 0), (0, skp - sk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, skp - sk), (0, 0), (0, 0)))
    kc = kp.reshape(b, nchunks, block_k, h, d).transpose(1, 0, 2, 3, 4)
    vc = vp.reshape(b, nchunks, block_k, h, dv).transpose(1, 0, 2, 3, 4)
    scale = d ** -0.5
    qf = q.astype(jnp.float32) * scale
    q_pos = jnp.arange(sq) + (sk - sq)

    def step(carry, inputs):
        acc, m, l = carry
        ci, kb, vb = inputs
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kb.astype(jnp.float32))
        k_pos = ci * block_k + jnp.arange(block_k)
        mask = k_pos[None, :] < sk
        if causal:
            mask &= q_pos[:, None] >= k_pos[None, :]
        if window is not None:
            mask &= (q_pos[:, None] - k_pos[None, :]) < window
        s = jnp.where(mask[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(mask[None, None], p, 0.0)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, vb.astype(jnp.float32))
        return (acc_new, m_new, l_new), None

    acc0 = jnp.zeros((b, h, sq, dv), jnp.float32)
    m0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(
        step, (acc0, m0, l0), (jnp.arange(nchunks), kc, vc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def _scores(q, k, causal: bool, window: Optional[int]) -> jax.Array:
    """Scaled f32 scores (B, H, Sq, Sk) of one block, -inf where masked.
    The MXU takes q and k in their own dtype; a power-of-two scale (head
    dim 64) is applied to q first, where it rounds nothing."""
    sq, sk, d = q.shape[1], k.shape[1], q.shape[-1]
    scale = d ** -0.5
    exact = math.frexp(scale)[0] == 0.5
    if exact:
        q = q * jnp.asarray(scale, q.dtype)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32)
    if not exact:
        s = s * scale
    if causal or window is not None:
        q_pos = jnp.arange(sq)[:, None] + (sk - sq)
        k_pos = jnp.arange(sk)[None, :]
        mask = jnp.ones((sq, sk), bool)
        if causal:
            mask &= q_pos >= k_pos
        if window is not None:
            mask &= (q_pos - k_pos) < window
        s = jnp.where(mask, s, -jnp.inf)
    return s


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _one_block_attention(q, k, v, causal: bool, window: Optional[int]):
    """Exact attention over one kv block; see ``_chunked_attention``."""
    return _one_block_fwd(q, k, v, causal, window)[0]


def _one_block_fwd(q, k, v, causal, window):
    s = _scores(q, k, causal, window)
    m = jnp.max(s, axis=-1, keepdims=True)
    # a row with no visible key has m = -inf: its p is all 0, so its
    # output is 0 and its lse +inf (the backward's p is 0 there too)
    m = jnp.where(m == -jnp.inf, 0.0, m)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    o = (o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
         ).astype(q.dtype)
    lse = jnp.where(l > 0, m[..., 0] + jnp.log(l), jnp.inf)
    return o, (q, k, v, o, lse)


def _one_block_bwd(causal, window, res, do):
    q, k, v, o, lse = res
    scale = q.shape[-1] ** -0.5
    p = jnp.exp(_scores(q, k, causal, window) - lse[..., None])
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1).transpose(0, 2, 1)          # (B, H, Sq)
    dp = jnp.einsum("bqhd,bkhd->bhqk", do, v,
                    preferred_element_type=jnp.float32)
    ds = (p * (dp - delta[..., None])).astype(q.dtype)
    dq = jnp.einsum("bhqk,bkhd->bqhd", ds, k,
                    preferred_element_type=jnp.float32) * scale
    dk = jnp.einsum("bhqk,bqhd->bkhd", ds, q,
                    preferred_element_type=jnp.float32) * scale
    dv = jnp.einsum("bhqk,bqhd->bkhd", p.astype(do.dtype), do,
                    preferred_element_type=jnp.float32)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_one_block_attention.defvjp(_one_block_fwd, _one_block_bwd)


# ---------------------------------------------------------------------------
# ssd (Mamba2 chunked scan)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("chunk", "impl"))
def ssd(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
        c: jax.Array, chunk: int = 64, impl: str = "pallas"):
    """Chunked SSD scan over heads with shared B/C.

    x (B, S, H, P), dt (B, S, H), a (H,), b/c (B, S, N).
    Returns (y (B, S, H, P), final_state (B, H, N, P)).

    impl="pallas": VMEM-resident per-chunk tiles;
    impl="xla": sequential-recurrence oracle.
    """
    bb, s, h, p = x.shape
    n = b.shape[-1]
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))
    sp = s + pad
    xf = x.transpose(0, 2, 1, 3).reshape(bb * h, sp, p)
    dtf = dt.transpose(0, 2, 1).reshape(bb * h, sp)
    af = jnp.tile(a, bb)
    bf = jnp.repeat(b[:, None], h, axis=1).reshape(bb * h, sp, n)
    cf = jnp.repeat(c[:, None], h, axis=1).reshape(bb * h, sp, n)
    if impl == "xla":
        y, state = ref.ssd_ref(xf, dtf, af, bf, cf)
    else:
        y, state = ssd_pallas(xf, dtf, af, bf, cf, chunk,
                              interpret=pallas_interpret())
    y = y.reshape(bb, h, sp, p).transpose(0, 2, 1, 3)[:, :s]
    return y, state.reshape(bb, h, n, p)
