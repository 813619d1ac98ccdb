"""Pallas TPU kernel: block-wise flash attention (online softmax).

Used by the transformer backbones for the 32k-prefill and 500k sliding-
window shapes, where materialising the (Sq, Sk) score matrix is
impossible.  TPU adaptation of the standard flash algorithm:

  * grid ``(batch*heads, q_blocks, kv_blocks)`` with the kv dim innermost
    so the running (acc, m, l) statistics stay in VMEM scratch across kv
    steps — no HBM round-trip for the accumulator;
  * (block_q, head_dim) and (block_k, head_dim) tiles are multiples of
    (8, 128) so both matmuls hit the MXU without re-layout;
  * causal and sliding-window masks are applied with position iota inside
    the tile (no mask tensor in HBM).

Validated against ``ref.attention_ref`` in interpret mode (CPU container);
on real TPU hardware the same ``pallas_call`` compiles natively.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, window: Optional[int],
                  block_q: int, block_k: int, q_offset: int, kv_len: int):
    qb = pl.program_id(1)
    kb = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)                      # (bq, d)
    k = k_ref[0].astype(jnp.float32)                      # (bk, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    # global positions (decode alignment: query i sits at i + q_offset)
    q_pos = qb * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0) + q_offset
    k_pos = kb * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = k_pos < kv_len                  # drop padded keys
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                   # (bq,)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_new)                       # rescale factor
    p = jnp.exp(s - m_new[:, None])                       # (bq, bk)
    p = jnp.where(mask, p, 0.0)

    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(kb == nk - 1)
    def _finalize():
        l_safe = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l_safe[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                           causal: bool = True,
                           window: Optional[int] = None,
                           scale: Optional[float] = None,
                           block_q: int = DEFAULT_BLOCK_Q,
                           block_k: int = DEFAULT_BLOCK_K,
                           q_offset: Optional[int] = None,
                           kv_len: Optional[int] = None, *,
                           interpret: bool) -> jax.Array:
    """Raw pallas_call over pre-flattened heads.

    Shapes: q (BH, Sq, D), k/v (BH, Sk, D); Sq % block_q == 0,
    Sk % block_k == 0.  ``q_offset`` aligns query positions (defaults to
    Sk - Sq); ``kv_len`` masks padded trailing keys.  Use
    ``ops.flash_attention`` for (B,S,H,D) inputs with padding/GQA
    handling.
    """
    bh, sq, d = q.shape
    sk = k.shape[1]
    assert sq % block_q == 0 and sk % block_k == 0, (sq, sk)
    if scale is None:
        scale = d ** -0.5
    if q_offset is None:
        q_offset = sk - sq
    if kv_len is None:
        kv_len = sk
    from jax.experimental.pallas import tpu as pltpu
    grid = (bh, sq // block_q, sk // block_k)
    return pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          window=window, block_q=block_q, block_k=block_k,
                          q_offset=q_offset, kv_len=kv_len),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
