"""Pallas TPU kernel: chunked SSD (Mamba2 state-space duality) scan.

The §Perf H2 analysis showed the XLA path's HBM traffic is dominated by
intra-chunk tensors; this kernel keeps ALL per-chunk intermediates — the
(L, L) masked score matrix, the decay vectors and the (N, P) running
state — in VMEM, writing only the (L, P) output tile per grid step.

Grid: ``(batch*heads, n_chunks)`` with chunks innermost; the (N, P)
state lives in VMEM scratch and persists across the sequential chunk
steps of one (batch, head).  Uses the separable-decay formulation with
exact-diagonal correction (same math as ``models.ssm.ssd_chunked``,
whose naive form is the oracle in ``ref.ssd_ref``).

Block shapes: L (chunk) x P and L x N tiles — L, P, N chosen as
multiples of (8, 128) at production scale; the two matmuls
(scores = C B^T and the masked-score x value product) hit the MXU.
Validated with interpret=True on CPU; on TPU the same pallas_call
compiles natively.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

CLIP = 60.0


def _ssd_kernel(a_ref, dt_ref, x_ref, b_ref, c_ref, y_ref, state_out_ref,
                state_ref, *, chunk: int, n_chunks: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    a = a_ref[pl.program_id(0)]                    # scalar decay (<0)
    dt = dt_ref[0].astype(jnp.float32)             # (L, 1) column
    x = x_ref[0].astype(jnp.float32)               # (L, P)
    bb = b_ref[0].astype(jnp.float32)              # (L, N)
    cc = c_ref[0].astype(jnp.float32)              # (L, N)

    li = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = li >= lj
    # inclusive prefix sum as a lower-triangular matmul (Mosaic has no
    # cumsum); HIGHEST keeps it exact in f32
    cum = jax.lax.dot_general(
        causal.astype(jnp.float32), dt * a, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)        # (L, 1) <= 0
    pos = jnp.exp(cum)
    neg = jnp.exp(jnp.minimum(-cum, CLIP))

    scores = jax.lax.dot_general(cc, bb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    masked = jnp.where(causal, scores, 0.0)

    bj = (neg * dt) * x                            # (L, P)
    acc = jax.lax.dot_general(masked, bj, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    y = pos * acc
    # exact diagonal correction (clip-robust self contribution)
    diag = jnp.sum(cc * bb, axis=1, keepdims=True)  # (L, 1)
    y = y + ((1.0 - pos * neg) * dt * diag) * x
    # inter-chunk: contribution of the carried state
    y = y + pos * jax.lax.dot_general(
        cc, state_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    y_ref[0] = y.astype(y_ref.dtype)

    # state update: S' = exp(cum_L) S + sum_j exp(cum_L - cum_j) dt_j B_j x_j
    # cum_L is formed as a (1, P) row: Mosaic cannot broadcast a (1, 1)
    # value across both sublanes and lanes
    last = jnp.sum(jnp.broadcast_to(dt * a, x.shape), axis=0,
                   keepdims=True)                  # (1, P), all cum_L
    wx = jnp.exp(last - cum) * (dt * x)            # (L, P)
    state_ref[...] = (jnp.exp(last) * state_ref[...]
                      + jax.lax.dot_general(
                          bb, wx, (((0,), (0,)), ((), ())),
                          preferred_element_type=jnp.float32))

    @pl.when(ci == n_chunks - 1)
    def _emit_state():
        state_out_ref[0] = state_ref[...]


def ssd_pallas(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
               c: jax.Array, chunk: int, *,
               interpret: bool) -> Tuple[jax.Array, jax.Array]:
    """Raw pallas_call.

    x (BH, S, P), dt (BH, S), a (BH,), b/c (BH, S, N); S % chunk == 0.
    Returns (y (BH, S, P), final_state (BH, N, P)).  Use ``ops.ssd`` for
    (B, S, H, P) layouts with shared B/C across heads.
    """
    bh, s, p = x.shape
    n = b.shape[-1]
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    from jax.experimental.pallas import tpu as pltpu
    grid = (bh, nc)
    y, state = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk, n_chunks=nc),
        grid=grid,
        in_specs=[
            # per-(batch, head) decays live whole in SMEM: a rank-1
            # (1,) VMEM block is not a legal TPU tile
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, chunk, 1), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, chunk, p), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, chunk, n), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, chunk, n), lambda i, j: (i, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, p), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, n, p), lambda i, j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, p), jnp.float32),
            jax.ShapeDtypeStruct((bh, n, p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        interpret=interpret,
    )(a.astype(jnp.float32), dt.reshape(bh, s, 1), x, b, c)
    return y, state
