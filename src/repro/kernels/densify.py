"""Pallas TPU kernel: densify IndexedSlices (scatter-add rows -> dense).

This is the per-step hot-spot of the paper's fix: converting the sparse
embedding gradient ``(n rows, d_model)`` at token ids ``indices`` into the
dense ``(vocab, d_model)`` tensor that the allreduce exchanges.

TPU adaptation (vs. Horovod's CPU ``tf.convert_to_tensor`` scatter):
random-access row scatter is hostile to the TPU's vector memory, so the
kernel reformulates scatter-add as a ONE-HOT MATMUL, which runs on the
MXU systolic array:

    out[vb] += onehot(indices_block, vocab_block).T @ values_block

Grid: ``(vocab_blocks, feature_blocks, row_blocks)`` with the row dim
innermost, so each ``(BV, BD)`` output tile stays resident in VMEM and is
revisited across row blocks (sequential-grid accumulation).  Block sizes
are multiples of (8, 128) to align with VREG lanes and the 128x128 MXU.

The cost is ``vocab * n * d`` MACs instead of ``n * d`` adds — but on TPU
the MXU delivers those MACs at peak, while a scatter would serialise; for
the paper's shapes (n = tokens-per-batch << vocab) the win is latency
predictability and zero HBM gather traffic.  The wrapper in ``ops.py``
pads all dims to block multiples; out-of-range indices contribute zero.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


DEFAULT_BLOCK_V = 512     # vocab rows per output tile
DEFAULT_BLOCK_D = 128     # feature lanes (MXU-aligned)
DEFAULT_BLOCK_N = 256     # slice rows per step


def _densify_kernel(idx_ref, val_ref, out_ref, *, block_v: int):
    """One (vocab-block, feature-block) tile; accumulates over row blocks."""
    vb = pl.program_id(0)
    rb = pl.program_id(2)

    @pl.when(rb == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    local = idx_ref[...] - vb * block_v                  # (1, BN) in-tile
    # one-hot (BV, BN): column r lights row local[r] iff it falls in-tile.
    rows = jax.lax.broadcasted_iota(jnp.int32, (block_v, local.shape[1]), 0)
    onehot = (rows == local).astype(val_ref.dtype)
    # MXU matmul: (BV, BN) @ (BN, BD) -> (BV, BD), accumulated in fp32;
    # f32 rows take the multi-pass HIGHEST contraction so they stay
    # exact (one default pass would round them to bf16)
    exact = (jax.lax.Precision.HIGHEST if val_ref.dtype == jnp.float32
             else None)
    out_ref[...] += jnp.dot(onehot, val_ref[...], precision=exact,
                            preferred_element_type=out_ref.dtype)


def densify_pallas(indices: jax.Array, values: jax.Array,
                   dense_shape: Tuple[int, ...],
                   block_v: int = DEFAULT_BLOCK_V,
                   block_d: int = DEFAULT_BLOCK_D,
                   block_n: int = DEFAULT_BLOCK_N, *,
                   interpret: bool) -> jax.Array:
    """Raw pallas_call. Requires pre-padded inputs:
    ``len(indices) % block_n == 0``, ``dense_shape`` divisible by
    ``(block_v, block_d)``.  Use ``ops.densify`` for arbitrary shapes.

    The ids travel as one ``(1, n)`` row so each ``(1, block_n)`` block
    is lane-aligned: a 1-D ``(block_n,)`` int32 block asks Mosaic for a
    tiling the XLA layout of the operand does not have.
    """
    vocab, d = dense_shape
    n = indices.shape[0]
    assert n % block_n == 0 and vocab % block_v == 0 and d % block_d == 0, (
        n, vocab, d, block_v, block_d, block_n)
    grid = (vocab // block_v, d // block_d, n // block_n)
    out_dtype = jnp.float32 if values.dtype == jnp.bfloat16 else values.dtype
    out = pl.pallas_call(
        functools.partial(_densify_kernel, block_v=block_v),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_n), lambda i, j, r: (0, r)),
            pl.BlockSpec((block_n, block_d), lambda i, j, r: (r, j)),
        ],
        out_specs=pl.BlockSpec((block_v, block_d), lambda i, j, r: (i, j)),
        out_shape=jax.ShapeDtypeStruct((vocab, d), out_dtype),
        interpret=interpret,
    )(indices.astype(jnp.int32).reshape(1, n), values)
    return out.astype(values.dtype)
