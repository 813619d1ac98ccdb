"""Pallas TPU kernel: fused int8 wire quantisation.

The int8 ``WireCodec`` narrows a packed fusion buffer to one byte per
element plus one f32 absmax scale per bucket.  The hot loop is the
elementwise ``scale -> round -> clip -> cast`` chain over up-to-128 MiB
buffers; on TPU that chain fuses into a single VPU pass over VMEM tiles
instead of four HBM round-trips.  The absmax reduction itself stays an
XLA reduce (one pass, already fused with the producer); the kernel takes
the reciprocal scale as a scalar input.

Layout: the flat buffer is viewed as ``(rows, 128)`` lanes and tiled in
``block_rows`` sublane blocks — multiples of 32 to satisfy the int8
(32, 128) tile constraint.  Interpret mode on CPU, native on TPU,
exactly like ``densify.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
DEFAULT_BLOCK_ROWS = 256      # (256, 128) f32 tiles = 128 KiB of VMEM
QMAX = 127.0


def _quantize_kernel(x_ref, inv_ref, out_ref):
    q = jnp.round(x_ref[...] * inv_ref[0, 0])
    out_ref[...] = jnp.clip(q, -QMAX, QMAX).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def quantize_pallas(flat: jax.Array, inv_scale: jax.Array,
                    block_rows: int = DEFAULT_BLOCK_ROWS, *,
                    interpret: bool) -> jax.Array:
    """Quantise a flat f32/bf16 buffer to int8 at ``1/inv_scale``.

    Pads to ``(block_rows, 128)`` tile multiples internally; returns the
    leading ``len(flat)`` elements.
    """
    n = flat.shape[0]
    tile = block_rows * LANES
    padded = -(-max(n, 1) // tile) * tile
    xp = jnp.pad(flat.astype(jnp.float32), (0, padded - n))
    rows = padded // LANES
    out = pl.pallas_call(
        _quantize_kernel,
        grid=(rows // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.int8),
        interpret=interpret,
    )(xp.reshape(rows, LANES),
      inv_scale.astype(jnp.float32).reshape(1, 1))
    return out.reshape(-1)[:n]
