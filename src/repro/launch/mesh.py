"""Production mesh construction (TPU v5e pod / 2-pod numbers).

Functions, not module-level constants: importing this module never
touches jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the dry-run steers GSPMD with
    ``with_sharding_constraint``, which only accepts Auto axes."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def data_axes(mesh) -> tuple:
    """The data-parallel axes of a mesh (everything but 'model')."""
    return tuple(a for a in mesh.axis_names if a != "model")


# Hardware constants for the roofline model (TPU v5e per chip) — read
# from the shared BandwidthProfile preset so the dry-run roofline, the
# benchmarks and the tuner can never disagree on the numbers
from repro.tuning.profile import get_profile as _get_profile

_TPU = _get_profile("tpu")
PEAK_FLOPS_BF16 = _TPU.peak_flops   # FLOP/s
HBM_BW = _TPU.hbm_bw                # B/s
ICI_BW = _TPU.cross_bw              # B/s per link
