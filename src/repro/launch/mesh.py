"""Production meshes.

Target hardware: TPU v5e pods — 256 chips/pod (16x16), 2 pods = 512 chips.
Functions (not module constants) so importing never touches jax device state.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

HW = {
    "peak_flops_bf16": 197e12,   # per chip
    "hbm_bw": 819e9,             # bytes/s per chip
    "ici_bw": 50e9,              # bytes/s per link
    "hbm_bytes": 16e9,           # per chip
}


def make_mesh(shape, axes, devices=None):
    """The one way this repo builds a mesh: every axis ``AxisType.Auto``.

    ``jax.make_mesh`` defaults to ``Explicit`` axes since JAX 0.7, and the
    partitioner-driven code here (``with_sharding_constraint`` in the shard
    hook, GSPMD-placed gradients) needs ``Auto`` ones.  ``devices`` defaults
    to all of ``jax.devices()``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def mesh_num_devices(mesh) -> int:
    n = 1
    for v in mesh.shape.values():
        n *= v
    return n
