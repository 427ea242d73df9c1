"""Production meshes + TPU v5e hardware constants for the roofline.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module touches no jax device state — required because only
launch/dryrun.py requests 512 placeholder host devices.
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes, devices=None):
    return jax.make_mesh(
        shape, axes, devices=devices,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_mesh(shape, axes, devices=None):
    """Arbitrary (elastic) mesh with the same axis-type convention."""
    return _make_mesh(tuple(shape), tuple(axes), devices=devices)


def mesh_context(mesh):
    """Enter a mesh (``jax.sharding.set_mesh``)."""
    return jax.sharding.set_mesh(mesh)


# TPU v5e, per chip (roofline constants from the assignment)
HW = dict(
    peak_flops_bf16=197e12,   # FLOP/s
    hbm_bw=819e9,             # B/s
    ici_bw=50e9,              # B/s per link
    hbm_bytes=16 * 1024**3,   # 16 GiB
)
