"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (never a module constant) so that
importing this module never touches jax device state — the dry-run sets
XLA_FLAGS for 512 host devices *before* calling it; tests and benches see
the default single device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

AXIS_POD = "pod"
AXIS_DATA = "data"
AXIS_MODEL = "model"


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single-pod (256 chips) or 2×16×16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = (AXIS_POD, AXIS_DATA, AXIS_MODEL) if multi_pod else (AXIS_DATA,
                                                                AXIS_MODEL)
    return make_mesh(shape, axes)


def make_mesh(shape: tuple, axes: tuple, devices=None):
    """Arbitrary mesh (serving, elastic restart targets, tests).

    Axes are ``Auto``: sharding follows ``partition.constrain`` and the
    rule tables, as every caller here expects (``jax.make_mesh`` defaults
    to ``Explicit`` axes, under which those constraints raise)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_host_mesh():
    """Single-device mesh for CPU tests/examples."""
    return make_mesh((1, 1), (AXIS_DATA, AXIS_MODEL))


def data_axes(mesh) -> tuple:
    """Axes that carry the batch (pod extends data across pods)."""
    return tuple(a for a in (AXIS_POD, AXIS_DATA) if a in mesh.axis_names)


def axis_size(mesh, name: str) -> int:
    if name not in mesh.axis_names:
        return 1
    return mesh.shape[name]
