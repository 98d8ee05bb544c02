"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module never touches JAX device state. The dry-run entrypoint
(`launch/dryrun.py`) forces 512 host devices *before* any JAX import;
everything else sees the real device count.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None) -> Mesh:
    """``jax.make_mesh`` with Auto axis types (GSPMD propagation)."""
    return jax.make_mesh(tuple(shape), tuple(axes), devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16×16 = 256-chip pod; 2×16×16 = 512-chip two-pod slice."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def mesh_axes(mesh: Mesh) -> Tuple[Tuple[str, int], ...]:
    return tuple((name, size) for name, size in mesh.shape.items())


def make_test_mesh(devices=None) -> Mesh:
    """Degenerate (1,1)/(n,1) mesh for CPU tests — same axis names."""
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    return make_mesh((n, 1), ("data", "model"), devices=devices)
