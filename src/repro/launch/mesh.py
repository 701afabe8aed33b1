"""Production meshes. Functions, not module constants — importing this
module never touches jax device state."""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AbstractMesh, AxisType


def make_mesh(shape: Sequence[int], names: Sequence[str], *,
              devices: Optional[Sequence] = None):
    """The one mesh constructor of the repo: ``jax.make_mesh`` with
    every axis ``Auto``. jax 0.9 makes Explicit axes by default, and the
    plans' ``with_sharding_constraint``/``shard_map`` calls need Auto
    ones."""
    return jax.make_mesh(tuple(shape), tuple(names),
                         axis_types=(AxisType.Auto,) * len(names),
                         devices=devices)


def make_abstract_mesh(shape: Sequence[int], names: Sequence[str]):
    """Device-free twin of :func:`make_mesh`, for plans and cost models
    priced without devices."""
    return AbstractMesh(tuple(shape), tuple(names),
                        axis_types=(AxisType.Auto,) * len(names))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod ('data','model'); multi-pod adds a leading
    2-pod axis: (2,16,16) = 512 chips ('pod','data','model')."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ('pod', 'data', 'model') if multi_pod else ('data', 'model')
    return make_mesh(shape, axes)


def make_fft_mesh(rows: int, cols: int, *, pods: int = 1):
    """The paper's PE-grid analogue: pencil grid ('x','y') [+ 'pod']."""
    if pods > 1:
        return make_mesh((pods, rows, cols), ('pod', 'x', 'y'))
    return make_mesh((rows, cols), ('x', 'y'))


def make_host_mesh(rows: int, cols: int):
    """Small fake-device mesh for CPU tests/examples (requires
    XLA_FLAGS=--xla_force_host_platform_device_count>=rows*cols)."""
    return make_mesh((rows, cols), ('data', 'model'))
