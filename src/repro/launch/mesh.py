"""Mesh construction: the one place this repo builds a ``jax.sharding.Mesh``.

Defined as functions (never module-level constants) so importing this module
never touches jax device state.  The dry-run entry point sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
import; everything else sees the real devices.

Every mesh has **Auto** axes: ``jax.make_mesh`` defaults to Explicit axes,
under which ``with_sharding_constraint`` on a bare/named spec (the
sequence-parallel residual pin in ``launch.steps``, the expert pin in
``models.moe``) is refused.  GSPMD propagation over Auto axes is what the
step builders are written for."""

from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], *, devices=None):
    """``jax.make_mesh`` with Auto axis types (see module docstring)."""
    kw = {} if devices is None else {"devices": devices}
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape), **kw)


def parse_mesh(spec: str) -> tuple:
    """``"DxM"`` -> ``(D, M)`` for a ``("data", "model")`` mesh."""
    try:
        data, model = (int(s) for s in spec.lower().split("x"))
    except ValueError:
        raise ValueError(f"mesh spec must look like DxM, got {spec!r}")
    if data < 1 or model < 1:
        raise ValueError(f"mesh axes must be >= 1, got {spec!r}")
    return data, model


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e target: 16x16 = 256 chips per pod; 2 pods = 512 chips.

    Axes: ``data`` (+ ``pod``) carry the batch / FL-client dimension,
    ``model`` carries tensor/expert parallelism."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 4):
    """Small host-device mesh for unit tests (subprocess with 8 devices)."""
    return make_mesh((data, model), ("data", "model"))


def batch_axes(mesh) -> tuple:
    """Mesh axes across which the global batch (= FL clients) is sharded."""
    names = mesh.axis_names
    return tuple(a for a in names if a != "model")


def fsdp_axes(mesh) -> tuple:
    """Mesh axes used for fully-sharded parameter storage."""
    return batch_axes(mesh)


def axis_size(mesh, axes) -> int:
    s = 1
    for a in axes:
        s *= mesh.shape[a]
    return s
