"""Production mesh construction (TPU v5e-256 pods).

A FUNCTION (not module-level constant) so importing never touches jax device
state; the dry-run sets XLA_FLAGS for 512 host devices before first jax init.
"""
from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_local_mesh"]


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_local_mesh(data: int | None = None, model: int = 1):
    """Small mesh over whatever devices exist (CPU tests / trainer)."""
    n = jax.device_count()
    data = data or (n // model)
    return _make_mesh((data, model), ("data", "model"))
