"""Production mesh construction.

``make_production_mesh()`` is a FUNCTION (never a module-level constant) so
importing this module never touches jax device state — required for the
XLA_FLAGS device-count trick in dryrun.py to work.

Production targets (TPU v5e):
  single pod : (data=16, model=16)           = 256 chips
  multi-pod  : (pod=2, data=16, model=16)    = 512 chips
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Arbitrary mesh for tests / small runs (e.g. (2, 2) on 4 devices)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
