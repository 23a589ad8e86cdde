"""Production mesh definitions.

Kept as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state.  The production target is TPU v5e:
one pod = 16x16 = 256 chips, multi-pod = 2 pods = 512 chips with a leading
pure-DP 'pod' axis (inter-pod traffic is one gradient reduction per step).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with ``Auto`` axes.  The model code shards through
    ``with_sharding_constraint`` (``distributed/sharding.py``), which jax's
    default ``Explicit`` axes refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (possibly fake) local devices exist."""
    n = len(jax.devices())
    assert data * model <= n, (data, model, n)
    return make_mesh((data, model), ("data", "model"))
