"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
import and only then builds the mesh.

Every mesh here is built with ``Auto`` axis types: the sharding plans and
``with_sharding_constraint`` pins in ``distrib/`` are GSPMD annotations,
which ``jax.make_mesh``'s default ``Explicit`` axes refuse.

Topology (TPU v5e-class): 256 chips/pod as a (16, 16) (data, model) mesh;
multi-pod adds a leading ``pod`` axis over DCN — 2 pods = 512 chips here,
but the same function scales to any pod count (the ``pod`` axis is
data-parallel by default and is the natural pipeline axis if
``distrib/pipeline`` is enabled).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Arbitrary mesh for tests / hillclimb variants."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model: Optional[int] = None):
    """A mesh over whatever devices exist (tests on the 1-CPU container)."""
    n = len(jax.devices())
    model = model or 1
    assert n % model == 0
    return make_mesh((n // model, model), ("data", "model"))
