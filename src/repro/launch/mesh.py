"""Production meshes.

Functions (not module-level constants) so importing never touches jax device
state.  Single pod: 16×16 = 256 chips (data, model).  Multi-pod: 2 pods =
512 chips (pod, data, model); the 'pod' axis carries only data parallelism
(gradient all-reduce crosses the DCN/ICI boundary once per step).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    # the model code places activations with with_sharding_constraint,
    # which takes Auto axes only (jax.make_mesh defaults to Explicit)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh(n_data: int = 2, n_model: int = 2):
    """Small mesh for subprocess tests with few fake devices."""
    return _auto_mesh((n_data, n_model), ("data", "model"))
