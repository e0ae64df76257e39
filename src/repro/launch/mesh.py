"""Production mesh construction.

Defined as FUNCTIONS (not module constants) so importing this module never
touches jax device state.  Single pod = 256 chips as (16 data x 16 model);
multi-pod adds a leading pure-DP "pod" axis (2 x 16 x 16 = 512 chips).
Gradient all-reduce crosses the pod axis (DCN on real hardware) — the
gradient-compression hook in optim/adamw.py targets exactly that traffic.

Every axis is ``AxisType.Auto``: the model code places activations with
``with_sharding_constraint`` (``models.common.shard``), which needs Auto
axes (``jax.make_mesh`` defaults to Explicit ones).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    auto = (AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=auto)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 2):
    """Small mesh for CI-size sharding tests (requires >= n_data*n_model
    host devices, e.g. via XLA_FLAGS=--xla_force_host_platform_device_count)."""
    return _auto_mesh((n_data, n_model), ("data", "model"))
