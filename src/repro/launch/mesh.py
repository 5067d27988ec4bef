"""Production mesh builders.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import and only then calls these.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_elastic_mesh(n_devices: int | None = None, model_parallel: int = 16):
    """Best mesh for an arbitrary (possibly degraded) device count — the
    elastic-restart path: keep TP fixed at what fits a model replica, put
    everything else on data."""
    n = n_devices or len(jax.devices())
    while n % model_parallel and model_parallel > 1:
        model_parallel //= 2
    return _auto_mesh((n // model_parallel, model_parallel),
                      ("data", "model"))


def make_host_mesh():
    """1-device mesh for CPU smoke tests."""
    return _auto_mesh((1, 1), ("data", "model"))


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the installed JAX defaults to
    Explicit axes, under which the ``with_sharding_constraint`` rules of
    this package do not apply."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))
