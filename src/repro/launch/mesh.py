"""Production meshes.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state): 16x16 = 256 chips per pod; multi_pod adds the 2-pod axis
(512 chips).  ``make_local_mesh`` builds the biggest (data, model) grid the
current process offers — used by smoke tests and the CPU examples.

Both build Auto axes: the models place activations with
``with_sharding_constraint`` (``parallel.sharding.shard``), which accepts
only Auto mesh axes."""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh(model_parallel: int = 1):
    n = len(jax.devices())
    model_parallel = min(model_parallel, n)
    while n % model_parallel:
        model_parallel -= 1
    return _auto_mesh((n // model_parallel, model_parallel),
                      ("data", "model"))
