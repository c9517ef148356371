"""Production mesh builders.  Functions (never module-level constants) so
importing this module does not touch jax device state."""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips, axes (data, model).
    Multi-pod: 2 pods x 256 = 512 chips, axes (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def _auto(n: int) -> tuple:
    """Model code shards through GSPMD (``with_sharding_constraint``), so
    every mesh axis is Auto; ``jax.make_mesh`` defaults to Explicit."""
    return (jax.sharding.AxisType.Auto,) * n


def make_local_mesh(model_parallel: int = 1, axes=("data", "model")):
    """Whatever devices exist locally, folded into (data, model)."""
    n = len(jax.devices())
    assert n % model_parallel == 0
    return jax.make_mesh((n // model_parallel, model_parallel), axes,
                         axis_types=_auto(len(axes)))
