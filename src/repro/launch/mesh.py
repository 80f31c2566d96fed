"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches JAX device state.  The single-pod mesh is a 16×16 = 256-chip
TPU v5e pod (data × model); the multi-pod mesh adds a leading DCN "pod"
axis (2 pods = 512 chips).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_elastic_mesh(devices: Optional[Sequence] = None, *,
                      model_parallel: int = 1):
    """Mesh from whatever devices are alive (elastic restart path).

    The data axis absorbs every device not used by model parallelism, so a
    checkpoint written on N hosts restores onto M hosts with only the data
    sharding re-derived.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if n % model_parallel:
        raise ValueError(f"{n} devices not divisible by {model_parallel=}")
    import numpy as np
    arr = np.asarray(devices).reshape(n // model_parallel, model_parallel)
    from jax.sharding import Mesh
    return Mesh(arr, ("data", "model"),
                axis_types=(AxisType.Auto, AxisType.Auto))

