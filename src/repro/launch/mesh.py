"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module never touches jax device state. The dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax
import; smoke tests and benchmarks see the real (1-device) platform.

Mesh shapes:
  single-pod: (16, 16)    axes ("data", "model")   = 256 chips (one v5e pod)
  multi-pod : (2, 16, 16) axes ("pod", "data", "model") = 512 chips
The "pod" axis is pure data parallelism (DCN-friendly: one gradient
reduction per step crosses it).
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple, axes: tuple) -> Mesh:
    """Arbitrary mesh for tests/examples (e.g. (2,4) on 8 host devices).
    Axes are `Auto`: the sharding rules place arrays with
    `with_sharding_constraint`, which refuses `Explicit` axes."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def elastic_mesh(preferred: tuple = (16, 16),
                 axes: tuple = ("data", "model")) -> Mesh:
    """Build the largest mesh the live device set supports (elastic
    scaling: on restart after losing hosts, keep the model axis and shrink
    the data axis -- checkpoint resharding handles the rest)."""
    n = len(jax.devices())
    model = preferred[-1]
    while model > 1 and n % model:
        model //= 2
    data = n // model
    return make_mesh((data, model), axes)
