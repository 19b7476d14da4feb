"""Production mesh construction.

A function, not a module-level constant, so importing this module never
touches jax device state.  Single pod: 16×16 = 256 chips (TPU v5e pod);
multi-pod: 2 × 256 = 512 chips with the leading 'pod' axis crossing the
inter-pod (DCN-class) boundary — gradient reduction and nothing else
should travel on it.

Every mesh here has Auto axes: shardings are propagated by the compiler
from ``with_sharding_constraint`` hints, and explicit per-device code
goes through ``jax.shard_map``.  Activate a mesh with ``jax.set_mesh``.
"""

from __future__ import annotations

import jax


def make_mesh(shape: tuple, axes: tuple):
    """``jax.make_mesh`` over the first prod(shape) devices, Auto axes."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(n_devices: int | None = None):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = n_devices or len(jax.devices())
    model = 1
    for cand in (4, 2, 1):
        if n % cand == 0:
            model = cand
            break
    return make_mesh((n // model, model), ("data", "model"))
