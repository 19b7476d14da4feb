"""Elastic scaling: re-mesh live state when the device pool changes.

Node loss (or growth) at scale means the mesh shape changes.  The
recovery path implemented here:

  1. ``shrink_mesh``/``make_elastic_mesh`` builds a new mesh over the
     surviving devices (keeping the 'model' extent if possible — TP
     degree is baked into weight shapes' divisibility, DP is not);
  2. ``remesh_tree`` re-shards a live pytree onto the new mesh with
     freshly resolved specs (the divisibility-aware rules in
     sharding/specs.py re-evaluate against the new axis sizes);
  3. the launcher re-jits its step for the new mesh and continues from
     the in-memory state — no checkpoint round-trip needed when the
     state survived; otherwise ckpt.restore provides it.

Tested by training on a mesh over N fake devices, shrinking to N/2,
and asserting loss continuity (tests/test_elastic.py).
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh

from repro.sharding import specs as S


def make_elastic_mesh(devices=None, model_parallel: int | None = None) -> Mesh:
    """Mesh over an arbitrary device list: ('data', 'model')."""
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if model_parallel is None:
        model_parallel = 1
        for cand in (16, 8, 4, 2):
            if n % cand == 0:
                model_parallel = cand
                break
    assert n % model_parallel == 0
    arr = np.array(devices).reshape(n // model_parallel, model_parallel)
    return Mesh(arr, ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)


def shrink_mesh(mesh: Mesh, lost_devices: set) -> Mesh:
    """Rebuild the mesh without the lost devices (same axis names)."""
    survivors = [d for d in mesh.devices.flatten() if d.id not in lost_devices]
    model = mesh.shape.get("model", 1)
    while model > 1 and len(survivors) % model != 0:
        model //= 2
    usable = (len(survivors) // model) * model
    return make_elastic_mesh(survivors[:usable], model_parallel=model)


def remesh_tree(tree, new_mesh: Mesh, spec_fn=S.param_specs):
    """Re-shard a live pytree onto a new mesh.

    Device buffers are pulled to host implicitly by jax.device_put when
    source and destination shardings differ; at multi-host scale this
    becomes a resharding transfer — the API is the same.
    """
    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
    new_specs = spec_fn(abstract, new_mesh)
    named = S.to_named(new_specs, new_mesh)
    return jax.device_put(tree, named)


def remesh_train_state(params, opt_state, new_mesh: Mesh):
    params = remesh_tree(params, new_mesh, S.param_specs)
    opt_state = remesh_tree(opt_state, new_mesh, S.opt_state_specs)
    return params, opt_state
