"""Elastic re-meshing: shrink/grow the mesh around failed hosts.

``plan_mesh``: given the healthy device count and a model-parallel size that
must be preserved (TP degree is baked into layouts/divisibility), pick the
largest (data, model) grid that fits — data parallelism absorbs the loss.
``reshard``: device_put a checkpointed state tree onto the new mesh's
shardings (restore and reshard are the same code path; see
checkpoint/checkpointer.py).
"""
from __future__ import annotations

import jax

from repro.launch.mesh import make_mesh
from repro.sharding import named, opt_specs, param_specs

__all__ = ["plan_mesh", "reshard_state"]


def plan_mesh(n_healthy: int, model_size: int, axis_names=("data", "model")):
    """Largest (data, model_size) mesh with data * model_size <= n_healthy."""
    if n_healthy < model_size:
        raise RuntimeError(
            f"cannot keep TP={model_size} with only {n_healthy} devices")
    data = n_healthy // model_size
    devices = jax.devices()[: data * model_size]
    return make_mesh((data, model_size), axis_names, devices)


def reshard_state(state: dict, params_shapes, new_mesh):
    """Re-place {params, opt} onto a new mesh after an elastic resize."""
    ps = named(new_mesh, param_specs(params_shapes, new_mesh))
    os_ = named(new_mesh, opt_specs(params_shapes, new_mesh))
    out = dict(state)
    out["params"] = jax.tree.map(jax.device_put, state["params"], ps)
    if "opt" in state:
        out["opt"] = jax.tree.map(jax.device_put, state["opt"], os_)
    return out
