"""Production meshes.

Defined as functions (never module-level constants) so importing this module
never touches jax device state.  The dry-run forces 512 host devices before
any jax import; smoke tests and benches see the real single device.
"""
from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_local_mesh", "make_mesh"]


def make_mesh(shape, axes, devices):
    """``jax.make_mesh`` with Auto axes: the sharding rules in this repo are
    written for compiler-propagated (pjit-style) shardings, while
    ``jax.make_mesh`` defaults to Explicit axes."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips single-pod; 2x16x16 = 512 chips across two pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for {shape} mesh, have {len(devices)} — "
            "set XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "importing jax (launch/dryrun.py does this)")
    return make_mesh(shape, axes, devices)


def make_local_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over whatever devices exist (tests / CPU runs)."""
    n = data * model
    return make_mesh((data, model), ("data", "model"), jax.devices()[:n])
