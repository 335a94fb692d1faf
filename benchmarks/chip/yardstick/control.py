"""The correctness control: the program with its plain-f32 integral images.

The configurations state f64 signals; the program computes their integral
images on the chip in two-float (compensated) f32.  The step that would
tempt a later change is plain f32 images, and the program has that path
itself (its ``compensated: False`` config of the ``sat_moments`` backends:
the Pallas scan on a TPU, the jitted f32 reference elsewhere).
``plain_f32_images`` switches every ``sat_moments`` dispatch onto it; the
benchmark's comparison has to fail under it.
"""
from __future__ import annotations

__all__ = ["plain_f32_images"]


def plain_f32_images(engine=None):
    """Hook for ``harness.run``: route ``repro.ops.sat_moments`` through
    the program's plain-f32 path.  Returns a function that undoes it."""
    import jax

    from repro import ops
    orig = ops.sat_moments
    backend = "pallas" if jax.default_backend() == "tpu" else "xla"

    def sat_moments(y, *, backend=backend, **_):
        return orig(y, backend=backend, config={"compensated": False})

    ops.sat_moments = sat_moments

    def undo():
        ops.sat_moments = orig
    return undo
