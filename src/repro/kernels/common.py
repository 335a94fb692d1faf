"""Shared kernel plumbing: interpret-mode default, tiling helpers and the
in-kernel lane scans.

All kernels are written against the TPU backend (pl.pallas_call + BlockSpec
VMEM tiling, MXU-aligned shapes); on CPU they run the kernel body under
``interpret=True`` (the correctness path the test suite runs).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

__all__ = ["default_interpret", "ceil_div", "pad_to", "lane_prefix_sum",
           "two_sum", "lane_prefix_sum_pair"]


def default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def lane_prefix_sum(x, segment: int | None = None):
    """Inclusive prefix sum along the lane (last) axis inside a kernel.

    Mosaic has no ``cumsum`` lowering, so this is the log-step
    (Hillis-Steele) scan: step d rotates the tile d lanes up with
    ``pltpu.roll`` and adds it where the lane's position is >= d.
    ``segment`` restarts the sum every ``segment`` lanes (a power of two
    dividing the lane count), so several short scans can share one vreg
    row.  The lane count must be a multiple of 128 on the chip.
    """
    width = x.shape[-1]
    seg = width if segment is None else segment
    pos = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    if seg != width:
        pos = pos % seg
    d = 1
    while d < seg:
        x = x + jnp.where(pos >= d, pltpu.roll(x, d, x.ndim - 1), 0.0)
        d *= 2
    return x


def two_sum(a, b):
    """Knuth TwoSum on (hi, lo) pairs: the rounding error of the ``hi``
    addition is recovered exactly and folded into ``lo``."""
    a_hi, a_lo = a
    b_hi, b_lo = b
    s = a_hi + b_hi
    z = s - a_hi
    err = (a_hi - (s - z)) + (b_hi - z)
    return s, a_lo + b_lo + err


def lane_prefix_sum_pair(hi, lo):
    """Compensated twin of :func:`lane_prefix_sum` over an unevaluated
    (hi, lo) float pair: the same log-step scan, each step combining with
    :func:`two_sum`."""
    axis = hi.ndim - 1
    width = hi.shape[-1]
    pos = jax.lax.broadcasted_iota(jnp.int32, hi.shape, axis)
    d = 1
    while d < width:
        keep = pos >= d
        hi, lo = two_sum((hi, lo), (
            jnp.where(keep, pltpu.roll(hi, d, axis), 0.0),
            jnp.where(keep, pltpu.roll(lo, d, axis), 0.0)))
        d *= 2
    return hi, lo


def ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def pad_to(x, multiple: int, axis: int):
    import jax.numpy as jnp
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x, size
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), size
