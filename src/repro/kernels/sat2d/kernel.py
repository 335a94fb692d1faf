"""Pallas TPU kernel: blocked 2D prefix sum (summed-area table).

Two sequential-grid passes, each a 1D scan with a VMEM carry:

  pass 1 (rows):    grid = (R/TR, C/TC); within a (TR, TC) tile compute the
                    row-wise prefix sum on the VPU (log-step shift-and-add,
                    see ``lane_prefix_sum``) and add the running carry
                    (TR, 1) kept in VMEM scratch.  TPU grids execute
                    sequentially with the last axis innermost, so the carry
                    is valid across the column tiles of one row band and is
                    reset when a new band starts (program_id(1) == 0).
  pass 2 (columns): the same kernel on the transposed layout.

``scan_rows_comp`` is the compensated twin: the values and the carry are
unevaluated (hi, lo) f32 pairs combined with TwoSum (see ``ref.py``), so
the scan keeps ~2^-48 relative precision on the chip.

Tiles are padded to the (8, 128) f32 vreg quantum — Mosaic rotates whole
lane rows only.  Tile sizes default to (256, 256) f32 — 256 KiB per buffer,
well inside the ~16 MiB/core VMEM budget including double buffering.  HBM
traffic is one read + one write per pass; the win over the XLA lowering is
fusing the (1, y, y^2) channel stack of the coreset's prefix-statistics
stage into one pass (see ops.sat_moments).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import (ceil_div, default_interpret, lane_prefix_sum,
                      lane_prefix_sum_pair, two_sum)

__all__ = ["scan_rows", "scan_rows_comp", "sat2d"]


def _row_scan_kernel(x_ref, o_ref, carry_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    cs = lane_prefix_sum(x_ref[...]) + carry_ref[...]
    o_ref[...] = cs
    carry_ref[...] = cs[:, -1:]


def _row_scan_seeded_kernel(x_ref, init_ref, o_ref, carry_ref):
    # identical to _row_scan_kernel except the running carry starts from a
    # caller-provided (TR, 1) column instead of zeros — the delta-SAT patch
    # continues a prefix sum from the integral-image row above the patch
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        carry_ref[...] = init_ref[...]

    cs = lane_prefix_sum(x_ref[...]) + carry_ref[...]
    o_ref[...] = cs
    carry_ref[...] = cs[:, -1:]


def _comp_row_scan_kernel(xh_ref, xl_ref, ih_ref, il_ref, oh_ref, ol_ref,
                          ch_ref, cl_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        ch_ref[...] = ih_ref[...]
        cl_ref[...] = il_ref[...]

    h, l = lane_prefix_sum_pair(xh_ref[...], xl_ref[...])
    h, l = two_sum((h, l), (ch_ref[...], cl_ref[...]))
    oh_ref[...] = h
    ol_ref[...] = l
    ch_ref[...] = h[:, -1:]
    cl_ref[...] = l[:, -1:]


def _tiling(n: int, m: int, tile_r: int, tile_c: int):
    """(tile rows, tile cols, row pad, col pad): tiles on the (8, 128)
    quantum, the array padded to whole tiles (trailing zeros leave every
    prefix unchanged)."""
    tr = min(tile_r, ceil_div(n, 8) * 8)
    tc = min(tile_c, ceil_div(m, 128) * 128)
    return tr, tc, (-n) % tr, (-m) % tc


@functools.partial(jax.jit, static_argnames=("tile_r", "tile_c", "interpret"))
def scan_rows(x: jnp.ndarray, tile_r: int = 256, tile_c: int = 256,
              interpret: bool | None = None,
              init: jnp.ndarray | None = None) -> jnp.ndarray:
    """Row-wise inclusive cumsum of a 2D array via the blocked kernel.

    ``init`` (optional, shape (n, 1)) seeds the running carry of each row:
    row i scans as ``init[i] + cumsum(x[i])`` — the continuation used by the
    ``delta_sat`` patch op, where ``init`` is the last unchanged prefix row.
    """
    if interpret is None:
        interpret = default_interpret()
    n, m = x.shape
    tr, tc, pad_r, pad_c = _tiling(n, m, tile_r, tile_c)
    xp = jnp.pad(x, ((0, pad_r), (0, pad_c)))
    np_, mp = xp.shape
    if init is None:
        out = pl.pallas_call(
            _row_scan_kernel,
            grid=(np_ // tr, mp // tc),
            in_specs=[pl.BlockSpec((tr, tc), lambda i, j: (i, j))],
            out_specs=pl.BlockSpec((tr, tc), lambda i, j: (i, j)),
            out_shape=jax.ShapeDtypeStruct((np_, mp), x.dtype),
            scratch_shapes=[pltpu.VMEM((tr, 1), x.dtype)],
            interpret=interpret,
        )(xp)
    else:
        ip = jnp.pad(init.astype(x.dtype), ((0, pad_r), (0, 0)))
        out = pl.pallas_call(
            _row_scan_seeded_kernel,
            grid=(np_ // tr, mp // tc),
            in_specs=[pl.BlockSpec((tr, tc), lambda i, j: (i, j)),
                      pl.BlockSpec((tr, 1), lambda i, j: (i, 0))],
            out_specs=pl.BlockSpec((tr, tc), lambda i, j: (i, j)),
            out_shape=jax.ShapeDtypeStruct((np_, mp), x.dtype),
            scratch_shapes=[pltpu.VMEM((tr, 1), x.dtype)],
            interpret=interpret,
        )(xp, ip)
    return out[:n, :m]


@functools.partial(jax.jit, static_argnames=("tile_r", "tile_c", "interpret"))
def scan_rows_comp(hi: jnp.ndarray, lo: jnp.ndarray, tile_r: int = 256,
                   tile_c: int = 256, interpret: bool | None = None,
                   init: tuple | None = None) -> tuple:
    """Compensated row-wise inclusive cumsum of the pair ``hi + lo``; the
    optional ``init`` (hi, lo) pair of (n, 1) columns seeds each row's
    carry like ``scan_rows(init=...)``."""
    if interpret is None:
        interpret = default_interpret()
    n, m = hi.shape
    tr, tc, pad_r, pad_c = _tiling(n, m, tile_r, tile_c)
    if init is None:
        init = (jnp.zeros((n, 1), hi.dtype), jnp.zeros((n, 1), hi.dtype))
    xs = [jnp.pad(a, ((0, pad_r), (0, pad_c))) for a in (hi, lo)]
    cs = [jnp.pad(a.astype(hi.dtype), ((0, pad_r), (0, 0))) for a in init]
    np_, mp = xs[0].shape
    tile = pl.BlockSpec((tr, tc), lambda i, j: (i, j))
    col = pl.BlockSpec((tr, 1), lambda i, j: (i, 0))
    out_h, out_l = pl.pallas_call(
        _comp_row_scan_kernel,
        grid=(np_ // tr, mp // tc),
        in_specs=[tile, tile, col, col],
        out_specs=[tile, tile],
        out_shape=[jax.ShapeDtypeStruct((np_, mp), hi.dtype)] * 2,
        scratch_shapes=[pltpu.VMEM((tr, 1), hi.dtype)] * 2,
        interpret=interpret,
    )(*xs, *cs)
    return out_h[:n, :m], out_l[:n, :m]


def sat2d(x: jnp.ndarray, tile: int = 256, interpret: bool | None = None) -> jnp.ndarray:
    """Inclusive 2D prefix sum: row scan, then column scan (transposed)."""
    r = scan_rows(x, tile, tile, interpret=interpret)
    return scan_rows(r.T, tile, tile, interpret=interpret).T
