"""Pallas TPU kernel: Algorithm 5 (FITTING-LOSS) evaluation, fused.

The tree-tuning inner loop evaluates many candidate k-trees against the
coreset.  Per (block tile, tree tile): rectangle-overlap counts, the
cumulative-mass interval overlap (the closed form of the paper's while-loop,
see core/fitting_loss.py), and the weighted squared-difference reduction —
all fused in VMEM, so HBM traffic is one read of the coreset tile and the
segmentation tile instead of a (B, K, 4) intermediate.

Layout (every in-kernel array is 2-D and lane-dense):

  * coreset blocks ride the sublane axis: a (TB, 16) tile packs
    rects | labels | weights | zero pad, read as (TB, 1) columns;
  * the leaves of TT candidate trees ride the lane axis: the segmentation
    is passed as an (8, T*Kp) array whose rows are r0, r1, c0, c1, label
    (rows 5-7 zero) and whose lanes run tree-major, each tree's K leaves
    zero-padded to Kp.  A zero-area leaf consumes no mass, so padding
    changes no loss;
  * the cumulative leaf mass Z (a cumsum over each tree's leaves, in
    ascending label order, ``ref.by_label``) is a segmented lane scan
    (``common.lane_prefix_sum``).

Grid (T/TT, B/TB) with the B axis innermost: TPU grids execute
sequentially with the last axis fastest, so each (1, TT*Kp) output tile
accumulates its per-leaf partial losses in place (initialized at b == 0);
the wrapper folds each tree's Kp lanes.  The coreset tile is read once per
(t, b) cell and scored against TT candidate trees while resident in VMEM.
``fitting_loss_call`` (one segmentation) is the T = 1 case.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common import ceil_div, default_interpret, lane_prefix_sum
from .ref import by_label

__all__ = ["fitting_loss_call", "fitting_loss_batched_call"]

_LANES = 128
_MAX_TILE_ELEMS = 1 << 16   # TB * TT * Kp f32 per live (TB, L) temporary


def _fl_kernel(seg_ref, blk_ref, o_ref, *, seg_len):
    b = pl.program_id(1)

    @pl.when(b == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    blk = blk_ref[...]                         # (TB, 16)
    seg = seg_ref[...]                         # (8, L)

    def col(j):
        return blk[:, j:j + 1]                 # (TB, 1)

    def row(i):
        return seg[i:i + 1, :]                 # (1, L)

    z_r = jnp.maximum(jnp.minimum(col(1), row(1))
                      - jnp.maximum(col(0), row(0)), 0.0)
    z_c = jnp.maximum(jnp.minimum(col(3), row(3))
                      - jnp.maximum(col(2), row(2)), 0.0)
    z = z_r * z_c                              # (TB, L) overlap counts
    Us = [col(8)]                              # cumulative point mass
    for p in range(1, 4):
        Us.append(Us[-1] + col(8 + p))
    # a leaf covering the whole block consumes all of its mass (see ref.py)
    area = (col(1) - col(0)) * (col(3) - col(2))
    z = jnp.where(z == area, Us[3], z)
    Z = lane_prefix_sum(z, seg_len)            # per-tree cumulative mass
    Zp = Z - z
    label = row(4)
    acc = jnp.zeros_like(z)
    for p in range(4):                         # the block's 4 points
        U = Us[p]
        Up = U - col(8 + p)
        consumed = jnp.maximum(jnp.minimum(Z, U) - jnp.maximum(Zp, Up), 0.0)
        diff = label - col(4 + p)
        acc = acc + consumed * diff * diff
    o_ref[...] += jnp.sum(acc, axis=0, keepdims=True)


def _leaf_lanes(K: int) -> int:
    """Lanes per tree: K padded to a power of two below one vreg row (so
    trees tile a 128-lane row evenly), to a multiple of 128 above."""
    if K >= _LANES:
        return ceil_div(K, _LANES) * _LANES
    return 1 << max(K - 1, 0).bit_length()


@functools.partial(jax.jit,
                   static_argnames=("tile_b", "tile_t", "interpret"))
def fitting_loss_batched_call(rects, labels4, weights4, seg_rects, seg_labels,
                              tile_b: int = 512, tile_t: int = 8,
                              interpret: bool | None = None):
    """(T,) Algorithm-5 losses, one pallas_call for the whole candidate set.

    rects/labels4/weights4: (B, 4) f32; seg_rects: (T, K, 4) f32;
    seg_labels: (T, K) f32.  B pads with zero-weight blocks (no loss),
    T pads with zero segmentations (rows sliced off).  ``tile_t`` is
    rounded up so a tree tile fills whole 128-lane rows, and ``tile_b`` is
    capped so the fused (TB, TT*Kp) temporaries stay inside VMEM.
    """
    if interpret is None:
        interpret = default_interpret()
    B = rects.shape[0]
    T, K = seg_rects.shape[0], seg_rects.shape[1]
    kp = _leaf_lanes(K)
    per_row = max(_LANES // kp, 1)             # trees per 128-lane row
    tt = min(ceil_div(max(tile_t, 1), per_row), ceil_div(T, per_row)) * per_row
    lanes = tt * kp
    cap = max(8, (_MAX_TILE_ELEMS // lanes) // 8 * 8)
    tb = min(ceil_div(tile_b, 8) * 8, ceil_div(max(B, 1), 8) * 8, cap)
    Bp = ceil_div(max(B, 1), tb) * tb
    Tp = ceil_div(T, tt) * tt

    seg_rects, seg_labels = by_label(seg_rects, seg_labels)
    blk = jnp.concatenate([rects, labels4, weights4,
                           jnp.zeros((B, 4), rects.dtype)], axis=1)  # (B,16)
    blk = jnp.pad(blk, ((0, Bp - B), (0, 0)))  # zero-weight blocks: no loss
    seg = jnp.concatenate([seg_rects, seg_labels[..., None]], axis=-1)
    seg = jnp.pad(seg, ((0, Tp - T), (0, kp - K), (0, 3)))     # (Tp, kp, 8)
    seg = seg.reshape(Tp * kp, 8).T                            # (8, Tp*kp)
    out = pl.pallas_call(
        functools.partial(_fl_kernel, seg_len=kp),
        grid=(Tp // tt, Bp // tb),             # B innermost: accumulation
        in_specs=[
            pl.BlockSpec((8, lanes), lambda t, b: (0, t)),
            pl.BlockSpec((tb, 16), lambda t, b: (b, 0)),
        ],
        out_specs=pl.BlockSpec((1, lanes), lambda t, b: (0, t)),
        out_shape=jax.ShapeDtypeStruct((1, Tp * kp), jnp.float32),
        interpret=interpret,
    )(seg.astype(jnp.float32), blk.astype(jnp.float32))
    return out.reshape(Tp, kp)[:T].sum(axis=1)


@functools.partial(jax.jit, static_argnames=("tile_b", "interpret"))
def fitting_loss_call(rects, labels4, weights4, seg_rects, seg_labels,
                      tile_b: int = 1024, interpret: bool | None = None):
    """Scalar Algorithm-5 loss. rects/labels4/weights4: (B, 4) f32;
    seg_rects: (K, 4) f32; seg_labels: (K,) f32."""
    return fitting_loss_batched_call(
        rects, labels4, weights4, seg_rects[None], seg_labels[None],
        tile_b=tile_b, tile_t=1, interpret=interpret)[0]
