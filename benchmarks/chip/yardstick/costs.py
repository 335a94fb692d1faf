"""Operations and bytes that each kernel call needs, from its shapes.

These count what the algorithm has to do for the call, never the padding
or layout work of one implementation, so a kernel's roofline share
(least time over device time) cannot pass 100% unless its device time
leaves out work.

``fitting_loss_batched(B, T, K)``: Algorithm 5 of T trees of K leaves
against a coreset of B blocks of 4 points.  Per (block, leaf) pair: the
row and column overlap (min, max, subtract, clamp: 4 + 4), their product
(1), the leaf's cumulative mass (1), and per point the consumed mass (min,
max, subtract, clamp: 4) and its weighted squared residual (subtract,
multiply, multiply, add: 4) — 10 + 4 x 8 = 42.  Bytes: each block's 4
rect corners, 4 labels and 4 weights once (12 f32), each leaf's rect and
label once (5 f32), one f32 loss per tree.

``sat_moments_comp(n, m)``: the (1, y, y^2) integral images in two-float
precision.  Reads y as a (hi, lo) f32 pair (8 bytes a cell) and writes
three images as (hi, lo) pairs (24 bytes a cell).  Per cell: y^2 and its
low part (3), and per moment channel y, y^2 two compensated prefix passes
of 6 operations each (TwoSum) — 3 + 2 x 2 x 6 = 27.
"""
from __future__ import annotations

__all__ = ["fitting_loss_batched", "sat_moments_comp", "least_seconds"]

F32 = 4


def fitting_loss_batched(B: int, T: int, K: int) -> tuple[float, float]:
    """(operations, bytes) of one call."""
    ops = 42.0 * B * T * K
    nbytes = F32 * (12.0 * B + 5.0 * T * K + T)
    return ops, nbytes


def sat_moments_comp(n: int, m: int) -> tuple[float, float]:
    cells = float(n) * float(m)
    return 27.0 * cells, 32.0 * cells


def least_seconds(ops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_ops = ops / peaks["flops_per_s"]
    t_bytes = nbytes / peaks["bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")
