"""Pure-jnp oracle for the 2D summed-area table (integral image), plus the
compensated-summation (two-float) f32 variants.

The compensated variants carry every partial sum as an unevaluated pair
``hi + lo`` of float32s (a "double-float"): prefix sums combine pairs with
Knuth's error-free TwoSum, so the rounding error of each addition lands in
the ``lo`` channel instead of being discarded.  The inputs are split the
same way (``hi = f32(x)``, ``lo = f32(x - f64(hi))``), which also captures
the f64 -> f32 cast error of the raw signal.  Recombining ``hi + lo`` in
f64 on the host yields integral images within ~1e-10 scaled relative error
(:func:`scaled_rel_err`) of the f64 oracle — comfortably inside the 1e-6
parity bound the tests hold them to — at roughly 3-4x the flops of the
plain f32 scan, all of them accelerator-resident.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..common import two_sum

__all__ = [
    "sat2d_ref", "sat_moments_ref", "delta_sat_ref", "sat_stack_ref",
    "split_hi_lo", "scaled_rel_err", "comp_cumsum", "sat_moments_comp_ref",
    "delta_sat_comp_ref", "sat_stack_comp_ref",
]


def sat2d_ref(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive 2D prefix sum of a (n, m) array."""
    return jnp.cumsum(jnp.cumsum(x, axis=0), axis=1)


def sat_moments_ref(y: jnp.ndarray) -> jnp.ndarray:
    """(3, n, m) integral images of (1, y, y^2) — the coreset's prefix stats.

    Canonical summation order is columns-within-row first, then down the
    rows: row i of the result is ``row i-1 + rowprefix(stk[i])``, which is
    exactly the recurrence ``delta_sat`` continues from a stored carry row.
    """
    stk = jnp.stack([jnp.ones_like(y), y, y * y], axis=0)
    return jnp.cumsum(jnp.cumsum(stk, axis=2), axis=1)


def delta_sat_ref(carry: jnp.ndarray, tail: jnp.ndarray) -> jnp.ndarray:
    """Patched integral-image rows for replaced/appended suffix rows.

    ``carry`` (3, m) is the integral-image row just above the patch (zeros
    when the patch starts at row 0); ``tail`` (b, m) holds the raw signal
    rows from the first changed row to the (new) end.  Returns (3, b, m):
    the rows of ``sat_moments_ref`` that change.
    """
    stk = jnp.stack([jnp.ones_like(tail), tail, tail * tail], axis=0)
    inner = jnp.cumsum(stk, axis=2)
    return carry[:, None, :] + jnp.cumsum(inner, axis=1)


def sat_stack_ref(stk: jnp.ndarray) -> jnp.ndarray:
    """Integral images over the last two axes of an arbitrarily-batched
    stack (columns-within-row first — same order as sat_moments_ref).  Used
    by the batched ``streaming_compress`` backends: one call integrates the
    moment rasters of every dirty merge-reduce bucket at once."""
    return jnp.cumsum(jnp.cumsum(stk, axis=-1), axis=-2)


# -------------------------------------------------- compensated (two-float)
def split_hi_lo(x) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Split a float64 host array into an (hi, lo) float32 pair with
    ``hi + lo == x`` to f32-pair precision (~2^-48 relative)."""
    import numpy as np
    x = np.asarray(x, np.float64)
    hi = np.asarray(x, np.float32)
    lo = np.asarray(x - np.asarray(hi, np.float64), np.float32)
    return jnp.asarray(hi), jnp.asarray(lo)


def scaled_rel_err(got, want) -> float:
    """max |got - want| scaled by the output's own magnitude (floor 1): the
    error measure the S2 - S1^2/S0 identity actually feels.  Elementwise
    relative error is meaningless here — integral images pass through
    near-zero entries whose denominators amplify benign f32 rounding."""
    import numpy as np
    got = np.asarray(got, np.float64).ravel()
    want = np.asarray(want, np.float64).ravel()
    scale = max(float(np.max(np.abs(want))) if want.size else 0.0, 1.0)
    return float(np.max(np.abs(got - want))) / scale if got.size else 0.0


def comp_cumsum(hi: jnp.ndarray, lo: jnp.ndarray,
                axis: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Compensated inclusive prefix sum along ``axis`` over (hi, lo) pairs."""
    return jax.lax.associative_scan(two_sum, (hi, lo), axis=axis)


def sat_moments_comp_ref(y_hi: jnp.ndarray, y_lo: jnp.ndarray
                         ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(hi, lo) pairs of the S1 and S2 moment integral images, (2, n, m)
    each.

    The count image ``S0[i, j] = (i+1)(j+1)`` depends on the shape alone,
    so the device neither computes nor ships it: the host makes it exactly
    in f64 where it lands the pairs (``repro.ops.backends``).  Only S1 and
    S2 pay for the compensated scans.  ``y^2`` enters as the pair
    ``(hi*hi, 2*hi*lo)``: the dropped ``lo^2`` term is ~2^-96 relative.
    """
    hi2, lo2 = y_hi * y_hi, 2.0 * y_hi * y_lo
    stk_hi = jnp.stack([y_hi, hi2], 0)
    stk_lo = jnp.stack([y_lo, lo2], 0)
    h, l = comp_cumsum(stk_hi, stk_lo, axis=2)
    return comp_cumsum(h, l, axis=1)


def delta_sat_comp_ref(carry_hi, carry_lo, tail_hi, tail_lo
                       ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Compensated twin of ``delta_sat_ref``: (3, b, m) patched rows as
    (hi, lo) pairs, the stored carry row entering as its own pair so chained
    patches keep full two-float precision."""
    ones = jnp.ones_like(tail_hi)
    hi2, lo2 = tail_hi * tail_hi, 2.0 * tail_hi * tail_lo
    stk_hi = jnp.stack([ones, tail_hi, hi2], 0)
    stk_lo = jnp.stack([jnp.zeros_like(tail_hi), tail_lo, lo2], 0)
    h, l = comp_cumsum(stk_hi, stk_lo, axis=2)
    # continue the row recurrence from the carry pair: prepend, scan, drop
    h = jnp.concatenate([carry_hi[:, None, :], h], axis=1)
    l = jnp.concatenate([carry_lo[:, None, :], l], axis=1)
    h, l = comp_cumsum(h, l, axis=1)
    return h[:, 1:, :], l[:, 1:, :]


def sat_stack_comp_ref(stk_hi: jnp.ndarray, stk_lo: jnp.ndarray
                       ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Compensated twin of ``sat_stack_ref`` over (hi, lo) pairs."""
    h, l = comp_cumsum(stk_hi, stk_lo, axis=-1)
    return comp_cumsum(h, l, axis=-2)
