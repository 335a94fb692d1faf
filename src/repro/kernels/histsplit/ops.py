"""Jit'd public wrapper for the histsplit kernel (matches the numpy-side
signature used by ``repro.trees.cart``)."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..sat2d.ref import split_hi_lo
from .kernel import histograms_kernel_call

__all__ = ["histograms"]


def histograms(codes, w, wy, wy2, n_bins: int, *, tile_p: int = 2048,
               variant: str = "fused", interpret: bool | None = None):
    """codes: (P, F) uint8/int; w/wy/wy2: (P,). Returns (F, n_bins, 3).

    ``variant="partials"`` is the compensated path: each value column is
    split into an (hi, lo) f32 pair (capturing the f64 -> f32 cast error),
    the kernel bins all six channels and emits per-P-tile partial
    histograms, and the cross-tile + hi/lo reduction happens here in f64 —
    so neither the input cast nor the scatter order of a long P axis leaves
    f32-level error in the bin sums.  ``variant="fused"`` accumulates the
    three f32 channels across tiles on the device.
    """
    codes_fp = jnp.asarray(np.asarray(codes).T, jnp.int32)       # (F, P)
    if variant == "partials":
        pairs = [split_hi_lo(a) for a in (w, wy, wy2)]
        vals = jnp.stack([p[0] for p in pairs]
                         + [p[1] for p in pairs], axis=1)        # (P, 6)
        out = histograms_kernel_call(codes_fp, vals, n_bins, tile_p=tile_p,
                                     variant=variant, interpret=interpret)
        out = np.asarray(out, np.float64)          # (C, F, n_bins, 6)
        return out[..., :3].sum(axis=0) + out[..., 3:].sum(axis=0)
    vals = jnp.stack([jnp.asarray(w, jnp.float32),
                      jnp.asarray(wy, jnp.float32),
                      jnp.asarray(wy2, jnp.float32)], axis=1)    # (P, 3)
    return histograms_kernel_call(codes_fp, vals, n_bins, tile_p=tile_p,
                                  variant=variant, interpret=interpret)
