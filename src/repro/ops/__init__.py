"""repro.ops — one canonical op surface, three backends per op.

The serving/tuning hot paths reduce to six primitives:

  ``sat_moments(y)``                     (3, n, m) integral images of
                                         (1, y, y²) — PrefixStats' build
  ``delta_sat(carry, tail)``             the integral-image rows that change
                                         when a row band is replaced or
                                         appended — the O(band) ingest patch
  ``fitting_loss(cs, rects, labels)``    Algorithm-5 loss of one tree
  ``fitting_loss_batched(cs, R, L)``     (T,) losses, one fused evaluation
  ``hist_split(codes, w, wy, wy2, B)``   CART split histograms
  ``streaming_compress(coresets)``       merge-reduce recompress of many
                                         buckets in one dispatch

Each dispatches through the backend registry (numpy oracle / jitted xla /
Pallas kernel), selected by platform and problem size — see
``registry.py`` for the rule.
Core, trees, and the serving engine all route through this module instead
of importing kernel modules directly, so both the read path (losses,
histograms) and the write path (delta ingest, streaming compress) are
backend-swappable and benchmarkable through one surface.
"""
from __future__ import annotations

import numpy as np

from . import backends as _backends  # noqa: F401  (registers implementations)
from .registry import (BACKENDS, OPS, BackendError, available_backends,
                       backend_override, device_info, dispatch, register,
                       resolve, select_backend, snapshot)

__all__ = [
    "OPS", "BACKENDS", "BackendError", "available_backends",
    "backend_override", "device_info", "dispatch", "register",
    "resolve", "select_backend", "selected_backend", "snapshot",
    "sat_moments", "delta_sat", "fitting_loss", "fitting_loss_batched",
    "hist_split", "streaming_compress",
    "fitting_loss_size", "fitting_loss_batched_size",
]


def sat_moments(y, *, backend: str | None = None, **kw) -> np.ndarray:
    """(3, n, m) integral images of (1, y, y^2) for a 2-D signal."""
    y = np.asarray(y)
    if y.ndim != 2:
        raise ValueError(f"signal must be 2D, got shape {y.shape}")
    return dispatch("sat_moments", y, backend=backend, size=3 * y.size, **kw)


def delta_sat(carry, tail, *, backend: str | None = None, **kw) -> np.ndarray:
    """(3, b, m) patched integral-image rows for a replaced/appended band.

    ``carry`` (3, m) is the integral-image row just above the first changed
    row (zeros when patching from row 0); ``tail`` (b, m) holds the raw
    signal rows from the first changed row to the (new) end of the signal.
    The numpy oracle continues the canonical ``sat_moments`` recurrence with
    the exact same sequential float64 additions, so chained delta patches
    are bitwise equal to a from-scratch rebuild; like ``sat_moments`` it
    never size-promotes off the f64 oracle (the rows feed S2 - S1^2/S0).
    """
    carry = np.asarray(carry)
    tail = np.asarray(tail)
    if tail.ndim != 2 or tail.shape[0] < 1:
        raise ValueError(f"tail must be a non-empty 2D band, got {tail.shape}")
    if carry.shape != (3, tail.shape[1]):
        raise ValueError(f"carry must have shape (3, {tail.shape[1]}), "
                         f"got {carry.shape}")
    return dispatch("delta_sat", carry, tail, backend=backend,
                    size=3 * tail.size, **kw)


def streaming_compress(coresets, k: int | None = None,
                       eps: float | None = None, *,
                       backend: str | None = None, **kw) -> list:
    """Merge-reduce "reduce": recompress a list of composed coresets.

    One dispatch recompresses every bucket in ``coresets`` (the dirty
    buckets of a merge-reduce level); the accelerator backends integrate all
    per-bucket moment rasters in a single batched call.  ``k``/``eps``
    default to each coreset's own parameters.  Precision-critical like
    ``sat_moments``: the rebuilt prefix stats feed the variance identity, so
    the f64 numpy oracle is never size-promoted away.
    """
    coresets = list(coresets)
    if not coresets:
        return []
    size = 3 * sum(int(cs.n) * int(cs.m) for cs in coresets)
    return dispatch("streaming_compress", coresets, k, eps, backend=backend,
                    size=size, **kw)


def fitting_loss_size(cs, seg_rects) -> int:
    """Selection 'size' of a fitting_loss problem (blocks x leaves) — the
    one definition shared by the wrapper below and callers that need to
    know the backend a dispatch will use (``selected_backend``)."""
    k = np.asarray(seg_rects).reshape(-1, 4).shape[0]
    return cs.num_blocks * max(k, 1)


def fitting_loss_batched_size(cs, seg_rects) -> int:
    """Selection 'size' of a batched problem (trees x blocks x leaves)."""
    sr = np.asarray(seg_rects)
    return cs.num_blocks * sr.shape[0] * max(sr.shape[1], 1)


def fitting_loss(cs, seg_rects, seg_labels, *,
                 backend: str | None = None, **kw) -> float:
    """Scalar Algorithm-5 loss of one k-segmentation against ``cs``."""
    sr = np.asarray(seg_rects).reshape(-1, 4)
    sl = np.asarray(seg_labels, np.float64).ravel()
    if sr.shape[0] != sl.shape[0]:
        raise ValueError("rects/labels length mismatch")
    return dispatch("fitting_loss", cs, sr, sl, backend=backend,
                    size=fitting_loss_size(cs, sr), **kw)


def fitting_loss_batched(cs, seg_rects, seg_labels, *,
                         backend: str | None = None, **kw) -> np.ndarray:
    """(T,) Algorithm-5 losses: seg_rects (T, K, 4), seg_labels (T, K)."""
    sr = np.asarray(seg_rects)
    sl = np.asarray(seg_labels, np.float64)
    if sr.ndim != 3 or sr.shape[-1] != 4:
        raise ValueError("batch rects must have shape (T, K, 4)")
    if sl.shape != sr.shape[:2]:
        raise ValueError("batch labels must have shape (T, K)")
    return dispatch("fitting_loss_batched", cs, sr, sl, backend=backend,
                    size=fitting_loss_batched_size(cs, sr), **kw)


def hist_split(codes, w, wy, wy2, n_bins: int, *,
               backend: str | None = None, **kw) -> np.ndarray:
    """(F, n_bins, 3) per-(feature, bin) sums of (w, wy, wy2);
    codes (P, F) integer bin ids."""
    codes = np.asarray(codes)
    if codes.ndim != 2:
        raise ValueError(f"codes must be (P, F), got shape {codes.shape}")
    return dispatch("hist_split", codes, w, wy, wy2, int(n_bins),
                    backend=backend, size=codes.size, **kw)


def selected_backend(op: str, size: int | None = None,
                     backend: str | None = None) -> str:
    """The backend name a dispatch of ``op`` at ``size`` would use — for
    surfacing in responses, ``/v1/stats`` and bench output."""
    return backend or select_backend(op, size)
