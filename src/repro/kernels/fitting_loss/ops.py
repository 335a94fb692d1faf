"""Jit'd public wrapper: evaluate a segmentation (or many) on a coreset.

``coreset_loss`` remains the thin coreset-to-arrays adapter the pallas
backend of ``repro.ops`` registers.  ``coreset_loss_many`` is a deprecated
shim: the per-segmentation Python loop it used to run is gone — it now
delegates to the dispatched batched op (one fused evaluation for all T
candidates).  New code should call ``repro.ops.fitting_loss_batched``.
"""
from __future__ import annotations

import warnings

import jax.numpy as jnp
import numpy as np

from .kernel import fitting_loss_batched_call, fitting_loss_call

__all__ = ["coreset_loss", "coreset_loss_batched", "coreset_loss_many"]

_MANY_DEPRECATION_WARNED = False


def coreset_loss(cs, seg_rects, seg_labels, interpret: bool | None = None,
                 tile_b: int = 1024):
    """Algorithm-5 loss of one segmentation against a SignalCoreset.
    ``tile_b`` is the coreset-block tile edge."""
    return fitting_loss_call(
        jnp.asarray(cs.rects, jnp.float32), jnp.asarray(cs.labels, jnp.float32),
        jnp.asarray(cs.weights, jnp.float32),
        jnp.asarray(seg_rects, jnp.float32), jnp.asarray(seg_labels, jnp.float32),
        tile_b=tile_b, interpret=interpret)


def coreset_loss_batched(cs, seg_rects, seg_labels,
                         interpret: bool | None = None,
                         tile_b: int = 512, tile_t: int = 8):
    """(T,) losses via the batched kernel: seg_rects (T, K, 4),
    seg_labels (T, K) scored in one pallas_call.  ``tile_b``/``tile_t``
    are the block/tree tile edges."""
    return fitting_loss_batched_call(
        jnp.asarray(cs.rects, jnp.float32), jnp.asarray(cs.labels, jnp.float32),
        jnp.asarray(cs.weights, jnp.float32),
        jnp.asarray(seg_rects, jnp.float32), jnp.asarray(seg_labels, jnp.float32),
        tile_b=tile_b, tile_t=tile_t, interpret=interpret)


def coreset_loss_many(cs, seg_rects_batch, seg_labels_batch,
                      interpret: bool | None = None):
    """Deprecated: use ``repro.ops.fitting_loss_batched``.

    Kept so existing callers and examples keep working; delegates to the
    backend dispatcher (or straight to the batched kernel when ``interpret``
    is pinned), so the old per-segmentation loop no longer exists.
    """
    global _MANY_DEPRECATION_WARNED
    if not _MANY_DEPRECATION_WARNED:
        _MANY_DEPRECATION_WARNED = True
        warnings.warn(
            "coreset_loss_many is deprecated; use repro.ops.fitting_loss_batched",
            DeprecationWarning, stacklevel=2)
    rs = [np.asarray(r, np.float64) for r in seg_rects_batch]
    ls = [np.asarray(l, np.float64) for l in seg_labels_batch]
    if len({r.shape for r in rs}) > 1:
        # ragged candidate set (differing leaf counts) — the old loop
        # accepted it, so score per segmentation; uniform K stays fused
        if interpret is not None:
            return jnp.stack([coreset_loss(cs, r, l, interpret=interpret)
                              for r, l in zip(rs, ls)])
        from repro import ops
        return jnp.asarray([ops.fitting_loss(cs, r, l)
                            for r, l in zip(rs, ls)])
    sr, sl = np.stack(rs), np.stack(ls)
    if interpret is not None:
        return coreset_loss_batched(cs, sr, sl, interpret=interpret)
    from repro import ops
    return jnp.asarray(ops.fitting_loss_batched(cs, sr, sl))
