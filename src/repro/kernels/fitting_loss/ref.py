"""Pure-jnp oracle for the Algorithm-5 smoothed-assignment loss.

``fitting_loss_ref`` is also the single source of the dense math: the
``repro.ops`` xla backend jits it, and ``core.sharded`` shards the vmapped
``fitting_loss_batched_ref`` over the device mesh — there is no second
hand-written dense implementation anywhere.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["fitting_loss_ref", "fitting_loss_batched_ref", "by_label"]


def by_label(seg_rects, seg_labels):
    """Each tree's leaves in ascending label order, the order in which
    they consume a block's mass (``core.fitting_loss``); leading axes are
    trees.  Leaves of equal label may take either order: their joint
    consumption, hence the loss, is the same."""
    order = jnp.argsort(seg_labels, axis=-1, stable=True)
    return (jnp.take_along_axis(seg_rects, order[..., None], axis=-2),
            jnp.take_along_axis(seg_labels, order, axis=-1))


def fitting_loss_ref(rects, labels4, weights4, seg_rects, seg_labels):
    """Dense Algorithm 5 over all (block, leaf, point) triples.

    rects (B,4) f32 half-open block corners; labels4/weights4 (B,4);
    seg_rects (K,4); seg_labels (K,).  Returns the scalar loss.

    A leaf that covers a whole block consumes the block's whole mass, which
    is the exact moment formula of ``core.fitting_loss``'s first phase: a
    block's mass equals its area in a one-shot coreset but not after a
    merge-reduce recompression, where smoothing by area alone would drop
    (or leave unassigned) part of the mass.
    """
    seg_rects, seg_labels = by_label(seg_rects, seg_labels)
    z_r = jnp.clip(jnp.minimum(rects[:, None, 1], seg_rects[None, :, 1])
                   - jnp.maximum(rects[:, None, 0], seg_rects[None, :, 0]), 0, None)
    z_c = jnp.clip(jnp.minimum(rects[:, None, 3], seg_rects[None, :, 3])
                   - jnp.maximum(rects[:, None, 2], seg_rects[None, :, 2]), 0, None)
    z = (z_r * z_c).astype(jnp.float32)              # (B, K)
    U = jnp.cumsum(weights4, axis=1)                  # (B, 4)
    Up = U - weights4
    area = ((rects[:, 1] - rects[:, 0])
            * (rects[:, 3] - rects[:, 2])).astype(jnp.float32)
    z = jnp.where(z == area[:, None], U[:, 3:], z)    # covering leaf: all mass
    Z = jnp.cumsum(z, axis=1)
    Zp = Z - z
    lo = jnp.maximum(Zp[:, :, None], Up[:, None, :])
    hi = jnp.minimum(Z[:, :, None], U[:, None, :])
    consumed = jnp.clip(hi - lo, 0.0, None)           # (B, K, 4)
    diff = seg_labels[None, :, None] - labels4[:, None, :]
    return (consumed * diff * diff).sum()


def fitting_loss_batched_ref(rects, labels4, weights4, seg_rects, seg_labels):
    """(T,) dense losses for T segmentations: seg_rects (T, K, 4),
    seg_labels (T, K).  vmap of :func:`fitting_loss_ref` over candidates —
    every device in the sharded path scores its block shard against all T
    trees at once."""
    return jax.vmap(
        lambda r, l: fitting_loss_ref(rects, labels4, weights4, r, l)
    )(seg_rects, seg_labels)
