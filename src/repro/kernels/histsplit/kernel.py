"""Pallas TPU kernel: CART/GBDT split histograms as one-hot MXU matmuls.

GPU gradient-boosting libraries (LightGBM/XGBoost CUDA) build per-node split
histograms with shared-memory **atomic scatter-adds**.  TPUs have no atomics
and no efficient scatter — the TPU-native reformulation (DESIGN.md §4) is a
dense one-hot contraction on the **MXU systolic array**:

    hist[f] = [w | wy | wy2]^T  @  onehot(codes[:, f])       (S x P)(P x B)

The one-hot tile is materialized in VMEM from an iota comparison (never in
HBM), so HBM traffic is just codes + values + the (F, S, B) output.

Grid: (P/TP,) — **one** grid axis.  Each step loads one (F, TP) codes tile
and one (TP, S) values tile and accumulates all F per-feature histograms in
place (the P axis is sequential on TPU, so in-place accumulation across
steps is sound).  Folding the feature loop into the kernel body instead of
a second grid axis divides the launch/step count by F and loads the values
tile once per P tile instead of once per (feature, P) tile.

The matmul is laid out as (S, TP) @ (TP, B): the B bins ride the 128-wide
lane axis (fully utilized for B >= 128) and the S value channels ride the
sublane axis.  The transposed layout — (B, TP) @ (TP, S) with S = 8 output
lanes — would waste 15/16 of every MXU output tile.

The "partials" variant skips the cross-tile accumulation and emits
per-P-tile partial histograms (P/TP, F, S, B) instead: the host combines
them in f64, turning the f32 scatter-order error of a long P axis into a
handful of f64 adds — the compensated path (``hist_split`` with
``config={"compensated": True}``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common import default_interpret

__all__ = ["histograms_kernel_call"]

_S_PAD = 8  # value lanes (3 used), padded to the f32 sublane quantum
# f32 values through the MXU at full f32 precision: the default pass
# rounds them to bf16 (3 significant digits), which the S2 - S1^2/S0
# split gains downstream cannot absorb.  The one-hot side is exact anyway.
_PRECISION = jax.lax.Precision.HIGHEST


def _hist_kernel(codes_ref, vals_ref, o_ref):
    p = pl.program_id(0)

    @pl.when(p == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    F = codes_ref.shape[0]
    n_bins = o_ref.shape[2]
    vals_t = vals_ref[...].T                                  # (S, TP)
    iota = jax.lax.broadcasted_iota(
        jnp.int32, (codes_ref.shape[1], n_bins), 1)
    for f in range(F):                                        # static unroll
        onehot = (codes_ref[f, :][:, None] == iota).astype(vals_ref.dtype)
        # (S, TP) @ (TP, B): bins on the lane axis, channels on sublanes
        o_ref[f] += jnp.dot(vals_t, onehot, precision=_PRECISION,
                            preferred_element_type=o_ref.dtype)


def _hist_kernel_partials(codes_ref, vals_ref, o_ref):
    # the compensated variant: no cross-tile accumulation — each grid step
    # owns its own output block, the host reduces the P/TP partials in f64
    F = codes_ref.shape[0]
    n_bins = o_ref.shape[3]
    vals_t = vals_ref[...].T
    iota = jax.lax.broadcasted_iota(
        jnp.int32, (codes_ref.shape[1], n_bins), 1)
    for f in range(F):
        onehot = (codes_ref[f, :][:, None] == iota).astype(vals_ref.dtype)
        o_ref[0, f] = jnp.dot(vals_t, onehot, precision=_PRECISION,
                              preferred_element_type=o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n_bins", "tile_p", "variant",
                                             "interpret"))
def histograms_kernel_call(codes_fp: jnp.ndarray, vals: jnp.ndarray,
                           n_bins: int, tile_p: int = 2048,
                           variant: str = "fused",
                           interpret: bool | None = None) -> jnp.ndarray:
    """codes_fp: (F, P) int32; vals: (P, S<=8) f32.

    Returns (F, n_bins, S) for ``variant="fused"``; the "partials" variant
    returns (P/TP, F, n_bins, S) per-tile partials for the host to combine
    in f64 (the compensated path).
    """
    if interpret is None:
        interpret = default_interpret()
    F, P = codes_fp.shape
    S = vals.shape[1]
    tp = min(tile_p, P)
    pad = (-P) % tp
    if pad:
        codes_fp = jnp.pad(codes_fp, ((0, 0), (0, pad)),
                           constant_values=n_bins - 1)
        vals = jnp.pad(vals, ((0, pad), (0, 0)))  # zero weights: no effect
    Pp = codes_fp.shape[1]
    vals_p = jnp.pad(vals, ((0, 0), (0, _S_PAD - S))) if S < _S_PAD else vals
    if variant == "partials":
        out = pl.pallas_call(
            _hist_kernel_partials,
            grid=(Pp // tp,),
            in_specs=[pl.BlockSpec((F, tp), lambda p: (0, p)),
                      pl.BlockSpec((tp, _S_PAD), lambda p: (p, 0))],
            out_specs=pl.BlockSpec((1, F, _S_PAD, n_bins),
                                   lambda p: (p, 0, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((Pp // tp, F, _S_PAD, n_bins),
                                           vals.dtype),
            interpret=interpret,
        )(codes_fp, vals_p)
        return out[:, :, :S, :].transpose(0, 1, 3, 2)  # (C, F, n_bins, S)
    if variant != "fused":
        raise ValueError(f"unknown histsplit variant {variant!r}")
    out = pl.pallas_call(
        _hist_kernel,
        grid=(Pp // tp,),
        in_specs=[pl.BlockSpec((F, tp), lambda p: (0, p)),
                  pl.BlockSpec((tp, _S_PAD), lambda p: (p, 0))],
        out_specs=pl.BlockSpec((F, _S_PAD, n_bins), lambda p: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((F, _S_PAD, n_bins), vals.dtype),
        interpret=interpret,
    )(codes_fp, vals_p)
    return out[:, :S, :].transpose(0, 2, 1)            # (F, n_bins, S)
