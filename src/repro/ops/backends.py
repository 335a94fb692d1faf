"""Registered backend implementations for the canonical op surface.

Each factory is resolved lazily on first dispatch (see registry.py), so the
numpy path never imports jax and importing ``repro.ops`` never imports the
kernel packages.  Conventions:

  * inputs are host values (SignalCoreset / numpy arrays / plain ints);
  * outputs are numpy arrays (or Python floats) — the dispatch surface is
    host-level; device-resident pipelines (the mesh-sharded scorer in
    ``core.sharded``) use the kernel/ref modules directly;
  * the ``pallas`` implementations accept ``interpret=`` so tests can pin
    interpret mode explicitly;
  * every implementation accepts ``config=``, a dict of which the
    accelerator backends read one key, ``compensated``: the two-float
    (TwoSum) path.  The integral-image ops (``sat_moments``,
    ``delta_sat``, ``streaming_compress``) default to it, so plain f32
    runs only where a caller passes ``{"compensated": False}``;
    ``hist_split`` defaults to its plain f32 lowering.  Tile sizes are
    the kernel wrappers' defaults.  The numpy oracle ignores ``config``.

The dense jnp math exists exactly once, in ``repro.kernels.*.ref`` — the
xla backends jit those oracles; nothing here re-derives a formula.  The
``compensated`` configs run the two-float (TwoSum) twins of the same refs
and recombine the (hi, lo) pairs in f64 on the host: accelerator-resident
f32 arithmetic whose result matches the f64 oracle to ~1e-10 scaled
relative error.  ``_compensated`` runs them, and under a live dispatch
span times the upload, the device call and the read-back apart.

The compensated ``sat_moments`` reads back the S1 and S2 pairs alone,
16·n·m bytes (``delta_sat`` reads back all three channels, 24·b·m).
``_land_sat`` sums them straight into a zero-bordered (3, n+1, m+1) f64
buffer in ``PrefixStats``' query layout, beside the exact count image
made on the host, and returns its (3, n, m) interior, which
``PrefixStats.from_sat`` wraps without a copy.
"""
from __future__ import annotations

import functools

import numpy as np

from repro import obs

from .registry import register


def _is_compensated(config, default: bool = True) -> bool:
    """The one key of ``config`` an accelerator backend reads."""
    return bool((config or {}).get("compensated", default))


def _recombine(pair) -> np.ndarray:
    """f64 host value of a compensated (hi, lo) f32 device pair."""
    hi, lo = pair
    return np.asarray(hi, np.float64) + np.asarray(lo, np.float64)


def _land_sat(pair) -> np.ndarray:
    """f64 (3, n, m) integral images from the (2, n, m) S1/S2 pairs of a
    compensated ``sat_moments`` call: the interior ``buf[:, 1:, 1:]`` of a
    zero-bordered (3, n+1, m+1) buffer whose channel 0 is the exact count
    image ``i·j``.  Each f32 pair is summed in f64 into its place, with no
    f64 temporaries; the bits are ``_recombine``'s."""
    hi, lo = (np.asarray(a) for a in pair)
    _, n, m = hi.shape
    buf = np.empty((3, n + 1, m + 1))
    np.multiply.outer(np.arange(n + 1.0), np.arange(m + 1.0), out=buf[0])
    buf[1:, 0, :] = 0.0
    buf[1:, 1:, 0] = 0.0
    np.add(hi, lo, out=buf[1:, 1:, 1:], dtype=np.float64)
    return buf[:, 1:, 1:]


def _compensated(call, *host, land=_recombine) -> np.ndarray:
    """``call`` on the (hi, lo) f32 pairs of the f64 host arrays ``host``,
    its output pair brought to f64 on the host by ``land``.

    Inside a live ``ops.dispatch`` span the dispatch gets three children,
    each ended at a sync: ``dispatch.h2d`` (split and upload, ``bytes``
    up), ``dispatch.device`` (the jitted call) and ``dispatch.d2h``
    (read-back and f64 sum, ``bytes`` down).  Outside one it runs as an
    untraced call always has: no sync, the same output."""
    from repro.kernels.sat2d.ref import split_hi_lo
    if not obs.current_span():
        return land(call(*(a for x in host for a in split_hi_lo(x))))
    import jax
    with obs.span("dispatch.h2d") as sp:
        pairs = jax.block_until_ready(
            [a for x in host for a in split_hi_lo(x)])
        sp.set_attr("bytes", sum(int(a.nbytes) for a in pairs))
    with obs.span("dispatch.device"):
        out = jax.block_until_ready(call(*pairs))
    # free the inputs before the read-back, as an untraced call does, so
    # concurrent band dispatches hold no more device memory than untraced
    del pairs
    with obs.span("dispatch.d2h") as sp:
        sp.set_attr("bytes", sum(int(a.nbytes) for a in out))
        return land(out)


# ------------------------------------------------------------- sat_moments
# (3, n, m) inclusive integral images of (1, y, y^2) — PrefixStats' core.


@register("sat_moments", "numpy")
def _sat_moments_numpy():
    def sat_moments(y, config=None):
        # canonical order: columns-within-row first, then down the rows, so
        # row i of the result is exactly row i-1 + rowprefix(stk[i]) — the
        # recurrence the delta_sat patch op continues bitwise from a stored
        # carry row (np.cumsum is a sequential per-element reduction)
        y = np.asarray(y, np.float64)
        stk = np.stack([np.ones_like(y), y, y * y], axis=0)
        return np.cumsum(np.cumsum(stk, axis=2), axis=1)
    return sat_moments


@register("sat_moments", "xla")
def _sat_moments_xla():
    import jax
    import jax.numpy as jnp
    from repro.kernels.sat2d.ref import sat_moments_comp_ref, sat_moments_ref
    f = jax.jit(sat_moments_ref)
    f_comp = jax.jit(sat_moments_comp_ref)

    def sat_moments(y, config=None):
        if _is_compensated(config):
            return _compensated(f_comp, y, land=_land_sat)
        return np.asarray(f(jnp.asarray(y, jnp.float32)))
    return sat_moments


@register("sat_moments", "pallas")
def _sat_moments_pallas():
    import jax.numpy as jnp
    from repro.kernels.sat2d.ops import sat_moments as kernel_sat_moments
    from repro.kernels.sat2d.ops import sat_moments_comp

    def sat_moments(y, interpret=None, config=None):
        if _is_compensated(config):
            return _compensated(functools.partial(
                sat_moments_comp, interpret=interpret), y, land=_land_sat)
        # donate: the device copy made here is never reused on the host
        return np.asarray(kernel_sat_moments(
            jnp.asarray(y, jnp.float32), interpret=interpret, donate=True))
    return sat_moments


# --------------------------------------------------------------- delta_sat
# patched integral-image rows for a replaced/appended row band: carry (3, m)
# is the integral row just above the patch, tail (b, m) the raw rows from
# the first changed row to the (new) end.  Output (3, b, m).


@register("delta_sat", "numpy")
def _delta_sat_numpy():
    def delta_sat(carry, tail, config=None):
        t = np.asarray(tail, np.float64)
        stk = np.stack([np.ones_like(t), t, t * t], axis=0)
        inner = np.cumsum(stk, axis=2)
        # prepend the carry row and let the sequential cumsum continue it:
        # row i is computed as row i-1 + inner[i], the *same* float ops a
        # from-scratch sat_moments build performs for these rows, so chained
        # delta patches stay bitwise equal to a full rebuild
        full = np.concatenate(
            [np.asarray(carry, np.float64)[:, None, :], inner], axis=1)
        return np.cumsum(full, axis=1)[:, 1:, :]
    return delta_sat


@register("delta_sat", "xla")
def _delta_sat_xla():
    import jax
    import jax.numpy as jnp
    from repro.kernels.sat2d.ref import delta_sat_comp_ref, delta_sat_ref
    f = jax.jit(delta_sat_ref)
    f_comp = jax.jit(delta_sat_comp_ref)

    def delta_sat(carry, tail, config=None):
        if _is_compensated(config):
            # the stored carry enters as its own (hi, lo) pair, so chained
            # patches keep full two-float precision across calls
            return _compensated(f_comp, carry, tail)
        return np.asarray(f(jnp.asarray(carry, jnp.float32),
                            jnp.asarray(tail, jnp.float32)))
    return delta_sat


@register("delta_sat", "pallas")
def _delta_sat_pallas():
    import jax.numpy as jnp
    from repro.kernels.sat2d.ops import (delta_sat_moments,
                                        delta_sat_moments_comp)

    def delta_sat(carry, tail, interpret=None, config=None):
        if _is_compensated(config):
            return _compensated(functools.partial(
                delta_sat_moments_comp, interpret=interpret), carry, tail)
        return np.asarray(delta_sat_moments(
            jnp.asarray(carry, jnp.float32), jnp.asarray(tail, jnp.float32),
            interpret=interpret, donate=True))
    return delta_sat


# ------------------------------------------------------------ fitting_loss
# scalar Algorithm-5 loss of one segmentation against a SignalCoreset.


@register("fitting_loss", "numpy")
def _fitting_loss_numpy():
    from repro.core.fitting_loss import fitting_loss

    def fl(cs, seg_rects, seg_labels, config=None):
        return float(fitting_loss(cs, seg_rects, seg_labels))
    return fl


@register("fitting_loss", "xla")
def _fitting_loss_xla():
    import jax
    import jax.numpy as jnp
    from repro.kernels.fitting_loss.ref import fitting_loss_ref
    f = jax.jit(fitting_loss_ref)

    def fl(cs, seg_rects, seg_labels, config=None):
        return float(f(
            jnp.asarray(cs.rects, jnp.float32),
            jnp.asarray(cs.labels, jnp.float32),
            jnp.asarray(cs.weights, jnp.float32),
            jnp.asarray(seg_rects, jnp.float32),
            jnp.asarray(seg_labels, jnp.float32)))
    return fl


@register("fitting_loss", "pallas")
def _fitting_loss_pallas():
    from repro.kernels.fitting_loss.ops import coreset_loss

    def fl(cs, seg_rects, seg_labels, interpret=None, config=None):
        return float(coreset_loss(cs, seg_rects, seg_labels,
                                  interpret=interpret))
    return fl


# ---------------------------------------------------- fitting_loss_batched
# (T,) losses for T candidate segmentations against one coreset.


@register("fitting_loss_batched", "numpy")
def _fitting_loss_batched_numpy():
    from repro.core.fitting_loss import fitting_loss

    def fb(cs, seg_rects, seg_labels, config=None):
        return np.array([fitting_loss(cs, r, l)
                         for r, l in zip(seg_rects, seg_labels)], np.float64)
    return fb


@register("fitting_loss_batched", "xla")
def _fitting_loss_batched_xla():
    import jax
    import jax.numpy as jnp
    from repro.kernels.fitting_loss.ref import fitting_loss_batched_ref
    f = jax.jit(fitting_loss_batched_ref)

    def fb(cs, seg_rects, seg_labels, config=None):
        return np.asarray(f(
            jnp.asarray(cs.rects, jnp.float32),
            jnp.asarray(cs.labels, jnp.float32),
            jnp.asarray(cs.weights, jnp.float32),
            jnp.asarray(seg_rects, jnp.float32),
            jnp.asarray(seg_labels, jnp.float32)), np.float64)
    return fb


@register("fitting_loss_batched", "pallas")
def _fitting_loss_batched_pallas():
    from repro.kernels.fitting_loss.ops import coreset_loss_batched

    def fb(cs, seg_rects, seg_labels, interpret=None, config=None):
        return np.asarray(coreset_loss_batched(
            cs, seg_rects, seg_labels, interpret=interpret), np.float64)
    return fb


# -------------------------------------------------------------- hist_split
# per-(feature, bin) sums of (w, wy, wy2) — the CART split-search hot spot.


@register("hist_split", "numpy")
def _hist_split_numpy():
    def hist(codes, w, wy, wy2, n_bins, config=None):
        codes = np.asarray(codes)
        out = np.empty((codes.shape[1], n_bins, 3), np.float64)
        for f in range(codes.shape[1]):
            c = codes[:, f]
            out[f, :, 0] = np.bincount(c, weights=w, minlength=n_bins)
            out[f, :, 1] = np.bincount(c, weights=wy, minlength=n_bins)
            out[f, :, 2] = np.bincount(c, weights=wy2, minlength=n_bins)
        return out
    return hist


@register("hist_split", "xla")
def _hist_split_xla():
    import jax
    import jax.numpy as jnp
    from repro.kernels.sat2d.ref import split_hi_lo

    # compensated variant: P-axis chunk combined in f64.  Short chunks keep
    # the *within*-chunk f32 accumulation (which the hi/lo split does not
    # compensate — only the input cast error) to ~32 adds per bin, an order
    # of magnitude inside the 1e-6 parity bound (tests/test_ops.py).
    _CHUNK = 8192

    # segment-sum per feature: O(P*F) work and memory, unlike the one-hot
    # einsum oracle in kernels/histsplit/ref.py whose (P, F, n_bins) one-hot
    # would blow up host memory at training sizes
    @functools.partial(jax.jit, static_argnames=("n_bins",))
    def _hist_vmap(codes, vals, n_bins):
        def one(c):
            return jax.ops.segment_sum(vals, c, num_segments=n_bins)
        return jax.vmap(one, in_axes=1)(codes)          # (F, n_bins, S)

    # compensated: per-chunk f32 segment sums of the (hi, lo) channel pairs,
    # combined across chunks (and hi+lo) in f64 on the host
    @functools.partial(jax.jit, static_argnames=("n_bins",))
    def _hist_chunked(codes, vals, n_bins):
        def one_chunk(c, v):
            def one(cf):
                return jax.ops.segment_sum(v, cf, num_segments=n_bins)
            return jax.vmap(one, in_axes=1)(c)
        return jax.vmap(one_chunk)(codes, vals)         # (C, F, n_bins, 6)

    def hist(codes, w, wy, wy2, n_bins, config=None):
        codes = np.asarray(codes)
        if _is_compensated(config, default=False):
            pairs = [split_hi_lo(a) for a in (w, wy, wy2)]
            vals = jnp.stack([p[0] for p in pairs]
                             + [p[1] for p in pairs], axis=1)   # (P, 6)
            P = codes.shape[0]
            pad = (-P) % _CHUNK
            cj = jnp.asarray(codes, jnp.int32)
            if pad:
                cj = jnp.pad(cj, ((0, pad), (0, 0)))    # bin 0, zero weights
                vals = jnp.pad(vals, ((0, pad), (0, 0)))
            C = cj.shape[0] // _CHUNK
            out = np.asarray(_hist_chunked(
                cj.reshape(C, _CHUNK, -1), vals.reshape(C, _CHUNK, 6),
                n_bins), np.float64)
            return out[..., :3].sum(axis=0) + out[..., 3:].sum(axis=0)
        vals = jnp.stack([jnp.asarray(w, jnp.float32),
                          jnp.asarray(wy, jnp.float32),
                          jnp.asarray(wy2, jnp.float32)], axis=1)
        return np.asarray(_hist_vmap(jnp.asarray(codes, jnp.int32), vals,
                                     n_bins), np.float64)
    return hist


@register("hist_split", "pallas")
def _hist_split_pallas():
    from repro.kernels.histsplit.ops import histograms

    def hist(codes, w, wy, wy2, n_bins, interpret=None, config=None):
        variant = ("partials" if _is_compensated(config, default=False)
                   else "fused")
        return np.asarray(histograms(codes, w, wy, wy2, n_bins,
                                     variant=variant, interpret=interpret),
                          np.float64)
    return hist


# ------------------------------------------------------- streaming_compress
# the merge-reduce "reduce" step as one dispatch: recompress a LIST of
# composed coresets (the dirty buckets of a level) into coresets-of-
# coresets.  The backend-differentiated stage is the integral images of the
# per-bucket moment rasters; rasterization and the partition/Caratheodory
# finish are shared host code in core.streaming.


def _stack_rasters(preps, dtype=np.float32):
    """Pad the per-bucket (3, n, m) moment rasters to one (L, 3, nmax, mmax)
    stack so the accelerator backends integrate every bucket in one call."""
    nmax = max(p.rasters[0].shape[0] for p in preps)
    mmax = max(p.rasters[0].shape[1] for p in preps)
    stk = np.zeros((len(preps), 3, nmax, mmax), dtype)
    for i, p in enumerate(preps):
        n, m = p.rasters[0].shape
        for c in range(3):
            stk[i, c, :n, :m] = p.rasters[c]
    return stk


def _finish_from_sats(coresets, preps, sats, k, eps):
    from repro.core.stats import PrefixStats
    from repro.core.streaming import _recompress_finish
    out = []
    for cs, p, sat in zip(coresets, preps, sats):
        n, m = p.rasters[0].shape
        ps = PrefixStats.from_sat(np.asarray(sat[:, :n, :m], np.float64))
        out.append(_recompress_finish(cs, p, ps, k, eps))
    return out


@register("streaming_compress", "numpy")
def _streaming_compress_numpy():
    def sc(coresets, k=None, eps=None, config=None):
        from repro.core.stats import PrefixStats
        from repro.core.streaming import _recompress_finish, _recompress_prep
        out = []
        for cs in coresets:
            p = _recompress_prep(cs)
            ps = PrefixStats.build_moments(*p.rasters)
            out.append(_recompress_finish(cs, p, ps, k, eps))
        return out
    return sc


@register("streaming_compress", "xla")
def _streaming_compress_xla():
    import jax
    import jax.numpy as jnp
    from repro.kernels.sat2d.ref import sat_stack_comp_ref, sat_stack_ref
    f = jax.jit(sat_stack_ref)
    f_comp = jax.jit(sat_stack_comp_ref)

    def sc(coresets, k=None, eps=None, config=None):
        from repro.core.streaming import _recompress_prep
        preps = [_recompress_prep(cs) for cs in coresets]
        if _is_compensated(config):
            sats = _compensated(f_comp, _stack_rasters(preps, np.float64))
        else:
            sats = np.asarray(f(jnp.asarray(_stack_rasters(preps))))
        return _finish_from_sats(coresets, preps, sats, k, eps)
    return sc


@register("streaming_compress", "pallas")
def _streaming_compress_pallas():
    import jax.numpy as jnp
    from repro.kernels.sat2d.ops import sat_stack, sat_stack_comp

    def sc(coresets, k=None, eps=None, interpret=None, config=None):
        from repro.core.streaming import _recompress_prep
        preps = [_recompress_prep(cs) for cs in coresets]
        if _is_compensated(config):
            sats = _compensated(functools.partial(
                sat_stack_comp, interpret=interpret),
                _stack_rasters(preps, np.float64))
        else:
            sats = np.asarray(sat_stack(jnp.asarray(_stack_rasters(preps)),
                                        interpret=interpret, donate=True))
        return _finish_from_sats(coresets, preps, sats, k, eps)
    return sc
