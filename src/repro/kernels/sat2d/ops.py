"""Jit'd public wrappers for the sat2d kernel.

The ``*_comp`` wrappers are the compensated (two-float) twins of the
``ref.py`` ``*_comp_ref`` oracles on the Pallas scan — the chip's path for
the precision-pinned integral images: they take and return (hi, lo) f32
pairs, the caller recombining them in f64.

Each plain wrapper exists in two jitted flavours: the plain one, and — on
accelerator platforms only — one with **buffer donation** on the scan
inputs.  The ``repro.ops`` backends ship fresh host arrays to the device on
every call and never touch them again, so the carry/stack buffers can be
donated to XLA and their HBM reused for the outputs (free on CPU, where
donation is unimplemented and would only warn).  Callers that keep their
arrays (tests, the mesh scorer) use the default non-donating path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import sat2d, scan_rows, scan_rows_comp

__all__ = ["sat", "sat_moments", "delta_sat_moments", "sat_stack",
           "sat_moments_comp", "delta_sat_moments_comp", "sat_stack_comp"]

_DEFAULT_TILE = 256


@functools.cache
def _donation_supported() -> bool:
    return jax.default_backend() in ("tpu", "gpu")


@functools.partial(jax.jit, static_argnames=("interpret",))
def sat(x: jnp.ndarray, interpret: bool | None = None) -> jnp.ndarray:
    """Summed-area table of a 2D array."""
    return sat2d(x, interpret=interpret)


def _sat_moments(y, tile, interpret):
    n, m = y.shape
    stk = jnp.stack([jnp.ones_like(y), y, y * y], axis=0)   # (3, n, m)
    r = scan_rows(stk.reshape(3 * n, m), tile, tile,
                  interpret=interpret).reshape(3, n, m)
    # column pass: transpose each channel, fold channels into rows again
    rt = r.transpose(0, 2, 1).reshape(3 * m, n)
    c = scan_rows(rt, tile, tile,
                  interpret=interpret).reshape(3, m, n).transpose(0, 2, 1)
    return c


_sat_moments_jit = functools.partial(jax.jit,
                                     static_argnames=("tile", "interpret"))
_sat_moments_plain = _sat_moments_jit(_sat_moments)
_sat_moments_donate = _sat_moments_jit(_sat_moments, donate_argnums=(0,))


def sat_moments(y: jnp.ndarray, tile: int = _DEFAULT_TILE,
                interpret: bool | None = None,
                donate: bool = False) -> jnp.ndarray:
    """(3, n, m) integral images of (1, y, y^2): the coreset prefix stats.

    The three channels are folded into the row axis so both scan passes run
    as single kernel launches ((3n, m) row scan; (m, 3n) per-channel column
    scan via a channel-blocked layout).  ``tile`` is the Pallas block
    edge; ``donate=True`` releases ``y``'s device buffer to XLA
    (accelerator platforms only — the caller must not reuse it)."""
    fn = (_sat_moments_donate if donate and _donation_supported()
          else _sat_moments_plain)
    return fn(y, tile=tile, interpret=interpret)


def _delta_sat_moments(carry, tail, tile, interpret):
    b, m = tail.shape
    stk = jnp.stack([jnp.ones_like(tail), tail, tail * tail], axis=0)
    inner = scan_rows(stk.reshape(3 * b, m), tile, tile,
                      interpret=interpret).reshape(3, b, m)
    # row-direction scan: fold channels x columns into the scan rows and
    # seed the carry with the stored integral-image row above the patch
    rt = inner.transpose(0, 2, 1).reshape(3 * m, b)
    init = carry.astype(tail.dtype).reshape(3 * m, 1)
    out = scan_rows(rt, tile, tile, interpret=interpret,
                    init=init).reshape(3, m, b)
    return out.transpose(0, 2, 1)


_delta_jit = functools.partial(jax.jit, static_argnames=("tile", "interpret"))
_delta_plain = _delta_jit(_delta_sat_moments)
_delta_donate = _delta_jit(_delta_sat_moments, donate_argnums=(0, 1))


def delta_sat_moments(carry: jnp.ndarray, tail: jnp.ndarray,
                      tile: int = _DEFAULT_TILE,
                      interpret: bool | None = None,
                      donate: bool = False) -> jnp.ndarray:
    """Patched integral-image rows (see ``ref.delta_sat_ref``): within-row
    prefix of the (1, y, y^2) stack of the changed rows, then a row-direction
    scan seeded from ``carry`` — two kernel launches regardless of how many
    rows changed.  ``donate=True`` hands the carry/tail buffers to XLA."""
    fn = (_delta_donate if donate and _donation_supported()
          else _delta_plain)
    return fn(carry, tail, tile=tile, interpret=interpret)


def _sat_stack(stk, tile, interpret):
    *lead, n, m = stk.shape
    flat = 1
    for d in lead:
        flat *= int(d)
    x = stk.reshape(flat * n, m)
    r = scan_rows(x, tile, tile, interpret=interpret).reshape(flat, n, m)
    rt = r.transpose(0, 2, 1).reshape(flat * m, n)
    c = scan_rows(rt, tile, tile, interpret=interpret).reshape(flat, m, n)
    return c.transpose(0, 2, 1).reshape(*lead, n, m)


_stack_jit = functools.partial(jax.jit, static_argnames=("tile", "interpret"))
_stack_plain = _stack_jit(_sat_stack)
_stack_donate = _stack_jit(_sat_stack, donate_argnums=(0,))


def sat_stack(stk: jnp.ndarray, tile: int = _DEFAULT_TILE,
              interpret: bool | None = None,
              donate: bool = False) -> jnp.ndarray:
    """Integral images over the last two axes of a batched stack — the
    Pallas body of the batched ``streaming_compress`` backend: the moment
    rasters of all dirty merge-reduce buckets fold into one (L*3*n, m) row
    scan + one (L*3*m, n) column scan.  ``donate=True`` hands the padded
    raster stack to XLA (it is rebuilt per call by the backend)."""
    fn = (_stack_donate if donate and _donation_supported()
          else _stack_plain)
    return fn(stk, tile=tile, interpret=interpret)


# ------------------------------------------------------- compensated twins
_comp_jit = functools.partial(jax.jit, static_argnames=("tile", "interpret"))


def _scan_2d_comp(hi, lo, tile, interpret, init=None):
    """Compensated inclusive 2-D prefix over the last two axes of a
    (c, n, m) pair stack: columns within each row, then down the rows
    (the canonical order of ``ref.sat_stack_ref``); ``init`` seeds the
    row-direction pass with a (c, m) carry pair."""
    c, n, m = hi.shape
    h, l = scan_rows_comp(hi.reshape(c * n, m), lo.reshape(c * n, m),
                          tile, tile, interpret=interpret)
    h = h.reshape(c, n, m).transpose(0, 2, 1).reshape(c * m, n)
    l = l.reshape(c, n, m).transpose(0, 2, 1).reshape(c * m, n)
    if init is not None:
        init = tuple(a.reshape(c * m, 1) for a in init)
    h, l = scan_rows_comp(h, l, tile, tile, interpret=interpret, init=init)
    return (h.reshape(c, m, n).transpose(0, 2, 1),
            l.reshape(c, m, n).transpose(0, 2, 1))


@_comp_jit
def sat_moments_comp(y_hi, y_lo, tile: int = _DEFAULT_TILE,
                     interpret: bool | None = None):
    """(hi, lo) pairs of the S1 and S2 integral images, (2, n, m) each —
    the Pallas twin of ``ref.sat_moments_comp_ref`` (the count image is
    made on the host)."""
    return _scan_2d_comp(jnp.stack([y_hi, y_hi * y_hi]),
                         jnp.stack([y_lo, 2.0 * y_hi * y_lo]), tile,
                         interpret)


@_comp_jit
def delta_sat_moments_comp(carry_hi, carry_lo, tail_hi, tail_lo,
                           tile: int = _DEFAULT_TILE,
                           interpret: bool | None = None):
    """(hi, lo) pairs of the patched (3, b, m) rows — the Pallas twin of
    ``ref.delta_sat_comp_ref``: the row-direction pass starts from the
    stored carry row's pair."""
    ones = jnp.ones_like(tail_hi)
    return _scan_2d_comp(
        jnp.stack([ones, tail_hi, tail_hi * tail_hi]),
        jnp.stack([jnp.zeros_like(tail_hi), tail_lo, 2.0 * tail_hi * tail_lo]),
        tile, interpret, init=(carry_hi, carry_lo))


@_comp_jit
def sat_stack_comp(stk_hi, stk_lo, tile: int = _DEFAULT_TILE,
                   interpret: bool | None = None):
    """Pallas twin of ``ref.sat_stack_comp_ref`` over (hi, lo) pairs."""
    *lead, n, m = stk_hi.shape
    h, l = _scan_2d_comp(stk_hi.reshape(-1, n, m), stk_lo.reshape(-1, n, m),
                         tile, interpret)
    return h.reshape(*lead, n, m), l.reshape(*lead, n, m)
