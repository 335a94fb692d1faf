"""Algorithms 1-2: slice partition and balanced partition invariants."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (PrefixStats, balanced_partition, signal_coreset,
                        slice_partition)
from repro.data import piecewise_signal


def naive_slice_partition(ps, r0, r1, sigma):
    """The paper's linear greedy scan (reference for the binary search)."""
    m = ps.shape[1]
    out = []
    c0 = 0
    while c0 < m:
        if ps.opt1(r0, r1, c0, c0 + 1) > sigma:
            rr = r0
            while rr < r1:
                re = rr + 1
                while re < r1 and ps.opt1(rr, re + 1, c0, c0 + 1) <= sigma:
                    re += 1
                out.append((rr, re, c0, c0 + 1))
                rr = re
            c0 += 1
        else:
            ce = c0 + 1
            while ce < m and ps.opt1(r0, r1, c0, ce + 1) <= sigma:
                ce += 1
            out.append((r0, r1, c0, ce))
            c0 = ce
    return out


@st.composite
def small_signal(draw):
    n = draw(st.integers(2, 10))
    m = draw(st.integers(2, 14))
    seed = draw(st.integers(0, 10_000))
    rng = np.random.default_rng(seed)
    scale = draw(st.sampled_from([0.1, 1.0, 5.0]))
    return rng.normal(size=(n, m)) * scale


@settings(max_examples=40, deadline=None)
@given(small_signal(), st.sampled_from([0.0, 0.05, 0.5, 5.0, 100.0]))
def test_slice_partition_matches_naive_greedy(y, sigma):
    ps = PrefixStats.build(y)
    n = y.shape[0]
    got = slice_partition(ps, 0, n, sigma)
    ref = naive_slice_partition(ps, 0, n, sigma)
    assert got == ref


@settings(max_examples=40, deadline=None)
@given(small_signal(), st.sampled_from([0.0, 0.1, 1.0, 20.0]),
       st.integers(1, 8))
def test_balanced_partition_tiles_and_respects_tolerance(y, tol, max_slices):
    ps = PrefixStats.build(y)
    part = balanced_partition(ps, tol, max_slices)
    n, m = y.shape
    raster = part.block_id_raster(n, m)        # raises if not a tiling
    assert raster.min() >= 0
    r = part.rects
    opt1s = ps.opt1(r[:, 0], r[:, 1], r[:, 2], r[:, 3])
    assert (opt1s <= tol + 1e-9).all()


def test_balanced_partition_constant_signal_is_one_block():
    y = np.full((20, 30), 3.25)
    ps = PrefixStats.build(y)
    part = balanced_partition(ps, 0.0, 16)
    assert part.num_blocks == 1


# ------------------------------------------- probes on bound image lines
# The partition's searches bind the image lines they read and probe them in
# Python floats.  ``_Reference`` is a copy of the searches as they ran before
# that: every probe goes through ``PrefixStats.opt1_scalar`` and its numpy
# scalars, in the same order.  The coreset fingerprints hang on these rects,
# so the comparison is exact (``np.array_equal``), search counts included.


class _Reference:
    """Algorithms 1-2 probing through ``opt1_scalar``; counts its searches."""

    def __init__(self, ps):
        self.ps = ps
        self.searches = 0

    def max_col_extent(self, r0, r1, c0, sigma):
        self.searches += 1
        opt1, m = self.ps.opt1_scalar, self.ps.shape[1]
        if opt1(r0, r1, c0, c0 + 1) > sigma:
            return c0
        lo, hi = c0 + 1, m
        while lo < hi:
            mid = (lo + hi + 1) // 2
            if opt1(r0, r1, c0, mid) <= sigma:
                lo = mid
            else:
                hi = mid - 1
        return lo

    def max_row_extent(self, c0, c1, r0, sigma):
        self.searches += 1
        opt1, n = self.ps.opt1_scalar, self.ps.shape[0]
        if opt1(r0, r0 + 1, c0, c1) > sigma:
            return r0
        lo, hi = r0 + 1, n
        while lo < hi:
            mid = (lo + hi + 1) // 2
            if opt1(r0, mid, c0, c1) <= sigma:
                lo = mid
            else:
                hi = mid - 1
        return lo

    def slice_partition(self, r0, r1, sigma):
        out, c0, m = [], 0, self.ps.shape[1]
        while c0 < m:
            c_end = self.max_col_extent(r0, r1, c0, sigma)
            if c_end == c0:
                rr = r0
                while rr < r1:
                    r_end = min(max(self.max_row_extent(c0, c0 + 1, rr, sigma),
                                    rr + 1), r1)
                    out.append((rr, r_end, c0, c0 + 1))
                    rr = r_end
                c0 += 1
            else:
                out.append((r0, r1, c0, c_end))
                c0 = c_end
        return out

    def slices_count_if(self, r0, r1, sigma, stop_above):
        cnt, c0, m = 0, 0, self.ps.shape[1]
        while c0 < m:
            c_end = self.max_col_extent(r0, r1, c0, sigma)
            if c_end == c0:
                rr = r0
                while rr < r1:
                    r_end = min(max(self.max_row_extent(c0, c0 + 1, rr, sigma),
                                    rr + 1), r1)
                    cnt += 1
                    if cnt > stop_above:
                        return cnt
                    rr = r_end
                c0 += 1
            else:
                cnt += 1
                c0 = c_end
            if cnt > stop_above:
                return cnt
        return cnt

    def balanced_partition(self, tol, max_slices):
        n = self.ps.shape[0]
        max_slices = max(int(max_slices), 1)
        rects, bounds, r0 = [], [0], 0
        while r0 < n:
            if self.slices_count_if(r0, r0 + 1, tol, max_slices) > max_slices:
                cur, r1 = self.slice_partition(r0, r0 + 1, tol), r0 + 1
            else:
                step, r1 = 1, r0 + 1
                while r1 < n:
                    cand = min(r1 + step, n)
                    if self.slices_count_if(r0, cand, tol, max_slices) <= max_slices:
                        r1, step = cand, step * 2
                    else:
                        break
                lo, hi = r1, min(r1 + step, n)
                while lo < hi:
                    mid = (lo + hi + 1) // 2
                    if self.slices_count_if(r0, mid, tol, max_slices) <= max_slices:
                        lo = mid
                    else:
                        hi = mid - 1
                r1 = lo
                cur = self.slice_partition(r0, r1, tol)
            rects.extend(cur)
            bounds.append(r1)
            r0 = r1
        return np.asarray(rects, np.int64).reshape(-1, 4), np.asarray(bounds)


def _built(y, k, eps):
    """The images of ``y`` and the partition parameters a build of it uses."""
    cs = signal_coreset(y, k, eps)
    return PrefixStats.build(y), cs.tolerance, cs.max_slices


def _piecewise_dc():
    return _built(piecewise_signal(240, 240, 16, seed=7) + 100.0, 16, 0.2)


def _smooth():
    r, c = np.meshgrid(np.linspace(0, 3, 96), np.linspace(0, 5, 128),
                       indexing="ij")
    return _built(np.sin(r) * np.cos(c) + 0.3 * r * c, 8, 0.3)


def _zero_tolerance():
    # every column of a noisy signal exceeds 0: the column fallback cuts
    # each one into unit cells, five rows to a band
    y = np.random.default_rng(3).normal(size=(24, 40))
    return PrefixStats.build(y), 0.0, 200


def _single_row_overflow():
    # one slice per band: each row that needs two overflows on its own
    y = piecewise_signal(64, 80, 6, seed=11)
    return PrefixStats.build(y), 0.05, 1


def _wide():
    return _built(piecewise_signal(8, 4096, 12, seed=5) + 100.0, 12, 0.2)


def _moments():
    # the streaming path's form: per-cell moments of weighted sparse points
    rng = np.random.default_rng(9)
    n, m = 72, 90
    live = rng.random((n, m)) < 0.4
    w0 = np.where(live, rng.uniform(0.5, 3.0, (n, m)), 0.0)
    y = piecewise_signal(n, m, 8, seed=9)
    return PrefixStats.build_moments(w0, w0 * y, w0 * y * y), 0.5, 12


@pytest.mark.parametrize("case", [_piecewise_dc, _smooth, _zero_tolerance,
                                  _single_row_overflow, _wide, _moments],
                         ids=["piecewise_dc", "smooth", "zero_tolerance",
                              "single_row_overflow", "wide_8x4096",
                              "build_moments"])
def test_bound_views_match_numpy_scalar_probes(case):
    ps, tol, max_slices = case()
    ref = _Reference(ps)
    rects, bounds = ref.balanced_partition(tol, max_slices)
    part = balanced_partition(ps, tol, max_slices)
    assert np.array_equal(part.rects, rects)
    assert np.array_equal(part.band_bounds, bounds)
    assert part.searches == ref.searches > 0


@pytest.mark.parametrize("axis", ["col", "row"])
def test_probe_ties_decide_as_numpy_scalar_probes(axis):
    """With sigma at ``opt1_scalar`` of a search's first probe the search
    takes the probe, and one ulp below it stops.  Rounding alone decides
    those ties, so a probe that sums its terms in another order shows here,
    where the rects above it rarely would."""
    y = np.random.default_rng(17).lognormal(0.0, 4.0, size=(24, 32))
    ps = PrefixStats.build(y)
    n, m = ps.shape
    below = lambda v: np.nextafter(v, -np.inf)
    if axis == "col":
        for r0 in range(n):
            for r1 in {r0 + 1, min(r0 + 3, n), n}:
                band = ps.band(r0, r1)
                for c0 in range(m):
                    v = float(ps.opt1_scalar(r0, r1, c0, c0 + 1))
                    assert band.col_extent(c0, v) > c0
                    assert band.col_extent(c0, below(v)) == c0
    else:
        for c0 in range(m):
            for c1 in {c0 + 1, min(c0 + 3, m), m}:
                for r0 in range(n):
                    v = float(ps.opt1_scalar(r0, r0 + 1, c0, c1))
                    assert ps.max_row_extent(c0, c1, r0, v) > r0
                    assert ps.max_row_extent(c0, c1, r0, below(v)) == r0
