"""Algorithm 1 (SLICEPARTITION) — greedy maximal-variance slicing of a band.

Given a horizontal band of rows [r0:r1) of the signal and a tolerance
``sigma``, partition it into vertical slices, each the *maximal* contiguous
column window whose opt1 is <= sigma.  When even a single column exceeds the
tolerance, that column is recursively partitioned horizontally (the paper's
``SLICEPARTITION(B^T, sigma)`` call).

Identical output to the paper's linear greedy scan, but each boundary is
located with a binary search over the monotone opt1 (see
``PrefixStats.max_col_extent``), so a band costs O(#slices * log m) instead
of O((r1-r0) * m).  The searches run on a :class:`~repro.core.stats.BandQuery`:
the column searches read only rows r0 and r1 of the three images, the
column fallback only columns c and c+1, each bound once as ``memoryview``s
and probed in Python floats.  A probe's arithmetic order is part of the
contract (the coreset fingerprints depend on it): see ``BandQuery``.
"""
from __future__ import annotations

from collections.abc import Iterator

from .stats import BandQuery, PrefixStats

__all__ = ["slice_partition", "band_slices", "slices_count_if", "Rect"]

# A rectangle is (r0, r1, c0, c1), half-open on both axes.
Rect = tuple[int, int, int, int]


def slice_partition(ps: PrefixStats, r0: int, r1: int,
                    sigma: float) -> list[Rect]:
    """Partition the band [r0:r1) into maximal slices with opt1 <= sigma
    (Algorithm 1)."""
    return list(band_slices(ps.band(r0, r1), sigma))


def band_slices(band: BandQuery, sigma: float) -> Iterator[Rect]:
    """The slices of Algorithm 1 over a bound band, in column order; a
    consumer that stops early stops the searches there too."""
    r0, r1 = band.r0, band.r1
    m = band.ps.shape[1]
    c0 = 0
    while c0 < m:
        c_end = band.col_extent(c0, sigma)
        if c_end == c0:
            # Single column already exceeds sigma: recurse on its transpose,
            # i.e. partition the column along rows (Algorithm 1 lines 4-6).
            # Single cells have opt1 = 0 <= sigma, so this always ends,
            # with unit cells at worst.
            rr = r0
            while rr < r1:
                r_end = min(max(band.row_extent(c0, rr, sigma), rr + 1), r1)
                yield (rr, r_end, c0, c0 + 1)
                rr = r_end
            c0 += 1
        else:
            yield (r0, r1, c0, c_end)
            c0 = c_end


def slices_count_if(band: BandQuery, sigma: float, stop_above: int) -> int:
    """Number of slices SLICEPARTITION would produce, early-exiting once the
    count exceeds ``stop_above`` (used by Algorithm 2's band-growing loop so
    rejected bands don't pay for a full partition)."""
    cnt = 0
    for _ in band_slices(band, sigma):
        cnt += 1
        if cnt > stop_above:
            break
    return cnt
