"""Merge-reduce: compose / recompress / streaming / sharded construction."""
import numpy as np
import pytest

from repro.core import (StreamingBuilder, compose, fitting_loss,
                        random_tree_segmentation, recompress, sharded_coreset,
                        signal_coreset, true_loss)
from repro.data import piecewise_signal, smooth_field


def _err(cs, y, seg):
    tl = true_loss(y, seg.rects, seg.labels)
    return abs(fitting_loss(cs, seg.rects, seg.labels) - tl) / max(tl, 1e-12)


def test_compose_equals_union_semantics():
    rng = np.random.default_rng(0)
    y = piecewise_signal(80, 60, 6, noise=0.15, seed=0)
    cs = sharded_coreset(y, 6, 0.3, num_bands=4)
    assert np.isclose(cs.total_mass(), y.size)
    for _ in range(6):
        q = random_tree_segmentation(80, 60, 6, rng)
        assert _err(cs, y, q) <= 0.3


def test_recompress_shrinks_and_keeps_guarantee():
    rng = np.random.default_rng(1)
    y = piecewise_signal(90, 70, 8, noise=0.2, seed=1)
    cs = sharded_coreset(y, 8, 0.3, num_bands=6, share_tolerance=False)
    rc = recompress(cs)
    assert rc.size <= cs.size
    assert np.isclose(rc.total_mass(), y.size)
    q = random_tree_segmentation(90, 70, 8, rng)
    assert _err(rc, y, q) <= 0.6   # two eps layers of merge-reduce


def test_streaming_builder_bounded_and_accurate():
    rng = np.random.default_rng(2)
    y = piecewise_signal(120, 50, 6, noise=0.15, seed=2)
    sb = StreamingBuilder(m=50, k=6, eps=0.3)
    for i in range(0, 120, 20):
        sb.insert_band(y[i:i + 20])
    cs = sb.result()
    assert np.isclose(cs.total_mass(), y.size)
    q = random_tree_segmentation(120, 50, 6, rng)
    assert _err(cs, y, q) <= 0.6


def test_compose_is_order_invariant_under_row_offsets():
    """compose() is exact concatenation: feeding the per-band coresets in a
    shuffled order (with matching offsets) must give identical losses and
    identical (sorted) block geometry."""
    from repro.core import compose
    y = piecewise_signal(64, 40, 5, noise=0.15, seed=4)
    bounds = [(0, 16), (16, 40), (40, 64)]
    parts = [signal_coreset(y[a:b], 5, 0.3) for a, b in bounds]
    offs = [a for a, _ in bounds]
    cs_sorted = compose(parts, offs, n_total=64)
    order = [2, 0, 1]
    cs_shuf = compose([parts[i] for i in order], [offs[i] for i in order],
                      n_total=64)
    key = lambda cs: np.lexsort(cs.rects.T[::-1])  # noqa: E731
    np.testing.assert_array_equal(cs_sorted.rects[key(cs_sorted)],
                                  cs_shuf.rects[key(cs_shuf)])
    rng = np.random.default_rng(4)
    q = random_tree_segmentation(64, 40, 5, rng)
    assert np.isclose(fitting_loss(cs_sorted, q.rects, q.labels),
                      fitting_loss(cs_shuf, q.rects, q.labels))
    # offsets must keep every block inside the stacked domain
    for cs in (cs_sorted, cs_shuf):
        assert cs.rects[:, 0].min() == 0 and cs.rects[:, 1].max() == 64


def test_streaming_cascade_offsets_tile_the_domain():
    """Uneven bands force multi-level bucket cascades; without recompression
    the merged rects must tile [0,n) x [0,m) exactly (area and mass checks
    catch any mis-anchored row offset) and moments must match the signal."""
    n, m = 110, 30
    y = piecewise_signal(n, m, 5, noise=0.1, seed=5)
    sb = StreamingBuilder(m=m, k=5, eps=0.3, recompress_levels=False)
    sizes = [10, 30, 15, 25, 20, 10]   # 6 bands -> buckets at levels 1 and 2
    r = 0
    for s in sizes:
        sb.insert_band(y[r:r + s])
        r += s
    assert sb.rows_seen == n and sb.max_level >= 1
    cs = sb.result()
    areas = ((cs.rects[:, 1] - cs.rects[:, 0])
             * (cs.rects[:, 3] - cs.rects[:, 2]))
    assert int(areas.sum()) == n * m               # tiling: no gap/overlap
    assert np.isclose(cs.total_mass(), n * m)
    assert np.isclose(cs.moments[:, 0].sum(), n * m)
    assert np.isclose(cs.moments[:, 1].sum(), y.sum())
    assert np.isclose(cs.moments[:, 2].sum(), (y * y).sum())
    # per-row-band mass: every original band contributes exactly rows*m
    for (a, b) in [(0, 10), (40, 55), (90, 110)]:
        covered = ((np.minimum(cs.rects[:, 1], b) - np.maximum(cs.rects[:, 0], a)).clip(0)
                   * (cs.rects[:, 3] - cs.rects[:, 2]))
        assert int(covered.sum()) == (b - a) * m


def test_recompress_after_out_of_order_compose_keeps_moments():
    """recompress over a shuffled-compose union: the weighted re-raster must
    preserve global mass/M1 and stay within the two-layer eps bound."""
    from repro.core import compose
    rng = np.random.default_rng(6)
    y = piecewise_signal(96, 32, 6, noise=0.15, seed=6)
    bounds = [(48, 96), (0, 48)]                    # deliberately unsorted
    parts = [signal_coreset(y[a:b], 6, 0.3) for a, b in bounds]
    cs = compose(parts, [a for a, _ in bounds], n_total=96)
    rc = recompress(cs)
    assert np.isclose(rc.total_mass(), y.size)
    assert np.isclose(rc.moments[:, 1].sum(), cs.moments[:, 1].sum())
    q = random_tree_segmentation(96, 32, 6, rng)
    assert _err(rc, y, q) <= 0.6


def test_shared_tolerance_matches_single_build_size():
    y = piecewise_signal(100, 80, 10, noise=0.2, seed=3)
    full = signal_coreset(y, 10, 0.3)
    sh = sharded_coreset(y, 10, 0.3, num_bands=4)   # share_tolerance=True
    assert sh.size <= 3 * full.size


def _band_bound_sum(y, k, num_bands):
    from repro.core import bicriteria
    from repro.core.sharded import band_bounds
    return sum(bicriteria(y[a:b], k, max_practical_iters=0).sigma
               for a, b in band_bounds(y.shape[0], num_bands))


@pytest.mark.parametrize("kind,expect", [("noisy", True), ("smooth", False)])
def test_shared_build_certifies_shared_sigma_against_band_bounds(kind, expect):
    """A shared-tolerance build is certified iff the shared sigma is no
    larger than the sum of the bands' O(N) bounds: on heavy noise the
    row/column bounds reach greedy / 6 and set the sigma, on a smooth
    field they do not, and the greedy floor stands."""
    from repro.core.coreset import greedy_loss
    from repro.core.sharded import band_bounds, shared_sigma
    from repro.core.stats import PrefixStats
    if kind == "noisy":
        y = piecewise_signal(512, 320, 6, noise=1.0, seed=4)
    else:
        y = smooth_field(512, 320, seed=6)
    greedy = greedy_loss(PrefixStats.build(y), 6)
    bound = _band_bound_sum(y, 6, 4)
    assert (greedy / 6 <= bound) is expect
    sh = shared_sigma(y, 6, band_bounds(512, 4))
    assert (sh.greedy, sh.bound) == (greedy, bound)
    assert sh.sigma == max(greedy / 6, bound)
    cs = sharded_coreset(y, 6, 0.3, num_bands=4)
    assert cs.certified is sh.certified is expect


def test_certify_shared_rule_is_sigma_against_the_band_sum():
    from repro.core.sharded import band_bounds, certify_shared
    y = piecewise_signal(128, 96, 6, noise=0.2, seed=2)
    bands = band_bounds(128, 4)
    parts = [signal_coreset(y[a:b], 6, 0.3, tolerance_override=0.05)
             for a, b in bands]
    bound = sum(p.sigma for p in parts)
    offsets = [a for a, _ in bands]
    at = certify_shared(parts, offsets, 128, bound)
    above = certify_shared(parts, offsets, 128, np.nextafter(bound, np.inf))
    assert at.certified is True
    assert above.certified is False
    # the rule decides only the flag: the composed blocks are the union
    assert at.fingerprint() == above.fingerprint() == \
        compose(parts, offsets, n_total=128).fingerprint()


def test_single_build_under_override_certifies_the_handed_tolerance():
    """A bare build handed a tolerance runs only the O(N) bounds, takes
    its sigma from them, and is certified iff the tolerance is within
    that sigma's budget eps^2 * sigma / k; its blocks are those of a
    build that estimated sigma the full way."""
    from repro.core import bicriteria
    y = piecewise_signal(128, 96, 6, noise=0.2, seed=2)
    k, eps = 6, 0.3
    sigma = bicriteria(y, k, max_practical_iters=0).sigma
    budget = eps * eps * sigma / k
    within = signal_coreset(y, k, eps, tolerance_override=budget)
    beyond = signal_coreset(y, k, eps,
                            tolerance_override=np.nextafter(budget, np.inf))
    assert within.sigma == beyond.sigma == sigma
    assert within.bicriteria.n_iterations == 0
    assert within.certified is True
    assert beyond.certified is False
    full = signal_coreset(y, k, eps)
    hinted = signal_coreset(y, k, eps, tolerance_override=budget,
                            _sigma_hint=(full.sigma, full.certified,
                                         full.bicriteria))
    assert hinted.fingerprint() == within.fingerprint()
    # the paper fidelity keeps its own sigma: max_slices depends on it
    paper = signal_coreset(y, k, eps, fidelity="paper",
                           tolerance_override=budget)
    assert paper.bicriteria.n_iterations > 0


@pytest.mark.parametrize("shape,k,dc", [((120, 96), 6, 100.0),
                                        ((64, 300), 16, 0.0)])
def test_dense_bounds_equal_the_unit_moment_bounds_bitwise(shape, k, dc):
    """A dense signal's row/column bounds count an interval by its width;
    that is bitwise the prefix sums of a unit-weight moment raster."""
    from repro.core import bicriteria
    y = np.random.default_rng(5).normal(size=shape) + dc
    dense = bicriteria(y, k, max_practical_iters=0)
    mom = bicriteria(None, k, moments=(np.ones_like(y), y, y * y),
                     max_practical_iters=0)
    assert (dense.sigma_rows, dense.sigma_cols) == \
        (mom.sigma_rows, mom.sigma_cols)
    assert dense.sigma_rows > 0 and dense.sigma_cols > 0
