"""The shared sigma of a band-parallel build, and the losses it serves.

A band-parallel build takes the single build's sigma rule
(``practical_sigma``): the sum of its bands' O(N) bounds, a certified lower
bound on opt_k of the whole signal, floored at greedy / 6 where the bounds
degenerate.  Before, it took greedy / 4, which lies several times above
opt_k where the greedy k-tree misses the structure.  Served losses consume
each block's mass in ascending label order.  On near-fit trees, whose
small losses show a coarse partition or a mispaired block, the coreset
stays within eps of a plain f64 evaluation."""
import numpy as np
import pytest

from repro.client import CoresetClient
from repro.core import fitting_loss, random_tree_segmentation, sharded_coreset
from repro.core.sharded import band_bounds, shared_sigma, shared_tolerance
from repro.data import piecewise_signal
from repro.service import (CoresetEngine, ServiceMetrics, make_server,
                           serve_forever_in_thread)

N, M, K, EPS, BANDS = 512, 320, 16, 0.2, 3

# (noise, seed) of 16-leaf signals: on ``bound`` the bands' bounds lie
# above greedy / 6 and set the sigma; on ``missed`` the greedy tree misses
# the structure, the bounds lie under greedy / 6 and the floor stands.  On
# ``missed`` greedy / 4 with the tree's own leaf order read a near-fit
# tree 0.36 off
_SIGNALS = {"bound": (0.3, 16), "missed": (0.15, 26)}


def _signal(noise, seed):
    """The signal ``piecewise_signal`` makes and its generating tree (the
    same draws: tree first, then noise, from one generator)."""
    seg = random_tree_segmentation(N, M, K, np.random.default_rng(seed))
    return piecewise_signal(N, M, K, noise=noise, seed=seed), seg


def _f64_loss(y, rects, labels) -> float:
    """Sum of squared residuals, leaf by leaf, in f64."""
    total = 0.0
    for (r0, r1, c0, c1), lam in zip(rects, labels):
        d = y[r0:r1, c0:c1] - lam
        total += float(np.einsum("ij,ij->", d, d))
    return total


def _near_fits(seg, count=8, jitter=0.3, seed=0):
    """The generating tree with its levels (2 x its labels) moved by
    ``jitter`` x N(0, 1): small losses, which show a coarse partition."""
    rng = np.random.default_rng(seed)
    return [2.0 * seg.labels + jitter * rng.normal(size=K)
            for _ in range(count)]


@pytest.mark.parametrize("name", sorted(_SIGNALS))
def test_shared_build_keeps_eps_against_the_f64_loss(name):
    y, seg = _signal(*_SIGNALS[name])
    cs = sharded_coreset(y, K, EPS, num_bands=BANDS)
    for labels in _near_fits(seg):
        truth = _f64_loss(y, seg.rects, labels)
        got = fitting_loss(cs, seg.rects, labels)
        assert abs(got - truth) <= EPS * truth
    sh = shared_sigma(y, K, band_bounds(N, BANDS))
    assert sh.sigma == max(sh.bound, sh.greedy / 6) < sh.greedy / 4
    assert cs.tolerance == shared_tolerance(sh.sigma, K, EPS)
    assert cs.certified is sh.certified is (name == "bound")


def test_label_order_keeps_the_greedy_tolerance_within_eps():
    """Where greedy / 4 makes the blocks coarse, the consumption order
    decides the near fits' error: in the tree's own leaf order they read
    up to 0.36 off, in label order within eps."""
    y, seg = _signal(*_SIGNALS["missed"])
    sh = shared_sigma(y, K, band_bounds(N, BANDS))
    cs = sharded_coreset(y, K, EPS, num_bands=BANDS,
                         tolerance_override=shared_tolerance(
                             sh.greedy / 4, K, EPS))
    for labels in _near_fits(seg):
        truth = _f64_loss(y, seg.rects, labels)
        assert abs(fitting_loss(cs, seg.rects, labels) - truth) <= EPS * truth


def test_bound_sets_sigma_on_heavy_noise():
    """On heavy noise the bands' bounds lie above greedy / 4: the sum sets
    the sigma, certified, and a coarser partition than greedy / 4 gave."""
    y = piecewise_signal(N, M, 6, noise=1.0, seed=4)
    sh = shared_sigma(y, 6, band_bounds(N, BANDS))
    assert sh.sigma == sh.bound > sh.greedy / 4
    cs = sharded_coreset(y, 6, 0.3, num_bands=BANDS)
    assert cs.certified is True
    assert cs.tolerance == shared_tolerance(sh.bound, 6, 0.3)
    assert cs.fingerprint() == "f78e48eefcf7d60c770badb3d8c475ab"


def test_paper_fidelity_bands_keep_their_bicriteria():
    """The paper fidelity shares the same sigma, but its bands run their
    own full bicriteria: their ``max_slices`` reads it."""
    y, _ = _signal(*_SIGNALS["missed"])
    y = y[:96, :64]
    cs = sharded_coreset(y, 4, 0.3, num_bands=2, fidelity="paper")
    sh = shared_sigma(y, 4, band_bounds(96, 2))
    assert cs.tolerance == shared_tolerance(sh.sigma, 4, 0.3)
    assert cs.bicriteria.n_iterations > 0


def test_engine_serves_eps_and_counts_uncertified_builds():
    """Through the served path at k=16: each build states eps as its
    ``eps_eff`` and answers a near-fit tree within it; the engine counts
    the build whose sigma rests on the greedy floor in ``/v1/stats`` and
    in the Prometheus text, and not the certified one."""
    eng = CoresetEngine(num_bands=BANDS, workers=2, metrics=ServiceMetrics())
    srv = make_server(eng)
    serve_forever_in_thread(srv)
    try:
        cl = CoresetClient(f"http://127.0.0.1:{srv.server_address[1]}",
                           encoding="binary")
        counters = lambda: cl.stats()["metrics"]["counters"]  # noqa: E731
        assert counters()["uncertified_builds_total"] == 0
        for name in ("bound", "missed"):
            y, seg = _signal(*_SIGNALS[name])
            cl.register_signal(name, values=y)
            b = cl.build(name, K, EPS)
            assert b.eps_eff == EPS and b.certified is (name == "bound")
            labels = _near_fits(seg, count=1)[0]
            r = cl.query_loss(name, seg.rects, labels, k=K, eps=EPS)
            assert r.eps_eff == EPS
            truth = _f64_loss(y, seg.rects, labels)
            assert abs(r.loss - truth) <= r.eps_eff * truth
            assert counters()["uncertified_builds_total"] == \
                int(name == "missed")
        assert "coreset_uncertified_builds_total 1" in \
            cl.metrics_text().split("\n")
    finally:
        srv.shutdown()
        srv.server_close()
        eng.close()
