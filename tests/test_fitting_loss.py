"""Algorithm 5: the vectorized smoothed assignment vs a literal reference."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (fitting_loss, overlap_counts, random_tree_segmentation,
                        signal_coreset)
from repro.data import piecewise_signal


def literal_smoothed_loss(cs, seg_rects, seg_labels):
    """The paper's while-loop (Algorithm 5 lines 9-25), verbatim, with the
    leaves taken in ascending label order."""
    total = 0.0
    z_all = overlap_counts(cs.rects, np.asarray(seg_rects))
    for b in range(cs.num_blocks):
        u = list(cs.weights[b].astype(float))
        labels = list(cs.labels[b].astype(float))
        i = 0
        for l_idx in np.argsort(seg_labels, kind="stable"):
            z = float(z_all[b, l_idx])
            lam = float(seg_labels[l_idx])
            while z >= 1e-12 and i < 4:
                if u[i] <= z + 1e-12:
                    total += u[i] * (lam - labels[i]) ** 2
                    z -= u[i]
                    u[i] = 0.0
                    i += 1
                else:
                    total += z * (lam - labels[i]) ** 2
                    u[i] -= z
                    z = 0.0
    return total


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 10_000), st.integers(2, 6))
def test_vectorized_matches_literal_while_loop(seed, k):
    rng = np.random.default_rng(seed)
    y = piecewise_signal(24, 30, 4, noise=0.3, seed=seed % 17)
    cs = signal_coreset(y, 4, 0.3)
    q = random_tree_segmentation(24, 30, k, rng)
    fast = fitting_loss(cs, q.rects, q.labels)
    slow = literal_smoothed_loss(cs, q.rects, q.labels)
    assert np.isclose(fast, slow, rtol=1e-8, atol=1e-6)


def test_single_leaf_is_exact_moment_formula():
    y = piecewise_signal(30, 30, 3, noise=0.2, seed=0)
    cs = signal_coreset(y, 3, 0.3)
    lam = 0.7
    rects = np.array([[0, 30, 0, 30]])
    expect = float(((y - lam) ** 2).sum())
    assert np.isclose(fitting_loss(cs, rects, np.array([lam])), expect,
                      rtol=1e-9)


def test_batched_jax_eval_matches_numpy():
    from repro.core import fitting_loss_batched
    rng = np.random.default_rng(1)
    y = piecewise_signal(40, 40, 5, noise=0.2, seed=1)
    cs = signal_coreset(y, 5, 0.3)
    segs = [random_tree_segmentation(40, 40, 5, rng) for _ in range(4)]
    sr = np.stack([s.rects for s in segs])
    sl = np.stack([s.labels for s in segs])
    batched = fitting_loss_batched(cs, sr, sl)
    seq = np.array([fitting_loss(cs, s.rects, s.labels) for s in segs])
    assert np.allclose(batched, seq, rtol=1e-4)


@pytest.mark.parametrize("backend", ["numpy", "xla", "pallas"])
def test_loss_does_not_depend_on_leaf_order(backend):
    """Leaves consume a block's mass in label order, so a tree's loss is
    the same however its leaves are listed, one tree or a batch.  In the
    listed order these trees read up to 6% apart on this signal."""
    from repro import ops
    rng = np.random.default_rng(1)
    gen = random_tree_segmentation(40, 48, 5, np.random.default_rng(1))
    y = piecewise_signal(40, 48, 5, noise=0.2, seed=1)
    cs = signal_coreset(y, 5, 0.3)
    q = random_tree_segmentation(40, 48, 6, rng)
    near = 2.0 * gen.labels + 0.3 * rng.normal(size=5)   # a near fit
    for rects, labels in ((q.rects, q.labels), (gen.rects, near)):
        perms = [rng.permutation(labels.size) for _ in range(4)]
        one = [ops.fitting_loss(cs, rects[p], labels[p], backend=backend)
               for p in perms]
        many = ops.fitting_loss_batched(
            cs, np.stack([rects[p] for p in perms]),
            np.stack([labels[p] for p in perms]), backend=backend)
        base = ops.fitting_loss(cs, rects, labels, backend=backend)
        assert np.allclose(one, base, rtol=1e-5)
        assert np.allclose(many, base, rtol=1e-5)
