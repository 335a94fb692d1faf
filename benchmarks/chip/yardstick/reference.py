"""The plain f64 reference: the squared-error loss of a tree on a signal.

A tree's loss is the sum over its leaves of sum((y - label)^2) over the
leaf's cells.  ``SignalReference`` evaluates it for many trees at once from
f64 prefix sums of the signal *after subtracting its mean*, so the sums it
differences stay small (|y - mean| of a few units) and the evaluation keeps
about 1e-12 of relative precision on the losses the benchmark compares;
``direct_loss`` is the leaf-by-leaf definition it is checked against in
``tests/bench``.  Nothing here imports the program.
"""
from __future__ import annotations

import numpy as np

__all__ = ["SignalReference", "direct_loss", "rel_errors"]


def direct_loss(y: np.ndarray, rects, labels) -> float:
    """Sum of squared residuals over the f64 signal, leaf by leaf."""
    total = 0.0
    for (r0, r1, c0, c1), lam in zip(np.asarray(rects, np.int64), labels):
        d = np.asarray(y[r0:r1, c0:c1], np.float64) - float(lam)
        total += float(np.einsum("ij,ij->", d, d))
    return total


class SignalReference:
    """Losses of trees on one signal from centred f64 prefix sums."""

    def __init__(self, y: np.ndarray):
        y = np.asarray(y, np.float64)
        self.shift = float(y.mean())
        yc = y - self.shift
        n, m = yc.shape
        self.p1 = np.zeros((n + 1, m + 1))
        self.p2 = np.zeros((n + 1, m + 1))
        np.cumsum(np.cumsum(yc, axis=0), axis=1, out=self.p1[1:, 1:])
        np.cumsum(np.cumsum(yc * yc, axis=0), axis=1, out=self.p2[1:, 1:])

    def _box(self, p, r0, r1, c0, c1):
        return p[r1, c1] - p[r0, c1] - p[r1, c0] + p[r0, c0]

    def losses(self, rects, labels) -> np.ndarray:
        """(T,) losses of trees given as (T, K, 4) rects and (T, K)
        labels; zero-area leaves add nothing."""
        rects = np.asarray(rects, np.int64)
        lab = np.asarray(labels, np.float64) - self.shift
        r0, r1, c0, c1 = (rects[..., i] for i in range(4))
        s0 = ((r1 - r0) * (c1 - c0)).astype(np.float64)
        s1 = self._box(self.p1, r0, r1, c0, c1)
        s2 = self._box(self.p2, r0, r1, c0, c1)
        return np.sum(s2 - 2.0 * lab * s1 + lab * lab * s0, axis=-1)


def rel_errors(served, truth) -> np.ndarray:
    """|served - truth| / truth, elementwise (truth > 0 on every signal the
    benchmark makes: its noise is never zero)."""
    served = np.asarray(served, np.float64)
    truth = np.asarray(truth, np.float64)
    return np.abs(served - truth) / truth
