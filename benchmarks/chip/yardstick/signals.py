"""Seeded signals and trees, generated on the host with numpy.

``random_tree`` and ``piecewise`` copy the program's
``repro.core.segmentation.random_tree_segmentation`` and
``repro.data.signals.piecewise_signal`` so that the benchmark's inputs do
not change when the program does; ``tests/bench`` checks that both copies
still draw the same numbers as the originals.
"""
from __future__ import annotations

import numpy as np

__all__ = ["seed_rng", "random_tree", "piecewise", "Record", "record",
           "tree_pool"]


def seed_rng(*words: int) -> np.random.Generator:
    """A generator keyed by any whole numbers (a run seed may exceed 32
    bits or be negative): each word enters the seed sequence as its value
    modulo 2**64."""
    return np.random.default_rng(
        np.random.SeedSequence([int(w) % (1 << 64) for w in words]))


def random_tree(n: int, m: int, k: int, rng: np.random.Generator):
    """A random k-leaf guillotine tree over [n] x [m]: (k, 4) int64
    half-open rects (r0, r1, c0, c1) and (k,) standard-normal labels."""
    rects = [(0, n, 0, m)]
    while len(rects) < k:
        areas = np.array([(r1 - r0) * (c1 - c0) for r0, r1, c0, c1 in rects],
                         float)
        splittable = np.array([(r1 - r0 > 1) or (c1 - c0 > 1)
                               for r0, r1, c0, c1 in rects])
        if not splittable.any():
            break
        p = areas * splittable
        i = rng.choice(len(rects), p=p / p.sum())
        r0, r1, c0, c1 = rects.pop(i)
        axes = [a for a, ok in ((0, r1 - r0 > 1), (1, c1 - c0 > 1)) if ok]
        ax = axes[rng.integers(len(axes))]
        if ax == 0:
            s = int(rng.integers(r0 + 1, r1))
            rects += [(r0, s, c0, c1), (s, r1, c0, c1)]
        else:
            s = int(rng.integers(c0 + 1, c1))
            rects += [(r0, r1, c0, s), (r0, r1, s, c1)]
    return np.asarray(rects, np.int64), rng.normal(size=len(rects))


def piecewise(n: int, m: int, k: int, noise: float, scale: float,
              rng: np.random.Generator):
    """A k-leaf tree's levels (``scale`` x its labels) plus iid Gaussian
    noise: (signal, rects, levels).  Draws the tree first, then the noise,
    from the one stream."""
    rects, labels = random_tree(n, m, k, rng)
    levels = scale * labels
    y = np.zeros((n, m))
    for (r0, r1, c0, c1), lam in zip(rects, levels):
        y[r0:r1, c0:c1] = lam
    y += noise * rng.normal(size=(n, m))
    return y, rects, levels


class Record:
    """One signal of a configuration: its values (offset by the DC level)
    and its generating tree with the levels it was drawn at."""

    __slots__ = ("name", "values", "rects", "levels")

    def __init__(self, name, values, rects, levels):
        self.name = name
        self.values = values
        self.rects = rects
        self.levels = levels


def record(signal: dict, name: str, *words: int) -> Record:
    """The seeded record ``name`` of a configuration's ``signal`` block
    (keys n, m, gen_leaves, noise, scale, dc_offset)."""
    rng = seed_rng(*words)
    n, m, k = int(signal["n"]), int(signal["m"]), int(signal["gen_leaves"])
    dc = float(signal["dc_offset"])
    y, rects, levels = piecewise(n, m, k, float(signal["noise"]),
                                 float(signal["scale"]), rng)
    y += dc
    return Record(name, y, rects, levels + dc)


def tree_pool(signal: dict, rec: Record, leaves: int, count: int,
              fit_share: float, jitter: float, rng: np.random.Generator):
    """``count`` candidate trees of ``leaves`` leaves for one record:
    (count, leaves, 4) rects and (count, leaves) labels.  A ``fit_share``
    of them (where the record's generating tree has ``leaves`` leaves) are
    that tree with its levels moved by ``jitter`` x N(0, 1) — near-fits,
    whose small losses show any loss of precision; the rest are random
    trees with levels at the DC level plus ``scale`` x N(0, 1)."""
    n, m = int(signal["n"]), int(signal["m"])
    dc, scale = float(signal["dc_offset"]), float(signal["scale"])
    rects = np.zeros((count, leaves, 4), np.int64)
    labels = np.zeros((count, leaves))
    near = rng.random(count) < fit_share if rec.rects.shape[0] == leaves \
        else np.zeros(count, bool)
    for i in range(count):
        if near[i]:
            rects[i] = rec.rects
            labels[i] = rec.levels + jitter * rng.normal(size=leaves)
        else:
            r, lab = random_tree(n, m, leaves, rng)
            rects[i, :r.shape[0]] = r
            labels[i, :r.shape[0]] = dc + scale * lab
    return rects, labels
