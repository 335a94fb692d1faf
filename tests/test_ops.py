"""repro.ops — the registry's one selection rule, backend parity (including
awkward shapes through the batched Pallas kernel's pad paths), compensated
f32 parity against the f64 oracle, and the real multi-device mesh path for
the dispatched batched loss."""
import itertools
import os
import subprocess
import sys
import textwrap
import warnings

import numpy as np
import pytest

from repro import ops
from repro.core import fitting_loss, random_tree_segmentation, signal_coreset
from repro.data import piecewise_signal
from repro.kernels.sat2d.ref import scaled_rel_err

RNG = np.random.default_rng(0)


def _coreset(n=57, m=41, k=5, eps=0.3, seed=0):
    return signal_coreset(piecewise_signal(n, m, k, noise=0.2, seed=seed),
                          k, eps)


def _candidates(n, m, k, t, seed=1):
    rng = np.random.default_rng(seed)
    segs = [random_tree_segmentation(n, m, k, rng) for _ in range(t)]
    return (np.stack([s.rects for s in segs]).astype(np.float64),
            np.stack([s.labels for s in segs]))


# ------------------------------------------------------------------ registry
def test_every_op_has_all_three_backends():
    for op in ops.OPS:
        assert ops.available_backends(op) == ops.BACKENDS


def _tpu(monkeypatch, on: bool) -> None:
    monkeypatch.setattr(ops.registry, "_platform_is_tpu", lambda: on)


@pytest.mark.parametrize("op,platform,size", itertools.product(
    ops.OPS, ("cpu", "tpu"), ("below", "at_or_above")))
def test_selection_rule(monkeypatch, op, platform, size):
    """The one rule: ``pallas`` on a TPU; elsewhere ``xla`` once an op with
    a size threshold reaches it, else the numpy oracle.  The precision-
    critical ops (threshold None: they feed S2 - S1^2/S0) never size-
    promote at any size, and interpret-mode Pallas is never selected off a
    TPU."""
    _tpu(monkeypatch, platform == "tpu")
    thr = ops.registry.XLA_SIZE_THRESHOLD[op]
    n = {"below": (thr or 1 << 16) - 1,
         "at_or_above": thr if thr is not None else 10**12}[size]
    if platform == "tpu":
        want = "pallas"
    elif thr is not None and size == "at_or_above":
        want = "xla"
    else:
        want = "numpy"
    assert ops.select_backend(op, n) == want
    assert ops.selected_backend(op, n) == want
    assert ops.selected_backend(op, n, backend="numpy") == "numpy"


def test_backend_override_context_beats_env(monkeypatch):
    """The override context beats every other selection input — the
    platform rule included; no environment variable takes part."""
    _tpu(monkeypatch, True)
    with ops.backend_override("numpy"):
        assert ops.select_backend("sat_moments") == "numpy"
        with ops.backend_override("xla"):
            assert ops.select_backend("sat_moments") == "xla"
        assert ops.select_backend("sat_moments") == "numpy"
    assert ops.select_backend("sat_moments") == "pallas"


def test_unknown_backend_and_op_raise():
    with pytest.raises(ops.BackendError):
        ops.resolve("fitting_loss", "cuda")
    with pytest.raises(ops.BackendError):
        ops.select_backend("matmul")


def test_snapshot_surfaces_selection_state():
    snap = ops.snapshot()
    assert set(snap) == set(ops.OPS)
    for entry in snap.values():
        assert set(entry["available"]) == set(ops.BACKENDS)
        assert entry["selected"] in ops.BACKENDS


# ------------------------------------------------------ backend parity (ops)
@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_batched_parity_vs_oracle(backend):
    cs = _coreset()
    sr, sl = _candidates(57, 41, 4, 5)
    want = np.array([fitting_loss(cs, r, l) for r, l in zip(sr, sl)])
    got = ops.fitting_loss_batched(cs, sr, sl, backend=backend)
    np.testing.assert_allclose(got, want, rtol=2e-3)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_single_loss_parity_vs_oracle(backend):
    cs = _coreset(seed=3)
    rng = np.random.default_rng(2)
    q = random_tree_segmentation(57, 41, 6, rng)
    want = fitting_loss(cs, q.rects, q.labels)
    got = ops.fitting_loss(cs, q.rects, q.labels, backend=backend)
    assert abs(got - want) / want < 2e-3


def test_k_equals_one_all_backends():
    cs = _coreset(seed=4)
    sr = np.array([[[0, 57, 0, 41]]], np.float64)     # one leaf covers all
    sl = np.array([[0.4]])
    want = ops.fitting_loss_batched(cs, sr, sl, backend="numpy")
    for b in ("xla", "pallas"):
        got = ops.fitting_loss_batched(cs, sr, sl, backend=b)
        np.testing.assert_allclose(got, want, rtol=2e-3)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_loss_parity_on_recompressed_coreset(backend):
    """After a merge-reduce recompression a block's mass differs from its
    area; a leaf covering the block must still consume all of the mass,
    as the oracle's exact moment phase does."""
    from repro.core import compose, recompress
    y = piecewise_signal(64, 44, 5, noise=0.15, seed=23) + 50.0
    parts = [signal_coreset(y[a:b], 5, 0.3) for a, b in ((0, 32), (32, 64))]
    cs = recompress(compose(parts, [0, 32], n_total=64))
    area = ((cs.rects[:, 1] - cs.rects[:, 0])
            * (cs.rects[:, 3] - cs.rects[:, 2]))
    assert np.any(np.abs(cs.weights.sum(axis=1) - area) > 1e-6 * area)
    sr, sl = _candidates(64, 44, 5, 4, seed=26)
    want = np.array([fitting_loss(cs, r, l) for r, l in zip(sr, sl)])
    got = ops.fitting_loss_batched(cs, sr, sl, backend=backend)
    np.testing.assert_allclose(got, want, rtol=1e-4)


def test_zero_weight_padded_blocks_contribute_nothing():
    # the fitting_loss_batched pad path: explicit zero-weight blocks must
    # not change the loss (same invariant the kernel's internal B-padding
    # relies on)
    cs = _coreset(seed=5)
    sr, sl = _candidates(57, 41, 3, 3, seed=6)
    base = ops.fitting_loss_batched(cs, sr, sl, backend="pallas")
    import copy
    padded = copy.copy(cs)
    extra = 7    # keeps B % tile awkward too
    padded.rects = np.vstack([cs.rects, np.zeros((extra, 4), np.int64)])
    padded.labels = np.vstack([cs.labels, RNG.normal(size=(extra, 4))])
    padded.weights = np.vstack([cs.weights, np.zeros((extra, 4))])
    got = ops.fitting_loss_batched(padded, sr, sl, backend="pallas")
    np.testing.assert_allclose(got, base, rtol=1e-6)


def test_sat_moments_parity_awkward_shape():
    y = piecewise_signal(33, 47, 4, noise=0.3, seed=7)   # non-tile multiple
    ref = ops.sat_moments(y, backend="numpy")
    for b in ("xla", "pallas"):
        got = ops.sat_moments(y, backend=b)
        np.testing.assert_allclose(got, ref, rtol=5e-4, atol=5e-3)


def test_delta_sat_numpy_oracle_is_bitwise_continuation():
    # the whole point of the f64 delta_sat oracle: chaining patches must be
    # indistinguishable from a from-scratch sat_moments build
    y = piecewise_signal(41, 37, 4, noise=0.3, seed=20)
    full = ops.sat_moments(y, backend="numpy")
    chained = ops.delta_sat(np.zeros((3, 37)), y[:17], backend="numpy")
    chained = np.concatenate(
        [chained, ops.delta_sat(chained[:, -1, :], y[17:], backend="numpy")],
        axis=1)
    assert chained.shape == full.shape
    for c in range(3):
        np.testing.assert_array_equal(chained[c], full[c])


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_delta_sat_parity_vs_oracle(backend):
    rng = np.random.default_rng(21)
    y = rng.normal(size=(45, 37))                        # off tile quanta
    carry = ops.sat_moments(y, backend="numpy")[:, 29, :]
    tail = y[30:]
    want = ops.delta_sat(carry, tail, backend="numpy")
    got = ops.delta_sat(carry, tail, backend=backend)
    np.testing.assert_allclose(got, want, rtol=5e-4, atol=5e-3)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_delta_sat_one_row_band_from_row_zero(backend):
    rng = np.random.default_rng(22)
    tail = rng.normal(size=(1, 129))                     # 1-row, m % 128 != 0
    want = ops.delta_sat(np.zeros((3, 129)), tail, backend="numpy")
    got = ops.delta_sat(np.zeros((3, 129)), tail, backend=backend)
    np.testing.assert_allclose(got, want, rtol=5e-4, atol=5e-3)
    np.testing.assert_allclose(
        np.asarray(want), ops.sat_moments(tail, backend="numpy"))


@pytest.mark.parametrize("op", ["sat_moments", "delta_sat"])
def test_compensated_dispatch_splits_transfer_and_device(op):
    """Under a live span a compensated xla dispatch times its upload,
    device call and read-back apart, with the bytes each moves; outside a
    trace it returns what an unsplit call returns, bit for bit."""
    import jax
    from repro.kernels.sat2d.ref import (delta_sat_comp_ref,
                                         sat_moments_comp_ref, split_hi_lo)
    from repro.obs import Tracer
    rng = np.random.default_rng(31)
    n, m = 37, 29
    y = rng.normal(size=(n, m)) + 100.0
    if op == "sat_moments":
        # the device ships the S1/S2 pairs alone; the counts are the host's
        args = (y,)
        h, l = jax.jit(sat_moments_comp_ref)(*split_hi_lo(y))
        counts = np.multiply.outer(np.arange(1.0, n + 1), np.arange(1.0, m + 1))
        want = np.concatenate([counts[None], np.asarray(h, np.float64)
                               + np.asarray(l, np.float64)])
        up, down = 8 * n * m, 16 * n * m
    else:
        carry = ops.sat_moments(y, backend="numpy")[:, 9, :]
        args = (carry, y[10:])
        want = ops.backends._recombine(jax.jit(delta_sat_comp_ref)(
            *split_hi_lo(carry), *split_hi_lo(y[10:])))
        up, down = 8 * (3 * m + (n - 10) * m), 24 * (n - 10) * m
    fn = getattr(ops, op)
    np.testing.assert_array_equal(fn(*args, backend="xla"), want)
    tr = Tracer(capacity=4)
    root = tr.start_trace("req")
    with tr.attach(root):
        got = fn(*args, backend="xla")
    root.end()
    np.testing.assert_array_equal(got, want)
    spans = tr.get(root.trace_id)["spans"]
    [disp] = [s for s in spans if s["name"] == "ops.dispatch"]
    kids = {s["name"]: s for s in spans if s["parent_id"] == disp["span_id"]}
    assert set(kids) == {"dispatch.h2d", "dispatch.device", "dispatch.d2h"}
    assert kids["dispatch.h2d"]["attrs"]["bytes"] == up
    assert kids["dispatch.d2h"]["attrs"]["bytes"] == down
    assert sum(k["duration_us"] for k in kids.values()) <= \
        disp["duration_us"]


@pytest.mark.parametrize("shape", [(37, 29), (2 * 128 + 3, 3 * 128 + 5)],
                         ids=["37x29", "259x389"])
@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_compensated_build_lands_in_query_layout(backend, shape):
    """A compensated build's images are the read-back pairs summed in f64
    beside the exact host counts, as three planes of one zero-bordered
    (3, n+1, m+1) buffer that ``from_sat`` wraps without a copy."""
    import jax
    from repro.core.stats import PrefixStats
    from repro.kernels.sat2d.ops import sat_moments_comp
    from repro.kernels.sat2d.ref import sat_moments_comp_ref, split_hi_lo
    n, m = shape
    y = np.random.default_rng(41).normal(size=shape) + 100.0
    if backend == "xla":
        h, l = jax.jit(sat_moments_comp_ref)(*split_hi_lo(y))
    else:   # the Pallas scan sums in its own blocked order
        h, l = sat_moments_comp(*split_hi_lo(y), interpret=True)
    assert h.shape == l.shape == (2, n, m)
    with ops.backend_override(backend):
        ps = PrefixStats.build(y)
    planes = (ps.p0, ps.p1, ps.p2)
    np.testing.assert_array_equal(
        ps.p0, np.multiply.outer(np.arange(n + 1), np.arange(m + 1)))
    for c in (1, 2):
        np.testing.assert_array_equal(planes[c][0], 0.0)
        np.testing.assert_array_equal(planes[c][:, 0], 0.0)
        np.testing.assert_array_equal(
            planes[c][1:, 1:], np.asarray(h[c - 1], np.float64)
            + np.asarray(l[c - 1], np.float64))
    buf = ps.p0.base
    assert buf.shape == (3, n + 1, m + 1) and buf.flags.c_contiguous
    for p in planes:
        assert p.flags.c_contiguous and p.base is buf
    with ops.backend_override("numpy"):
        want = PrefixStats.build(y)
    for got_c, want_c in zip(planes, (want.p0, want.p1, want.p2)):
        assert scaled_rel_err(got_c, want_c) <= PARITY_RTOL


def _bordered(s, border=0.0):
    buf = np.full((3, s.shape[1] + 1, s.shape[2] + 1), border)
    buf[:, 1:, 1:] = s
    return buf


@pytest.mark.parametrize("case", ["plain", "nonzero_border", "sub_view",
                                  "f32_buffer"])
def test_from_sat_copies_anything_but_a_landing(case):
    """Only the interior view of a zero-bordered f64 (3, n+1, m+1) buffer
    is wrapped in place; any other (3, n, m) input gives fresh planes with
    the same values, so a later in-place patch cannot reach the caller's
    array."""
    from repro.core.stats import PrefixStats
    y = np.random.default_rng(43).normal(size=(9, 7))
    s = ops.sat_moments(y, backend="numpy")
    if case == "plain":
        arg = s
    elif case == "nonzero_border":
        arg = _bordered(s, border=1.0)[:, 1:, 1:]
    elif case == "sub_view":    # the interior of a larger zero border
        arg = np.pad(_bordered(s), ((0, 0), (0, 1), (0, 1)))[:, 1:-1, 1:-1]
    else:
        arg = _bordered(s).astype(np.float32)[:, 1:, 1:]
    ps = PrefixStats.from_sat(arg)
    for c, p in enumerate((ps.p0, ps.p1, ps.p2)):
        assert not np.shares_memory(p, arg)
        np.testing.assert_array_equal(p[0], 0.0)
        np.testing.assert_array_equal(p[:, 0], 0.0)
        np.testing.assert_array_equal(p[1:, 1:], arg[c])
    landed = _bordered(s)[:, 1:, 1:]
    ps = PrefixStats.from_sat(landed)
    assert all(np.shares_memory(p, landed) for p in (ps.p0, ps.p1, ps.p2))


# -------------------------------- compensated-f32 parity, awkward shapes
# The two-float paths of the integral-image and histogram ops must match
# the f64 oracle to 1e-6 in the scaled measure S2 - S1^2/S0 feels.
PARITY_RTOL = 1e-6
_COMP_RNG = np.random.default_rng(7)
_COMPENSATED = {"compensated": True}


def test_compensated_sat_parity_off_tile_quantum():
    # 131 x 67: off the 128-row tile quantum, large offset so plain f32
    # cumsum error is visible while the two-float path stays in bound
    y = _COMP_RNG.normal(size=(131, 67)) + 1e6
    want = ops.sat_moments(y, backend="numpy")
    got = ops.sat_moments(y, backend="xla", config=_COMPENSATED)
    assert scaled_rel_err(got, want) <= PARITY_RTOL


def test_compensated_delta_sat_parity():
    y = _COMP_RNG.normal(size=(34, 257)) + 1e5  # odd band, off-quantum width
    carry = ops.sat_moments(y[:1], backend="numpy")[:, 0, :]
    want = ops.delta_sat(carry, y[1:], backend="numpy")
    got = ops.delta_sat(carry, y[1:], backend="xla", config=_COMPENSATED)
    assert scaled_rel_err(got, want) <= PARITY_RTOL


def _hist_problem(P, F, B, zero_frac=0.0):
    codes = _COMP_RNG.integers(0, B, size=(P, F)).astype(np.uint8)
    w = _COMP_RNG.uniform(0.5, 1.5, P)
    if zero_frac:
        w[_COMP_RNG.random(P) < zero_frac] = 0.0  # zero-weight rows vanish
    yv = _COMP_RNG.normal(size=P) + 100.0
    return codes, w, w * yv, w * yv * yv


@pytest.mark.parametrize("backend", ["xla", "pallas"],
                         ids=["xla-chunked", "pallas-partials"])
def test_compensated_hist_parity_awkward_shapes(backend):
    # P=4097: off both the 2048 Pallas tile and the 8192 XLA chunk quantum,
    # so the padded tail blocks (zero-weight by construction) are exercised
    codes, w, wy, wy2 = _hist_problem(4097, 3, 16, zero_frac=0.1)
    want = ops.hist_split(codes, w, wy, wy2, 16, backend="numpy")
    got = ops.hist_split(codes, w, wy, wy2, 16, backend=backend,
                         config=_COMPENSATED)
    assert scaled_rel_err(got, want) <= PARITY_RTOL


@pytest.mark.parametrize("backend", ["xla", "pallas"],
                         ids=["xla-chunked", "pallas-partials"])
def test_compensated_hist_parity_single_bin(backend):
    codes, w, wy, wy2 = _hist_problem(1023, 2, 1)    # n_bins=1 degenerate
    want = ops.hist_split(codes, w, wy, wy2, 1, backend="numpy")
    got = ops.hist_split(codes, w, wy, wy2, 1, backend=backend,
                         config=_COMPENSATED)
    assert scaled_rel_err(got, want) <= PARITY_RTOL


def test_delta_sat_validates_shapes():
    with pytest.raises(ValueError):
        ops.delta_sat(np.zeros((3, 4)), np.zeros((2, 5)))   # carry mismatch
    with pytest.raises(ValueError):
        ops.delta_sat(np.zeros((3, 4)), np.zeros((0, 4)))   # empty band


@pytest.mark.parametrize("backend", ["numpy", "xla", "pallas"])
def test_streaming_compress_batched_parity(backend):
    """One dispatch recompresses several buckets; every backend must
    preserve the exact f64 mass/M1/M2 (those never route through f32) and
    agree with the numpy oracle on the recompressed geometry's loss."""
    from repro.core import compose
    y = piecewise_signal(64, 44, 5, noise=0.15, seed=23)
    parts = [signal_coreset(y[a:b], 5, 0.3) for a, b in ((0, 32), (32, 64))]
    buckets = [compose(parts, [0, 32], n_total=64),
               compose(list(reversed(parts)), [32, 0], n_total=64)]
    ref = ops.streaming_compress(buckets, backend="numpy")
    got = ops.streaming_compress(buckets, backend=backend)
    assert len(got) == len(buckets)
    rng = np.random.default_rng(24)
    q = random_tree_segmentation(64, 44, 5, rng)
    for g, r, b in zip(got, ref, buckets):
        assert np.isclose(g.total_mass(), b.total_mass())
        assert np.isclose(g.moments[:, 1].sum(), b.moments[:, 1].sum())
        assert np.isclose(g.moments[:, 2].sum(), b.moments[:, 2].sum())
        lg = fitting_loss(g, q.rects, q.labels)
        lr = fitting_loss(r, q.rects, q.labels)
        np.testing.assert_allclose(lg, lr, rtol=0.1)


def test_streaming_compress_empty_and_single():
    assert ops.streaming_compress([]) == []
    cs = _coreset(seed=25)
    from repro.core import recompress
    via_op = ops.streaming_compress([cs], backend="numpy")[0]
    direct = recompress(cs)
    assert via_op.fingerprint() == direct.fingerprint()


def test_hist_split_parity_awkward_sizes():
    P, F, B = 1030, 3, 17                                # P % tile != 0
    codes = RNG.integers(0, B, size=(P, F)).astype(np.uint8)
    w = RNG.uniform(0.1, 2, P)
    y = RNG.normal(size=P)
    ref = ops.hist_split(codes, w, w * y, w * y * y, B, backend="numpy")
    for b in ("xla", "pallas"):
        got = ops.hist_split(codes, w, w * y, w * y * y, B, backend=b)
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


# ------------------------------------------- batched kernel tile pad paths
def test_batched_kernel_awkward_tile_quanta():
    """B and K not multiples of the tile quantum, T not a multiple of the
    T-tile: every pad path of the (T-tile, B-tile) grid at once."""
    import jax.numpy as jnp
    from repro.kernels.fitting_loss.kernel import fitting_loss_batched_call
    cs = _coreset(seed=8)
    B = cs.num_blocks
    assert B > 13
    rects = jnp.asarray(cs.rects[:13], jnp.float32)      # B=13, tile_b=8
    lab = jnp.asarray(cs.labels[:13], jnp.float32)
    wgt = jnp.asarray(cs.weights[:13], jnp.float32)
    sr, sl = _candidates(57, 41, 7, 3, seed=9)           # T=3, tile_t=2, K=7
    got = np.asarray(fitting_loss_batched_call(
        rects, lab, wgt, jnp.asarray(sr, jnp.float32),
        jnp.asarray(sl, jnp.float32), tile_b=8, tile_t=2, interpret=True))
    from repro.kernels.fitting_loss.ref import fitting_loss_ref
    want = np.array([float(fitting_loss_ref(
        rects, lab, wgt, jnp.asarray(r, jnp.float32),
        jnp.asarray(l, jnp.float32))) for r, l in zip(sr, sl)])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


# --------------------------------------------------------- deprecated shim
def test_coreset_loss_many_shim_delegates_and_warns_once():
    import repro.kernels.fitting_loss.ops as fl_ops
    cs = _coreset(seed=10)
    sr, sl = _candidates(57, 41, 4, 3, seed=11)
    fl_ops._MANY_DEPRECATION_WARNED = False
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        got = np.asarray(fl_ops.coreset_loss_many(cs, list(sr), list(sl)))
        again = np.asarray(fl_ops.coreset_loss_many(cs, list(sr), list(sl)))
    deps = [w for w in caught if issubclass(w.category, DeprecationWarning)]
    assert len(deps) == 1                       # warn once
    want = np.array([fitting_loss(cs, r, l) for r, l in zip(sr, sl)])
    np.testing.assert_allclose(got, want, rtol=2e-3)
    np.testing.assert_allclose(again, want, rtol=2e-3)


def test_coreset_loss_many_shim_accepts_ragged_leaf_counts():
    # the pre-dispatch loop accepted candidates with differing K; the shim
    # must too (per-item scoring instead of the fused stack).  The warn-once
    # flag is reset and the warning captured explicitly so this test is
    # order-independent and stays green under -W error::DeprecationWarning.
    import repro.kernels.fitting_loss.ops as fl_ops
    cs = _coreset(seed=14)
    rng = np.random.default_rng(15)
    segs = [random_tree_segmentation(57, 41, k, rng) for k in (3, 6)]
    fl_ops._MANY_DEPRECATION_WARNED = False
    with pytest.warns(DeprecationWarning, match="fitting_loss_batched"):
        got = np.asarray(fl_ops.coreset_loss_many(
            cs, [s.rects for s in segs], [s.labels for s in segs]))
    want = np.array([fitting_loss(cs, s.rects, s.labels) for s in segs])
    np.testing.assert_allclose(got, want, rtol=2e-3)


# -------------------------------------------------------- engine integration
def test_engine_stats_surface_ops_backends():
    from repro.service import CoresetEngine, ServiceMetrics
    eng = CoresetEngine(workers=1, metrics=ServiceMetrics())
    try:
        eng.register_signal("s", piecewise_signal(48, 32, 4, seed=12))
        sr, sl = _candidates(48, 32, 3, 2, seed=13)
        r = eng.tree_loss_batch("s", sr.astype(np.int64), sl, eps=0.3)
        assert r["backend"] in ("numpy", "xla", "pallas")
        snap = eng.stats()["ops_backends"]
        assert set(snap) == set(ops.OPS)
    finally:
        eng.close()


def test_engine_counts_backend_compiles():
    from repro.service import CoresetEngine, ServiceMetrics
    eng = CoresetEngine(workers=1, metrics=ServiceMetrics())
    try:
        # the first accelerator resolve installs the compile listener
        ops.sat_moments(np.ones((19, 7)), backend="xla")
        before = eng.metrics.get("jit_compiles_total")
        ops.sat_moments(np.ones((23, 5)), backend="xla")   # a new shape
        assert eng.metrics.get("jit_compiles_total") > before
        assert "coreset_jit_compiles_total" in eng.metrics.render()
    finally:
        eng.close()
    closed = eng.metrics.get("jit_compiles_total")
    ops.sat_moments(np.ones((29, 3)), backend="xla")
    assert eng.metrics.get("jit_compiles_total") == closed


# ----------------------------------------------------------- real mesh path
@pytest.mark.slow   # subprocess + forced 8-device host mesh; ci_smoke's ops
                    # stage still runs it by name
def test_mesh_sharded_batched_loss_matches_oracle():
    """The ROADMAP's 'exercise the mesh path for real': a forced 8-device
    host mesh and fitting_loss_batched shard_map'd over it — parity against
    the numpy oracle, AND the dispatch profile must attribute the hop to the
    batched Pallas kernel (backend ``pallas+shard_map``), not the dense ref
    the old pjit path ran.  Runs in a subprocess so XLA_FLAGS takes effect
    before jax initializes."""
    script = textwrap.dedent("""
        import numpy as np, jax
        assert jax.device_count() >= 8, jax.devices()
        from repro.launch.mesh import make_mesh
        from repro.core import (fitting_loss, fitting_loss_batched,
                                random_tree_segmentation, signal_coreset)
        from repro.core.sharded import MESH_BACKEND
        from repro.data import piecewise_signal
        from repro.obs import profile
        y = piecewise_signal(48, 40, 5, noise=0.2, seed=0)
        cs = signal_coreset(y, 5, 0.3)
        rng = np.random.default_rng(0)
        segs = [random_tree_segmentation(48, 40, 4, rng) for _ in range(3)]
        sr = np.stack([s.rects for s in segs]).astype(np.float64)
        sl = np.stack([s.labels for s in segs])
        samples = []
        profile.add_hook(lambda op, b, size, dt: samples.append((op, b)))
        mesh = make_mesh((8,), ("data",), jax.devices())
        got = fitting_loss_batched(cs, sr, sl, mesh=mesh)
        want = np.array([fitting_loss(cs, s.rects, s.labels) for s in segs])
        assert np.allclose(got, want, rtol=2e-3, atol=1e-3), (got, want)
        assert ("fitting_loss_batched", MESH_BACKEND) in samples, samples
        assert MESH_BACKEND == "pallas+shard_map", MESH_BACKEND
        print("MESH-PARITY-OK devices=%d backend=%s"
              % (jax.device_count(), MESH_BACKEND))
    """)
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH="src" + os.pathsep + os.environ.get("PYTHONPATH", ""))
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run([sys.executable, "-c", script], env=env, cwd=repo,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr
    assert "MESH-PARITY-OK" in proc.stdout
