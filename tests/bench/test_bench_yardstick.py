"""CPU tests of the chip benchmark's yardstick (benchmarks/chip/yardstick):
generators, percentiles, the trace reduction, operation and byte counts,
the peak table and the f64 reference."""
from __future__ import annotations

import json
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "benchmarks" / "chip"))

from yardstick import (costs, devtrace, endtoend, peaks, reference,  # noqa: E402
                       signals, traffic)

CHIP = ROOT / "benchmarks" / "chip"
CONFIGS = {p.stem: json.loads(p.read_text())
           for p in sorted((CHIP / "configs").glob("*.json"))}
MIXES = {p.stem: traffic.load(p)
         for p in sorted((CHIP / "traffic").glob("*.json"))}
SMALL = {"n": 96, "m": 80, "gen_leaves": 6, "noise": 0.15, "scale": 2.0,
         "dc_offset": 100.0}


def _small(config: dict) -> dict:
    out = dict(config)
    out["signal"] = dict(SMALL)
    out["recordcount"] = 3
    return out


# ------------------------------------------------------------ generators
def test_copies_draw_what_the_program_draws():
    from repro.core.segmentation import random_tree_segmentation
    from repro.data.signals import piecewise_signal
    for seed in (0, 5):
        r, lab = signals.random_tree(64, 48, 7, np.random.default_rng(seed))
        s = random_tree_segmentation(64, 48, 7, np.random.default_rng(seed))
        np.testing.assert_array_equal(r, s.rects)
        np.testing.assert_array_equal(lab, s.labels)
        y, _, _ = signals.piecewise(64, 48, 7, 0.15, 2.0,
                                    np.random.default_rng(seed))
        np.testing.assert_array_equal(
            y, piecewise_signal(64, 48, 7, noise=0.15, scale=2.0, seed=seed))


@pytest.mark.parametrize("seed", [0, 3, 2**31 + 11, -4])
def test_records_and_pools_repeat_per_seed(seed):
    cfg = _small(CONFIGS["ycsb_rasters"])
    a = signals.record(cfg["signal"], "a", seed, 1, 0)
    b = signals.record(cfg["signal"], "a", seed, 1, 0)
    np.testing.assert_array_equal(a.values, b.values)
    np.testing.assert_array_equal(a.rects, b.rects)
    np.testing.assert_array_equal(a.levels, b.levels)
    c = signals.record(cfg["signal"], "a", seed + 1, 1, 0)
    assert not np.array_equal(a.values, c.values)


@pytest.mark.parametrize("name", sorted(MIXES))
def test_traffic_is_deterministic_per_seed(name):
    mix, cfg = MIXES[name], _small(CONFIGS["ycsb_rasters"])
    assert mix["route"] == "register_build"
    a, b = traffic.insert_order(cfg, 9), traffic.insert_order(cfg, 9)
    np.testing.assert_array_equal(a, b)
    # another seed: the same records, in another order
    c = traffic.insert_order(cfg, 2**31 + 10)
    assert sorted(a) == sorted(c) == list(range(cfg["recordcount"]))
    assert not np.array_equal(a, c)
    np.testing.assert_array_equal(traffic.record_at(mix, cfg, 1).values,
                                  traffic.record_at(mix, cfg, 1).values)
    assert not np.array_equal(traffic.record_at(mix, cfg, 1).values,
                              traffic.record_at(mix, cfg, 2).values)
    # the clients between them insert every record, each as often as the
    # others give or take one
    seen = [traffic.insert_index(mix, a, cl, j) for j in range(4)
            for cl in range(int(mix["clients"]))]
    counts = np.bincount(seen, minlength=cfg["recordcount"])
    assert counts.max() - counts.min() <= 1
    assert traffic.insert_name(1, 2) != traffic.insert_name(2, 1)


def test_reference_matches_the_direct_loss():
    rng = np.random.default_rng(1)
    y = 100.0 + rng.normal(size=(70, 90))
    ref = reference.SignalReference(y)
    trees = [signals.random_tree(70, 90, 9, rng) for _ in range(5)]
    rects = np.stack([t[0] for t in trees])
    labels = 100.0 + np.stack([t[1] for t in trees])
    got = ref.losses(rects, labels)
    want = [reference.direct_loss(y, r, lab) for r, lab in zip(rects, labels)]
    np.testing.assert_allclose(got, want, rtol=1e-11)
    # zero-area padding leaves add nothing
    padded = np.concatenate([rects, np.zeros((5, 3, 4), np.int64)], axis=1)
    np.testing.assert_allclose(
        ref.losses(padded, np.pad(labels, ((0, 0), (0, 3)))), got, rtol=0)


def test_rel_errors():
    np.testing.assert_allclose(reference.rel_errors([11.0, 9.0], [10.0, 10.0]),
                               [0.1, 0.1])


# ------------------------------------------------------------ percentiles
class _Run:
    def __init__(self, records, t0=0.0, seconds=10.0):
        self.records, self.t0, self.seconds = records, t0, seconds


def test_percentiles_are_from_due_times_over_all_requests():
    # a closed-loop request is due when its client sends it; the median is
    # taken over every request of the window, a failed one infinitely late
    sent = np.arange(10, dtype=float)
    done = sent + np.array([1, 1, 1, 1, 1, 1, 1, 1, 2, 3], float)
    ok = np.ones(10, bool)
    run = _Run({"sent": sent, "done": done, "ok": ok})
    assert endtoend.ready_p50_s(run) == pytest.approx(1.0)
    lat = done - sent
    assert endtoend.percentile(lat, 95) == pytest.approx(np.percentile(lat, 95))
    ok[:6] = False
    failed = _Run(dict(run.records, ok=ok))
    assert np.isinf(endtoend.ready_p50_s(failed))
    assert np.isinf(endtoend.percentile(np.where(ok, lat, np.inf), 95))


def test_ready_median_counts_failures_as_late():
    recs = {"ok": np.array([True, True, False]),
            "sent": np.array([0.0, 1.0, 2.0]),
            "done": np.array([4.0, 7.0, 3.0])}
    assert endtoend.ready_p50_s(_Run(recs)) == pytest.approx(6.0)


# ------------------------------------------------------- operations, bytes
def test_fitting_loss_costs_by_hand():
    # 2 blocks x 3 trees x 4 leaves = 24 pairs at 42 operations each;
    # bytes: 12 f32 per block, 5 per leaf, 1 per tree
    assert costs.fitting_loss_batched(2, 3, 4) == (42 * 24, 4 * (24 + 60 + 3))


def test_sat_moments_costs_by_hand():
    assert costs.sat_moments_comp(10, 20) == (27 * 200, 32 * 200)


def test_least_seconds_names_its_bound():
    p = {"flops_per_s": 100.0, "bytes_per_s": 10.0}
    assert costs.least_seconds(1000.0, 50.0, p) == (10.0, "compute")
    assert costs.least_seconds(100.0, 50.0, p) == (5.0, "memory")


# ------------------------------------------------------------------ peaks
def test_peaks_known_and_unknown():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e["flops_per_s"] == 197e12 and v5e["bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v99 imaginary")


# ------------------------------------------------------- trace reduction
def test_union_and_gaps_by_hand():
    iv = np.array([[0, 10], [5, 20], [30, 40]], float)
    assert devtrace.union_seconds(iv, 0, 100) == pytest.approx(30e-9)
    assert devtrace.union_seconds(iv, 8, 35) == pytest.approx(17e-9)
    np.testing.assert_array_equal(devtrace.gaps(iv, 0, 50),
                                  [[20, 30], [40, 50]])


def test_op_names_drop_suffixes():
    assert devtrace._op_name(
        "%fitting_loss_batched_call.1 = f32[1,128] custom-call(...)") == \
        "fitting_loss_batched_call"
    assert devtrace._op_name("%pad.7.clone = f32[16] pad(...)") == "pad"


def test_reduction_of_a_trace_recorded_here(tmp_path):
    """Record a small trace on this host's CPU backend and reduce it: the
    jitted program appears as a module, its ops as busy time inside the
    window, the marker on the host clock, and busy never exceeds the
    window."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(x):
        return jnp.tanh(x @ x).sum()

    x = jnp.ones((256, 256))
    step(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("chipbench.window_open"):
        pass
    for _ in range(5):
        step(x).block_until_ready()
    jax.profiler.stop_trace()
    path = next(tmp_path.glob("**/*.xplane.pb"))
    tr = devtrace.DeviceTrace.from_file(path)
    lo = tr.markers["chipbench.window_open"]
    hi = max(e for ops in tr.planes.values() for _, _, e in ops) + 1
    busy = tr.busy_seconds(lo, hi)
    assert 0 < busy <= (hi - lo) / 1e9
    assert sum(1 for _, name, s, _ in tr.modules
               if name.startswith("jit_step") and lo <= s <= hi) >= 5
    assert tr.module_seconds("jit_step", lo, hi) > 0
    assert tr.top_ops(lo, hi) and all(v > 0 for _, v in tr.top_ops(lo, hi))
    g = tr.idle_gaps(lo, hi)
    assert busy + float(np.sum(g[:, 1] - g[:, 0])) / 1e9 == \
        pytest.approx((hi - lo) / 1e9, rel=1e-9)
