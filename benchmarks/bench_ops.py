"""Backend sweep for the repro.ops dispatch surface.

Times every registered backend (numpy oracle, jitted xla, Pallas —
interpret-mode on this CPU container, so its wall-times are kernel-body
semantics, not TPU timing) for each of the four canonical ops, and records
cross-backend parity deltas.  The batched-Pallas-vs-dense delta is the
number ``scripts/ci_smoke.sh`` gates on (<= 1e-4 relative): the serving
engine's /v1/query/loss:batch hot path rides the batched kernel on TPU, so
it must agree with the dense dispatched path it replaced.

The ``compensated`` section measures the two-float paths against the f64
oracle in the scaled measure the variance identity feels
(``scripts/check_bench_regression.py ops`` holds them to 1e-6).

Results merge into ``benchmarks/results/bench_ops.json`` keyed by op and
backend (existing keys from other runs are preserved).

  python -m benchmarks.bench_ops [--fast]
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

try:
    from .common import RESULTS, emit, timed   # python -m benchmarks.bench_ops
except ImportError:  # pragma: no cover
    sys.path.insert(0, str(__import__("pathlib").Path(__file__).parent))
    from common import RESULTS, emit, timed    # python benchmarks/bench_ops.py

from repro import ops                                        # noqa: E402
from repro.core import random_tree_segmentation, signal_coreset  # noqa: E402
from repro.data import piecewise_signal                      # noqa: E402
from repro.kernels.sat2d.ref import scaled_rel_err           # noqa: E402
from repro.obs.profile import shape_bucket                   # noqa: E402


def _merge_save(obj: dict) -> None:
    RESULTS.mkdir(parents=True, exist_ok=True)
    path = RESULTS / "bench_ops.json"
    merged = {}
    if path.exists():
        merged = json.loads(path.read_text())
    for op, per_backend in obj.items():
        if isinstance(per_backend, dict):
            merged.setdefault(op, {}).update(per_backend)
        else:
            merged[op] = per_backend
    path.write_text(json.dumps(merged, indent=1, default=float))


def _rel(a, b) -> float:
    a = np.asarray(a, np.float64).ravel()
    b = np.asarray(b, np.float64).ravel()
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-9)))


def _ingest_delta_gate(n: int, m: int, band_rows: int) -> dict:
    """Delta write path vs legacy full re-ingest on an (n, m) registered
    signal: the delta ships/patches ``band_rows`` rows (delta_sat + version
    fold), the legacy path re-registers all n rows and re-SATs from scratch.
    Also records the loss parity of the delta-built coreset against a
    from-scratch build of the final signal (ci_smoke gates both numbers)."""
    import time

    from repro.core import fitting_loss, random_tree_segmentation
    from repro.service import CoresetEngine, ServiceMetrics

    rng = np.random.default_rng(7)
    y = piecewise_signal(n, m, 8, noise=0.15, seed=2)
    band = rng.normal(size=(band_rows, m))
    y2 = y.copy()
    y2[n - band_rows:] = band
    k, eps = 8, 0.3

    eng = CoresetEngine(workers=1, metrics=ServiceMetrics())
    scratch = CoresetEngine(workers=1, metrics=ServiceMetrics())
    try:
        eng.register_signal("sig", y)
        eng.signal("sig").ensure_stats()   # steady state: SAT materialized
        t0 = time.perf_counter()
        eng.ingest_delta("sig", band, row0=n - band_rows)
        delta_s = time.perf_counter() - t0
        cs_delta, _, _ = eng.get_coreset("sig", k, eps)

        scratch.register_signal("sig", y2)
        cs_scratch, _, _ = scratch.get_coreset("sig", k, eps)

        # legacy full re-ingest of the same mutation: all n rows over the
        # registration path + a from-scratch re-SAT of the new state
        t0 = time.perf_counter()
        eng.register_signal("sig", y2, replace=True)
        eng.signal("sig").ensure_stats()
        rebuild_s = time.perf_counter() - t0

        q = random_tree_segmentation(n, m, k, rng)
        ld = fitting_loss(cs_delta, q.rects, q.labels)
        ls = fitting_loss(cs_scratch, q.rects, q.labels)
        parity = abs(ld - ls) / max(abs(ls), 1e-12)
        return {"n": n, "m": m, "band_rows": band_rows,
                "delta_ms": delta_s * 1e3, "rebuild_ms": rebuild_s * 1e3,
                "speedup": rebuild_s / max(delta_s, 1e-9),
                "loss_parity_rel": parity,
                "delta_fingerprint_matches": bool(
                    cs_delta.fingerprint() == cs_scratch.fingerprint())}
    finally:
        eng.close()
        scratch.close()


def run(fast: bool = False) -> dict:
    rng = np.random.default_rng(0)
    results: dict = {}
    repeat = 2 if fast else 3

    def sweep(op_name, call, parity_of, size=None):
        per = {}
        ref = None
        for b in ops.BACKENDS:
            out, _ = timed(call, b)                    # warmup / compile
            out, dt = timed(call, b, repeat=repeat)
            if ref is None:
                ref = parity_of(out)                   # numpy runs first
            per[b] = {"us_per_call": dt * 1e6,
                      "rel_delta_vs_numpy": _rel(parity_of(out), ref)}
            emit(f"ops/{op_name}_{b}", dt * 1e6,
                 f"rel_vs_numpy={per[b]['rel_delta_vs_numpy']:.2e}")
        if size is not None:
            per["auto_backend"] = ops.selected_backend(op_name, size)
            per["shape_bucket"] = shape_bucket(size)
        return per

    # ---- sat_moments
    n = 256 if fast else 768
    y = rng.normal(size=(n, n))
    results["sat_moments"] = sweep(
        "sat_moments", lambda b: ops.sat_moments(y, backend=b), lambda o: o,
        size=3 * y.size)

    # ---- fitting_loss + fitting_loss_batched on one coreset
    ys = piecewise_signal(96 if fast else 160, 80 if fast else 120, 6,
                          noise=0.2, seed=0)
    cs = signal_coreset(ys, 6, 0.3)
    segs = [random_tree_segmentation(*ys.shape, 6, rng)
            for _ in range(4 if fast else 16)]
    sr = np.stack([s.rects for s in segs]).astype(np.float64)
    sl = np.stack([s.labels for s in segs])
    results["fitting_loss"] = sweep(
        "fitting_loss",
        lambda b: ops.fitting_loss(cs, segs[0].rects, segs[0].labels,
                                   backend=b),
        lambda o: o, size=ops.fitting_loss_size(cs, segs[0].rects))
    results["fitting_loss_batched"] = sweep(
        "fitting_loss_batched",
        lambda b: ops.fitting_loss_batched(cs, sr, sl, backend=b),
        lambda o: o, size=ops.fitting_loss_batched_size(cs, sr))

    # the CI gate: batched Pallas kernel vs the dense dispatched (xla) path
    dense = ops.fitting_loss_batched(cs, sr, sl, backend="xla")
    pallas = ops.fitting_loss_batched(cs, sr, sl, backend="pallas")
    gate = _rel(pallas, dense)
    results["parity"] = {
        "batched_pallas_vs_dense_rel": gate,
        "coreset_blocks": cs.num_blocks, "trees": int(sr.shape[0]),
        "leaves": int(sr.shape[1]),
    }
    emit("ops/parity_batched_pallas_vs_dense", 0.0, f"rel={gate:.2e}")

    # ---- hist_split
    P, F, B = (50_000, 4, 64) if fast else (200_000, 8, 256)
    codes = rng.integers(0, B, size=(P, F)).astype(np.uint8)
    w = rng.uniform(0.5, 1.5, P)
    yv = rng.normal(size=P)
    results["hist_split"] = sweep(
        "hist_split",
        lambda b: ops.hist_split(codes, w, w * yv, w * yv * yv, B, backend=b),
        lambda o: o, size=codes.size)

    # ---- delta_sat (the ingest patch: one band's worth of rows, not O(N))
    dn, dm, band_rows = (512, 256, 16) if fast else (2048, 512, 32)
    yd = rng.normal(size=(dn, dm))
    carry = ops.sat_moments(yd, backend="numpy")[:, dn - band_rows - 1, :]
    tail = yd[dn - band_rows:]
    results["delta_sat"] = sweep(
        "delta_sat", lambda b: ops.delta_sat(carry, tail, backend=b),
        lambda o: o, size=3 * tail.size)

    # ---- streaming_compress (batched recompress of two composed buckets)
    from repro.core import compose
    sn = 96 if fast else 192
    ys = piecewise_signal(sn, 64, 5, noise=0.15, seed=1)
    parts = [signal_coreset(ys[a:b], 5, 0.3)
             for a, b in ((0, sn // 2), (sn // 2, sn))]
    buckets = [compose(parts, [0, sn // 2], n_total=sn)] * 2
    results["streaming_compress"] = sweep(
        "streaming_compress",
        lambda b: ops.streaming_compress(buckets, backend=b),
        lambda o: np.concatenate([np.sort(c.moments, axis=None) for c in o]))

    # ---- ingest_delta end-to-end gate numbers (ci_smoke asserts on these):
    # delta-patching a band into a registered signal vs the legacy full
    # re-ingest (replace registration + from-scratch re-SAT), plus the loss
    # parity of the delta-built coreset against a from-scratch build
    results["ingest_delta"] = _ingest_delta_gate(dn, dm, band_rows)
    emit("ops/ingest_delta_vs_rebuild",
         results["ingest_delta"]["delta_ms"] * 1e3,
         f"rebuild_ms={results['ingest_delta']['rebuild_ms']:.1f} "
         f"parity={results['ingest_delta']['loss_parity_rel']:.2e}")

    # ---- compensated-f32 parity vs the f64 oracle (fresh runs)
    results["compensated"] = _compensated_section(codes, w, w * yv,
                                                  w * yv * yv, B, y)

    # selection state alongside the numbers (what auto would pick here)
    results["selection"] = {op: s["selected"]
                            for op, s in ops.snapshot().items()}
    _merge_save(results)
    return results


def _compensated_section(codes, w, wy, wy2, B, y) -> dict:
    """The parity rows ``check_bench_regression ops`` holds to 1e-6: the
    two-float paths of ``sat_moments`` (xla) and ``hist_split`` (pallas
    partials, xla chunked) against the f64 oracle."""
    sec: dict = {}
    want = ops.sat_moments(y, backend="numpy")
    t0 = time.perf_counter()
    got = ops.sat_moments(y, backend="xla", config={"compensated": True})
    comp_us = (time.perf_counter() - t0) * 1e6
    plain = ops.sat_moments(y, backend="xla", config={"compensated": False})
    sec["sat_moments"] = {
        "rel_err": scaled_rel_err(got, want),
        "plain_rel_err": scaled_rel_err(plain, want),
        "us": comp_us, "backend": "xla", "shape": list(y.shape)}

    wanth = ops.hist_split(codes, w, wy, wy2, B, backend="numpy")
    t0 = time.perf_counter()
    goth = ops.hist_split(codes, w, wy, wy2, B, backend="pallas",
                          config={"compensated": True})
    hist_us = (time.perf_counter() - t0) * 1e6
    gotx = ops.hist_split(codes, w, wy, wy2, B, backend="xla",
                          config={"compensated": True})
    sec["hist_split"] = {
        "rel_err": scaled_rel_err(goth, wanth),
        "xla_chunked_rel_err": scaled_rel_err(gotx, wanth),
        "us": hist_us, "backend": "pallas", "variant": "partials",
        "shape": [int(codes.shape[0]), int(codes.shape[1]), int(B)]}
    for op_name, row in sec.items():
        emit(f"ops/compensated_{op_name}", row["us"],
             f"rel_err={row['rel_err']:.2e}")
    return sec


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    args = ap.parse_args()
    run(fast=args.fast)
