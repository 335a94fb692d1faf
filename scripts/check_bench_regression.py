#!/usr/bin/env python
"""Bench regression gate: fresh results vs committed baselines.

Compares the wall-clock rows of a fresh ``benchmarks/results/*.json`` run
against the committed snapshots in ``benchmarks/baselines/`` and fails when
a gated row regressed by more than ``--factor`` (default 1.25 = +25%).

Gated rows (lower is better, all wall-clock):

  bench_ops.json       <op>.numpy.us_per_call   per canonical op
  bench_service.json   <mode>.register_seconds  per wire mode present
  bench_service.json   cluster.register_seconds + cluster.loss.p50_ms
                       (the ``cluster`` suite: loadgen over the distributed
                       plane — register includes the band scatter, loss p50
                       rides gather/compose-built coresets)
  bench_service.json   delta_mix.reanchor_hit_p50_ms +
                       stream.stream_compress_p50_ms (the ``stream`` suite:
                       builds served off a re-anchored cache entry, and the
                       v2 chunked compress transfer)
  bench_service.json   overload.rejected_rtt_p50_ms (the ``qos`` suite:
                       the HTTP round-trip of a 503 admission rejection)

Absolute rows (gated against a fixed limit, not a baseline ratio):

  bench_ops.json       compensated.{sat_moments,hist_split}.rel_err <= 1e-6
  (the ``ops`` suite) — the compensated-f32 paths must hold their parity
  vs the f64 oracle
  bench_service.json   <mode>.tracing.overhead_frac < 0.05 — request
  tracing must cost under 5% on the loss-query p50 (the A/B probe in
  bench_service measures tracing-on vs tracing-off on the same server)
  delta_mix.post_reanchor_miss_rate <= 0.01 — a disjoint-delta re-anchor
  must leave subsequent builds as pure cache hits;
  stream.encode_peak_ratio <= 0.5 — the v2 chunked encoder's peak memory
  must stay a small fraction of the buffered v1 body's;
  overload.admit_decision_us < 50 — the admission decision (admit +
  release) sits on every admitted request's path and must stay microscopic

Noise handling — micro-timings on shared boxes swing well past 25% run to
run, so a single sample proves nothing:

  * rows below an absolute floor are skipped (scheduler noise, not signal);
  * on failure the gate RE-RUNS the suite's bench (up to ``--retries``
    times) and compares the per-row MINIMUM across runs — a true
    regression survives every re-measure, a load spike does not;
  * ``BENCH_REGRESSION_FACTOR`` loosens the factor for CI runners whose
    hardware differs from the baseline machine.

``--update`` refreshes the baselines from the fresh results instead of
comparing (run it after an intentional perf change, commit the diff).

Run:  python scripts/check_bench_regression.py [ops|service|all] [--update]
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
RESULTS = ROOT / "benchmarks" / "results"
BASELINES = ROOT / "benchmarks" / "baselines"

# (file, row path resolver, floor) — a resolver yields (row name, value)
_OPS_FLOOR_US = 500.0      # numpy per-call timings under 0.5 ms are noise
_SVC_FLOOR_S = 0.005       # registration under 5 ms likewise
_LOSS_FLOOR_MS = 1.0       # loss p50s under 1 ms are scheduler noise
_TRACING_OVERHEAD_MAX = 0.05   # spans must stay under 5% of loss-query p50


def _ops_rows(doc: dict):
    for op, backends in doc.items():
        if isinstance(backends, dict) and isinstance(
                backends.get("numpy"), dict) and \
                "us_per_call" in backends["numpy"]:
            yield f"{op}.numpy.us_per_call", float(
                backends["numpy"]["us_per_call"]), _OPS_FLOOR_US


def _service_rows(doc: dict):
    for mode, res in doc.items():
        if mode == "cluster":
            continue        # gated by the dedicated cluster suite
        if isinstance(res, dict) and "register_seconds" in res:
            yield f"{mode}.register_seconds", float(
                res["register_seconds"]), _SVC_FLOOR_S


def _cluster_rows(doc: dict):
    """Distributed-plane rows only: the ``cluster`` mode entry written by
    ``bench_service.py --cluster``.  Register includes the band scatter to
    3 workers; loss p50 is the query path over gather-composed coresets."""
    res = doc.get("cluster")
    if not isinstance(res, dict):
        return
    if "register_seconds" in res:
        yield ("cluster.register_seconds", float(res["register_seconds"]),
               _SVC_FLOOR_S)
    loss = res.get("loss")
    if isinstance(loss, dict) and "p50_ms" in loss:
        yield "cluster.loss.p50_ms", float(loss["p50_ms"]), _LOSS_FLOOR_MS


def _service_abs_rows(doc: dict):
    """(row, value, absolute limit): rows gated by a fixed ceiling rather
    than a baseline ratio.  Tracing overhead is a *fraction* already, so a
    relative factor against a near-zero baseline would be meaningless."""
    for mode, res in doc.items():
        tracing = res.get("tracing") if isinstance(res, dict) else None
        if isinstance(tracing, dict) and "overhead_frac" in tracing:
            yield (f"{mode}.tracing.overhead_frac",
                   float(tracing["overhead_frac"]), _TRACING_OVERHEAD_MAX)


_PARITY_RTOL = 1e-6            # compensated-f32 parity vs f64 oracle


def _ops_abs_rows(doc: dict):
    """Fixed ceilings: the compensated-f32 parity of the integral-image and
    histogram paths against the f64 oracle (scaled relative error)."""
    sec = doc.get("compensated")
    if not isinstance(sec, dict):
        return
    for op in ("sat_moments", "hist_split"):
        row = sec.get(op)
        if isinstance(row, dict) and "rel_err" in row:
            yield (f"compensated.{op}.rel_err", float(row["rel_err"]),
                   _PARITY_RTOL)


_MISS_RATE_MAX = 0.01          # post-re-anchor builds must be cache hits
_STREAM_PEAK_RATIO_MAX = 0.5   # v2 encode peak vs v1 buffered encode peak
_RATE_FLOOR_MS = 1.0           # sub-ms p50s are scheduler noise


def _stream_rows(doc: dict):
    """Relative rows of the ``delta_mix`` and ``stream`` mode entries
    written by ``bench_service.py --delta-mix`` / ``--stream``: the build
    latency served off a re-anchored entry, and the chunked-compress p50."""
    dm = doc.get("delta_mix")
    if isinstance(dm, dict) and dm.get("reanchor_hit_p50_ms") is not None:
        yield ("delta_mix.reanchor_hit_p50_ms",
               float(dm["reanchor_hit_p50_ms"]), _RATE_FLOOR_MS)
    st = doc.get("stream")
    if isinstance(st, dict) and "stream_compress_p50_ms" in st:
        yield ("stream.stream_compress_p50_ms",
               float(st["stream_compress_p50_ms"]), _RATE_FLOOR_MS)


def _stream_abs_rows(doc: dict):
    """Fixed ceilings: a disjoint-delta re-anchor must leave subsequent
    builds as pure cache hits, and the v2 encoder's peak memory must stay
    a small fraction of the buffered v1 body's."""
    dm = doc.get("delta_mix")
    if isinstance(dm, dict) and "post_reanchor_miss_rate" in dm:
        yield ("delta_mix.post_reanchor_miss_rate",
               float(dm["post_reanchor_miss_rate"]), _MISS_RATE_MAX)
    st = doc.get("stream")
    if isinstance(st, dict) and "encode_peak_ratio" in st:
        yield ("stream.encode_peak_ratio",
               float(st["encode_peak_ratio"]), _STREAM_PEAK_RATIO_MAX)


_ADMIT_DECISION_MAX_US = 50.0  # admit+release cycle every request pays
_REJECT_FLOOR_MS = 0.2         # 503 RTTs are small; sub-0.2ms is noise


def _qos_rows(doc: dict):
    """Relative rows of the ``overload`` mode entry written by
    ``bench_service.py --overload``: the HTTP round-trip of a 503
    rejection — saying no must stay cheap or overload pushback melts the
    server it is protecting."""
    ov = doc.get("overload")
    if isinstance(ov, dict) and ov.get("rejected_rtt_p50_ms") is not None:
        yield ("overload.rejected_rtt_p50_ms",
               float(ov["rejected_rtt_p50_ms"]), _REJECT_FLOOR_MS)


def _qos_abs_rows(doc: dict):
    """Fixed ceiling: the in-process admission decision (admit + release)
    must stay under 50us — it sits on EVERY admitted request's path."""
    ov = doc.get("overload")
    if isinstance(ov, dict) and "admit_decision_us" in ov:
        yield ("overload.admit_decision_us",
               float(ov["admit_decision_us"]), _ADMIT_DECISION_MAX_US)


_SUITES = {
    "ops": ("bench_ops.json", _ops_rows,
            [[sys.executable, "-m", "benchmarks.bench_ops", "--fast"]],
            _ops_abs_rows),
    "service": ("bench_service.json", _service_rows,
                [[sys.executable, "benchmarks/bench_service.py", "--smoke",
                  "--encoding", "json"],
                 [sys.executable, "benchmarks/bench_service.py", "--smoke",
                  "--encoding", "binary"]],
                _service_abs_rows),
    "cluster": ("bench_service.json", _cluster_rows,
                [[sys.executable, "benchmarks/bench_service.py", "--smoke",
                  "--cluster"]],
                None),
    "stream": ("bench_service.json", _stream_rows,
               [[sys.executable, "benchmarks/bench_service.py", "--smoke",
                 "--delta-mix", "0.3"],
                [sys.executable, "benchmarks/bench_service.py", "--smoke",
                 "--stream"]],
               _stream_abs_rows),
    "qos": ("bench_service.json", _qos_rows,
            [[sys.executable, "benchmarks/bench_service.py", "--smoke",
              "--overload"]],
            _qos_abs_rows),
}


def _rerun(suite: str) -> None:
    env = dict(os.environ)
    env["PYTHONPATH"] = "src" + (os.pathsep + env["PYTHONPATH"]
                                 if env.get("PYTHONPATH") else "")
    for cmd in _SUITES[suite][2]:
        subprocess.run(cmd, cwd=ROOT, env=env, check=True,
                       stdout=subprocess.DEVNULL)


def _check_suite(suite: str, factor: float, best: dict) -> list[str]:
    """One comparison pass; ``best`` accumulates the per-row minimum over
    every fresh run seen so far."""
    fname, rows_of, _, abs_rows_of = _SUITES[suite]
    fresh = json.loads((RESULTS / fname).read_text())
    for name, val, _ in rows_of(fresh):
        best[name] = min(val, best.get(name, val))
    base_rows = dict(
        (name, (val, floor)) for name, val, floor
        in rows_of(json.loads((BASELINES / fname).read_text())))
    failures, compared = [], 0
    for name, val, floor in rows_of(fresh):
        if name not in base_rows:
            continue
        base_val, _ = base_rows[name]
        val = best[name]
        if base_val < floor or val < floor:
            continue        # below the noise floor on either side
        compared += 1
        ratio = val / base_val
        status = "FAIL" if ratio > factor else "ok"
        print(f"[bench_regression] {suite}:{name} baseline={base_val:.1f}"
              f" best-fresh={val:.1f} ({ratio:.2f}x, allowed {factor:.2f}x)"
              f" {status}")
        if ratio > factor:
            failures.append(f"{suite}:{name} {ratio:.2f}x")
    if compared == 0:
        print(f"[bench_regression] WARN {suite}: no gated rows above "
              f"the noise floor — gate vacuous")
    if abs_rows_of is not None:
        # absolute rows: same best-of-remeasures discipline, fixed ceiling
        for name, val, limit in abs_rows_of(fresh):
            best[name] = min(val, best.get(name, val))
            val = best[name]
            status = "FAIL" if val > limit else "ok"
            print(f"[bench_regression] {suite}:{name} best-fresh={val:.4f} "
                  f"(absolute limit {limit}) {status}")
            if val > limit:
                failures.append(f"{suite}:{name} {val:.3f} > {limit}")
    return failures


def check(which: str, factor: float, update: bool, retries: int) -> int:
    suites = list(_SUITES) if which == "all" else [which]
    failed = []
    for suite in suites:
        fname = _SUITES[suite][0]
        fresh_p = RESULTS / fname
        base_p = BASELINES / fname
        if not fresh_p.exists():
            print(f"[bench_regression] SKIP {suite}: no fresh {fresh_p}")
            continue
        if update:
            BASELINES.mkdir(parents=True, exist_ok=True)
            base_p.write_text(fresh_p.read_text())
            print(f"[bench_regression] baseline updated: {base_p}")
            continue
        if not base_p.exists():
            print(f"[bench_regression] SKIP {suite}: no baseline {base_p} "
                  f"(run with --update to create it)")
            continue
        best: dict = {}
        failures = _check_suite(suite, factor, best)
        attempt = 0
        while failures and attempt < retries:
            attempt += 1
            print(f"[bench_regression] {suite}: {len(failures)} row(s) over "
                  f"budget — re-measuring ({attempt}/{retries}) to rule out "
                  f"machine load")
            _rerun(suite)
            failures = _check_suite(suite, factor, best)
        failed.extend(failures)
    if failed:
        print(f"[bench_regression] FAIL: {len(failed)} row(s) regressed "
              f"> {factor:.2f}x across every re-measure: {failed}")
        return 1
    if not update:
        print("[bench_regression] PASS")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("which", nargs="?", default="all",
                    choices=("ops", "service", "cluster",
                             "stream", "qos", "all"))
    ap.add_argument("--update", action="store_true",
                    help="refresh baselines from fresh results")
    ap.add_argument("--factor", type=float,
                    default=float(os.environ.get("BENCH_REGRESSION_FACTOR",
                                                 "1.25")),
                    help="allowed slowdown (default 1.25 = +25%%)")
    ap.add_argument("--retries", type=int, default=2,
                    help="re-measures before a regression is declared real")
    args = ap.parse_args()
    return check(args.which, args.factor, args.update, args.retries)


if __name__ == "__main__":
    sys.exit(main())
