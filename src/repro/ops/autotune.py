"""Kernel autotuning + compensated-precision promotion for ``repro.ops``.

The dispatch heuristics in ``registry.py`` are static: capability (TPU ->
pallas) and a per-op size crossover, with the precision-critical ops pinned
to the f64 numpy oracle at every size.  On real hardware neither rule is
sharp — the best Pallas tile shape depends on the device generation and the
problem size, the XLA ``hist_split`` has several algorithmically different
lowerings, and the f64 pin forfeits the accelerator entirely even when a
compensated-summation f32 path would be provably accurate enough.  This
module closes all three gaps:

  * a **search**: per (op, backend) configuration space — Pallas tile
    sizes / grid shapes, XLA variant choices, compensated-summation on/off
    — measured against the numpy oracle on representative problems;
  * a **persisted cache**: ``.autotune.json`` in the checkout (override
    with ``REPRO_AUTOTUNE_CACHE``), versioned by a fingerprint of the kernel
    sources so stale entries never outlive the code they measured; corrupt
    or mismatched caches are ignored, never fatal;
  * a **dispatch consult**: ``registry.select_backend`` asks
    :func:`tuned_backend` before falling back to the static heuristics, and
    each accelerator backend asks :func:`plan` for its tuned configuration
    (tile sizes, variant, compensated flag) at call time.  A cold cache
    reproduces today's behaviour exactly.

Precision promotion: a tuning entry for a precision-pinned op
(``XLA_SIZE_THRESHOLD[op] is None``) may carry a *parity certificate* — the
measured scaled relative error of the compensated-f32 path against the f64
oracle.  Only entries whose certificate passes :data:`PARITY_RTOL` can lift
the pin, and ``REPRO_OPS_PRECISION=f64`` disables promotion outright (the
pin is both the cold-cache default and the escape hatch).

CLI::

    python -m repro.ops.autotune [--ops OP,OP] [--budget quick|full]
                                 [--cache PATH] [--json]
"""
from __future__ import annotations

import argparse
import functools
import hashlib
import json
import os
import pathlib
import platform
import sys
import threading
import time

import numpy as np

__all__ = [
    "CACHE_ENV_VAR", "DISABLE_ENV_VAR", "PRECISION_ENV_VAR", "PARITY_RTOL",
    "TuneCache", "cache_path", "kernel_fingerprint", "device_kind",
    "precision_mode", "get_cache", "reset_cache", "plan", "tuned_backend",
    "tune_op", "tune_all", "counters_snapshot", "snapshot", "main",
]

CACHE_ENV_VAR = "REPRO_AUTOTUNE_CACHE"
DISABLE_ENV_VAR = "REPRO_AUTOTUNE"          # "0"/"off" disables consultation
PRECISION_ENV_VAR = "REPRO_OPS_PRECISION"   # f64 | compensated | fast
SCHEMA_VERSION = 1
PARITY_RTOL = 1e-6     # compensated-f32 certificate bound vs the f64 oracle

# ----------------------------------------------------------- search spaces
# Each op/backend maps to the list of configurations the tuner measures.
# Config keys are interpreted by the backend implementations (backends.py):
#   compensated  — two-float (TwoSum) summation, f64-combined on the host
#   tile / tile_p / tile_t / tile_b — Pallas block shapes
#   variant      — algorithmically distinct lowering of the same op
# A TPU host selects pallas before any certificate is consulted, so the
# pallas integral-image spaces hold compensated configs only: a tuned
# plain-f32 winner would otherwise be served there.
SEARCH_SPACE: dict[str, dict[str, list[dict]]] = {
    "sat_moments": {
        "xla": [{"compensated": False}, {"compensated": True}],
        "pallas": [{"tile": t, "compensated": True} for t in (128, 256, 512)],
    },
    "delta_sat": {
        "xla": [{"compensated": False}, {"compensated": True}],
        "pallas": [{"tile": t, "compensated": True} for t in (128, 256, 512)],
    },
    "hist_split": {
        "xla": [{"variant": "vmap", "compensated": False},
                {"variant": "flat", "compensated": False},
                {"variant": "chunked", "compensated": True}],
        "pallas": [{"variant": "fused", "tile_p": t}
                   for t in (512, 1024, 2048, 4096, 8192)]
                  + [{"variant": "partials", "compensated": True, "tile_p": t}
                     for t in (1024, 2048, 4096, 8192)]
                  + [{"variant": "legacy", "tile_p": 512}],
    },
    "fitting_loss": {
        "xla": [{}],
        "pallas": [{"tile_b": t} for t in (256, 512, 1024)],
    },
    "fitting_loss_batched": {
        "xla": [{}],
        "pallas": [{"tile_t": tt, "tile_b": tb}
                   for tt in (4, 8, 16) for tb in (256, 512)],
    },
    "streaming_compress": {
        "xla": [{"compensated": False}, {"compensated": True}],
        "pallas": [{"tile": t, "compensated": True} for t in (128, 256)],
    },
}

# Canonical large-bucket problem shapes: shared by ``tune_all`` and the
# ``autotune`` section of bench_ops so the tuned entries land in exactly the
# buckets the bench (and the regression gate) reads back.
LARGE_SHAPES = {
    "sat_moments": {"n": 384, "m": 384},
    "delta_sat": {"band": 64, "m": 2048},
    "hist_split": {"P": 120_000, "F": 8, "B": 256},
    "fitting_loss_batched": {"n": 320, "m": 240, "k": 8, "T": 64},
}

_COUNTERS = {"cache_hit": 0, "cache_miss": 0, "tune_runs": 0,
             "promoted_f32": 0, "tuned_dispatch": 0, "cache_load_errors": 0}


def _count(name: str, by: int = 1) -> None:
    # deliberately lock-free: these sit on the dispatch hot path, and a
    # rare lost increment in telemetry beats a lock acquire per dispatch
    _COUNTERS[name] = _COUNTERS.get(name, 0) + by


def counters_snapshot() -> dict:
    return dict(_COUNTERS)


def _enabled() -> bool:
    return os.environ.get(DISABLE_ENV_VAR, "").strip().lower() not in (
        "0", "off", "false", "no")


def precision_mode() -> str:
    """``f64`` (never lift a pin), ``compensated`` (lift only with a parity
    certificate — the default), or ``fast`` (plain-f32 promotion allowed,
    the documented TPU trade-off)."""
    mode = os.environ.get(PRECISION_ENV_VAR, "").strip().lower()
    return mode if mode in ("f64", "compensated", "fast") else "compensated"


@functools.cache
def kernel_fingerprint() -> str:
    """Hash of the kernel/backend sources + the search space: a cache entry
    measured against different code is stale and must not be consulted."""
    here = pathlib.Path(__file__).resolve()
    kernels = here.parents[1] / "kernels"
    h = hashlib.sha256()
    for p in sorted((here.parent / "backends.py",
                     *kernels.glob("*/kernel.py"), *kernels.glob("*/ref.py"))):
        try:
            h.update(p.read_bytes())
        except OSError:
            pass
    h.update(repr(sorted(SEARCH_SPACE.items())).encode())
    h.update(str(SCHEMA_VERSION).encode())
    return h.hexdigest()[:12]


@functools.cache
def device_kind() -> str:
    """Coarse accelerator class ("cpu"/"tpu"/"gpu") — cache entries do not
    transfer across device kinds.  Forces XLA client init, like the
    registry's capability rule; cached for the same reason."""
    import jax
    return jax.default_backend()


def host_fingerprint() -> str:
    """Provenance string for bench rows: which machine produced a number."""
    return (f"{platform.system()}-{platform.machine()}"
            f"-py{platform.python_version()}-cpus{os.cpu_count()}")


from repro.obs.profile import shape_bucket  # noqa: E402  (lightweight, and
# already imported by registry.py — kept module-level so the per-dispatch
# consult does not pay a sys.modules lookup)


def cache_path() -> pathlib.Path:
    env = os.environ.get(CACHE_ENV_VAR, "").strip()
    if env:
        return pathlib.Path(env).expanduser()
    return pathlib.Path(__file__).resolve().parents[3] / ".autotune.json"


class TuneCache:
    """The persisted tuning table: (op, backend, device, bucket) -> entry.

    An entry records the winning config, its measured wall time, the numpy
    oracle's wall time on the same problem, and (for precision-pinned ops)
    the compensated path's measured scaled relative error — the parity
    certificate promotion is gated on.
    """

    def __init__(self, path: pathlib.Path | None = None):
        self.path = path or cache_path()
        self.entries: dict[str, dict] = {}
        self.loaded_from_disk = False

    @staticmethod
    def key(op: str, backend: str, device: str, bucket: str) -> str:
        return f"{op}|{backend}|{device}|{bucket}"

    def load(self) -> "TuneCache":
        """Tolerant load: corrupt JSON, wrong schema version, or a kernel-
        fingerprint mismatch all yield an empty cache (heuristics apply) —
        a bad cache file must never take down dispatch."""
        self.entries = {}
        self.loaded_from_disk = False
        try:
            doc = json.loads(self.path.read_text())
        except (OSError, ValueError):
            if self.path.exists():
                _count("cache_load_errors")
            return self
        if not isinstance(doc, dict) or doc.get("version") != SCHEMA_VERSION:
            _count("cache_load_errors")
            return self
        if doc.get("fingerprint") != kernel_fingerprint():
            # stale-by-construction: the kernels changed under the entries
            _count("cache_load_errors")
            return self
        entries = doc.get("entries")
        if isinstance(entries, dict):
            self.entries = {k: v for k, v in entries.items()
                            if isinstance(v, dict) and "config" in v}
            self.loaded_from_disk = True
        return self

    def save(self) -> pathlib.Path:
        """Atomic write (tmp + rename): a concurrent reader never sees a
        torn file, which load() would otherwise discard as corrupt."""
        self.path.parent.mkdir(parents=True, exist_ok=True)
        doc = {"version": SCHEMA_VERSION, "fingerprint": kernel_fingerprint(),
               "host": host_fingerprint(), "entries": self.entries}
        tmp = self.path.with_suffix(f".tmp.{os.getpid()}")
        tmp.write_text(json.dumps(doc, indent=1, default=float))
        tmp.replace(self.path)
        return self.path

    def put(self, op: str, backend: str, bucket: str, entry: dict) -> None:
        self.entries[self.key(op, backend, device_kind(), bucket)] = entry
        _DECISIONS.clear()     # new measurements invalidate memoized picks

    def get(self, op: str, backend: str, bucket: str) -> dict | None:
        return self.entries.get(self.key(op, backend, device_kind(), bucket))

    def for_op(self, op: str, bucket: str) -> dict[str, dict]:
        """backend -> entry for every backend tuned at this bucket."""
        out = {}
        for backend in ("xla", "pallas"):
            e = self.get(op, backend, bucket)
            if e is not None:
                out[backend] = e
        return out


_CACHE: TuneCache | None = None
_CACHE_KEY: str | None = None     # value of $REPRO_AUTOTUNE_CACHE at load
_CACHE_LOCK = threading.Lock()


_DECISIONS: dict[tuple, str | None] = {}   # (op, bucket, mode) -> backend
_MISSING = object()


@functools.cache
def _pinned_ops() -> frozenset:
    """Ops whose XLA_SIZE_THRESHOLD is None (precision-pinned) — snapshotted
    once; the threshold table is a module constant."""
    from . import registry
    return frozenset(op for op, thr in registry.XLA_SIZE_THRESHOLD.items()
                     if thr is None)


def get_cache() -> TuneCache:
    """The in-process cache, reloaded when the env var is repointed.  The
    staleness check is one environ lookup + string compare: this sits on
    the dispatch hot path (per CART node for ``hist_split``)."""
    global _CACHE, _CACHE_KEY
    key = os.environ.get(CACHE_ENV_VAR, "")
    if _CACHE is None or _CACHE_KEY != key:
        with _CACHE_LOCK:
            if _CACHE is None or _CACHE_KEY != key:
                _CACHE = TuneCache().load()
                _CACHE_KEY = key
                _DECISIONS.clear()
    return _CACHE


def reset_cache() -> None:
    """Drop the in-process cache so the next consult re-reads disk/env —
    tests repoint ``REPRO_AUTOTUNE_CACHE`` (or tune in-process) and call
    this."""
    global _CACHE
    with _CACHE_LOCK:
        _CACHE = None
        _DECISIONS.clear()


# ------------------------------------------------------- dispatch consults
def plan(op: str, backend: str, size: int | None) -> dict:
    """The tuned configuration an accelerator backend should run with at
    this problem size — ``{}`` on a cold miss (the backend's built-in
    defaults apply).  Called by backends.py on every accelerator dispatch;
    one dict lookup when warm."""
    if backend == "numpy" or not _enabled():
        return {}
    entry = get_cache().get(op, backend, shape_bucket(size))
    if entry is None:
        _count("cache_miss")
        return {}
    _count("cache_hit")
    return dict(entry.get("config") or {})


def tuned_backend(op: str, size: int | None) -> str | None:
    """The backend the tuning cache recommends for ``op`` at ``size``, or
    ``None`` when the static heuristics should decide (cold cache, no
    winning entry, or a precision pin with no passing certificate).

    On the hot path (warm cache) this is a memoized dict lookup — the full
    decision below runs once per (op, bucket, precision mode)."""
    if size is None or not _enabled():
        return None
    cache = get_cache()
    if not cache.entries:
        return None
    pinned = op in _pinned_ops()
    mode = precision_mode()
    if pinned and mode == "f64":
        return None          # the escape hatch: never lift the pin
    key = (op, shape_bucket(size), mode)
    best_name = _DECISIONS.get(key, _MISSING)
    if best_name is _MISSING:
        best_name = _DECISIONS[key] = _decide(cache, op, key[1], pinned, mode)
    if best_name is not None:
        _count("tuned_dispatch")
        if pinned:
            _count("promoted_f32")
    return best_name


def _decide(cache: TuneCache, op: str, bucket: str, pinned: bool,
            mode: str) -> str | None:
    best_name, best_us = None, None
    for backend, entry in cache.for_op(op, bucket).items():
        if backend == "pallas" and device_kind() != "tpu":
            # interpret-mode Pallas is a correctness path, never an auto
            # selection — a quick-budget timing fluke must not promote it
            continue
        us, numpy_us = entry.get("us"), entry.get("numpy_us")
        if not us or not numpy_us or us >= numpy_us:
            continue         # the oracle won at tune time: nothing to gain
        if pinned and mode == "compensated":
            cfg = entry.get("config") or {}
            rel = entry.get("rel_err")
            if not cfg.get("compensated") or rel is None or rel > PARITY_RTOL:
                continue     # no parity certificate: the pin holds
        if best_us is None or us < best_us:
            best_name, best_us = backend, us
    return best_name


def snapshot() -> dict:
    """Cache + counter state for ``/v1/stats`` and bench provenance."""
    cache = get_cache()
    return {"enabled": _enabled(), "cache_path": str(cache.path),
            "cache_loaded": cache.loaded_from_disk,
            "entries": len(cache.entries),
            "fingerprint": kernel_fingerprint(),
            "precision_mode": precision_mode(),
            "counters": counters_snapshot()}


# ------------------------------------------------------------------ tuning
def _scaled_rel_err(got, want) -> float:
    """max |a-b| scaled by the output's own magnitude (floor 1): the error
    measure the S2 - S1^2/S0 identity actually feels.  Elementwise relative
    error is meaningless here — integral images pass through near-zero
    entries whose denominators amplify benign f32 rounding."""
    got = np.asarray(got, np.float64).ravel()
    want = np.asarray(want, np.float64).ravel()
    scale = max(float(np.max(np.abs(want))) if want.size else 0.0, 1.0)
    return float(np.max(np.abs(got - want))) / scale if got.size else 0.0


def _time_call(fn, repeat: int) -> tuple[float, object]:
    fn()                                    # warmup / compile
    t0 = time.perf_counter()
    out = None
    for _ in range(repeat):
        out = fn()
    return (time.perf_counter() - t0) / repeat, out


def _problem(op: str, rng: np.random.Generator, fast: bool) -> tuple:
    """(call_factory, size) for a representative large-bucket problem.
    ``call_factory(backend, config)`` returns a zero-arg callable running
    the op end to end through the public wrapper (so the timing includes
    the same host<->device traffic dispatch pays)."""
    from repro import ops
    if op == "sat_moments":
        n = 256 if fast else LARGE_SHAPES[op]["n"]
        y = rng.normal(size=(n, n))
        return (lambda backend, cfg:
                lambda: ops.sat_moments(y, backend=backend, config=cfg)), \
            3 * y.size
    if op == "delta_sat":
        shp = LARGE_SHAPES[op]
        b, m = (32, 512) if fast else (shp["band"], shp["m"])
        y = rng.normal(size=(b + 1, m))
        carry = ops.sat_moments(y[:1], backend="numpy")[:, 0, :]
        tail = y[1:]
        return (lambda backend, cfg:
                lambda: ops.delta_sat(carry, tail, backend=backend,
                                      config=cfg)), 3 * tail.size
    if op == "hist_split":
        shp = LARGE_SHAPES[op]
        P, F, B = (40_000, 4, 64) if fast else (shp["P"], shp["F"], shp["B"])
        codes = rng.integers(0, B, size=(P, F)).astype(np.uint8)
        w = rng.uniform(0.5, 1.5, P)
        yv = rng.normal(size=P)
        wy, wy2 = w * yv, w * yv * yv
        return (lambda backend, cfg:
                lambda: ops.hist_split(codes, w, wy, wy2, B, backend=backend,
                                       config=cfg)), codes.size
    if op == "fitting_loss_batched":
        from repro.core import random_tree_segmentation, signal_coreset
        from repro.data import piecewise_signal
        shp = LARGE_SHAPES[op]
        n, m, k, T = ((96, 80, 6, 16) if fast else
                      (shp["n"], shp["m"], shp["k"], shp["T"]))
        y = piecewise_signal(n, m, k, noise=0.2, seed=3)
        cs = signal_coreset(y, k, 0.25)
        segs = [random_tree_segmentation(n, m, k, rng) for _ in range(T)]
        sr = np.stack([s.rects for s in segs]).astype(np.float64)
        sl = np.stack([s.labels for s in segs])
        return (lambda backend, cfg:
                lambda: ops.fitting_loss_batched(cs, sr, sl, backend=backend,
                                                 config=cfg)), \
            ops.fitting_loss_batched_size(cs, sr)
    raise ValueError(f"no tuning problem defined for op {op!r}")


TUNABLE_OPS = ("sat_moments", "delta_sat", "hist_split",
               "fitting_loss_batched")


def tune_op(op: str, *, budget: str = "quick", seed: int = 0,
            verbose: bool = False) -> dict[str, dict]:
    """Measure every configured (backend, config) for ``op`` on its
    representative problem and record the per-backend winner (with the
    numpy-oracle baseline and, for compensated configs, the parity
    certificate) into the cache.  Returns backend -> winning entry."""
    _count("tune_runs")
    rng = np.random.default_rng(seed)
    fast = budget == "quick"
    repeat = 2 if fast else 5
    call_of, size = _problem(op, rng, fast)
    bucket = shape_bucket(size)
    numpy_us, want = _time_call(call_of("numpy", {}), repeat)
    numpy_us *= 1e6
    cache = get_cache()
    winners: dict[str, dict] = {}
    for backend, configs in SEARCH_SPACE.get(op, {}).items():
        best = None
        for cfg in configs:
            try:
                us, got = _time_call(call_of(backend, cfg), repeat)
            except Exception as exc:  # noqa: BLE001 — a config that cannot
                # run on this device (VMEM overflow, unsupported lowering)
                # is a lost candidate, not a failed tune
                if verbose:
                    print(f"[autotune] {op}/{backend} {cfg}: "
                          f"{type(exc).__name__}: {exc}", file=sys.stderr)
                continue
            rel = _scaled_rel_err(_comparable(op, got), _comparable(op, want))
            entry = {"config": cfg, "us": us * 1e6, "numpy_us": numpy_us,
                     "rel_err": rel, "size": int(size), "bucket": bucket,
                     "tuned_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
                     "host": host_fingerprint()}
            if verbose:
                print(f"[autotune] {op}/{backend} {cfg}: "
                      f"{entry['us']:.0f}us (numpy {numpy_us:.0f}us) "
                      f"rel_err={rel:.2e}")
            if best is None or entry["us"] < best["us"]:
                best = entry
        if best is not None:
            cache.put(op, backend, bucket, best)
            winners[backend] = best
    return winners


def _comparable(op: str, out):
    """Project an op's output to the array the parity certificate compares
    (streaming_compress returns coreset objects; everything else arrays)."""
    if op == "streaming_compress":
        return np.concatenate([np.sort(np.asarray(c.moments), axis=None)
                               for c in out])
    return out


def tune_all(ops_list=None, *, budget: str = "quick", seed: int = 0,
             verbose: bool = False, save: bool = True) -> dict:
    """Tune every (or the named) tunable op and persist the cache."""
    results = {}
    for op in (ops_list or TUNABLE_OPS):
        if op not in TUNABLE_OPS:
            raise ValueError(f"op {op!r} is not tunable; "
                             f"tunable ops: {TUNABLE_OPS}")
        results[op] = tune_op(op, budget=budget, seed=seed, verbose=verbose)
    if save:
        get_cache().save()
    return results


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.ops.autotune",
        description="Populate the kernel tuning cache for this host.")
    ap.add_argument("--ops", default=None,
                    help=f"comma list of ops to tune (default: all of "
                         f"{','.join(TUNABLE_OPS)})")
    ap.add_argument("--budget", choices=("quick", "full"), default="quick")
    ap.add_argument("--cache", default=None,
                    help=f"cache file (default: ${CACHE_ENV_VAR} or "
                         f".autotune.json in the checkout)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", action="store_true",
                    help="print the tuned entries as JSON on stdout")
    args = ap.parse_args(argv)
    if args.cache:
        os.environ[CACHE_ENV_VAR] = args.cache
        reset_cache()
    ops_list = ([s.strip() for s in args.ops.split(",") if s.strip()]
                if args.ops else None)
    results = tune_all(ops_list, budget=args.budget, seed=args.seed,
                       verbose=not args.json)
    path = get_cache().save()
    summary = {"cache": str(path), "fingerprint": kernel_fingerprint(),
               "device": device_kind(),
               "entries": len(get_cache().entries),
               "tuned": {op: {b: {"config": e["config"],
                                  "us": e["us"], "numpy_us": e["numpy_us"],
                                  "rel_err": e["rel_err"]}
                              for b, e in per.items()}
                         for op, per in results.items()}}
    if args.json:
        print(json.dumps(summary, indent=1, default=float))
    else:
        print(f"[autotune] wrote {len(get_cache().entries)} entr"
              f"{'y' if len(get_cache().entries) == 1 else 'ies'} to {path} "
              f"(fingerprint {kernel_fingerprint()}, device {device_kind()})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
