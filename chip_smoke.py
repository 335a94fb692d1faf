#!/usr/bin/env python
"""Bring-up check: the coreset service's served path on TPU chips.

  python chip_smoke.py              # one chip: the single-host served path
  python chip_smoke.py --chips 4    # four chips: the cluster serving plane

One chip (one process, which owns the chip):

  1. refuses to run unless JAX's first device is a TPU (no CPU fallback,
     no interpret-mode Pallas);
  2. kernel parity: each Pallas kernel (the integral images both
     compensated, as the chip runs them, and in plain f32) against the f64
     numpy oracle on small inputs;
  3. starts the real server (``serve_coresets``'s engine constructor and
     ``make_server``) on an ephemeral port and drives it over the binary
     SDK: registers a seeded 4096 x 4096 ``piecewise_signal`` shifted by a
     DC level of 100 (the regime where plain-f32 integral images break),
     builds (k=16, eps=0.2), answers 32 ``/v1/query/loss`` (the generating
     tree among them), one ``loss:batch`` of 16 trees, one ``fit`` of 3
     trees, a 64-row replace delta and a 64-row append delta each followed
     by a rebuild, and one ``compress``;
  4. checks every served loss against a plain numpy evaluation on the f64
     signal within the ``eps_eff`` the API returns, the batch against the
     sequential answers, the delta-then-build coreset against a one-shot
     build within the composed bound, the forest against one fitted on the
     f64 histogram oracle, and the compressed points' moments against the
     signal's.

Four chips: a launcher that never imports JAX starts a single-host
reference build (on chip 0, exiting before anything else starts), then one
``ShardWorker`` process per chip, each pinned to its own chip, a
coordinator that holds no chip, and an SDK client process that holds none
either.  It checks that every worker runs on a TPU and a distinct chip,
and that the gathered coreset is fingerprint-equal to the reference with
the same losses.

Earlier output lines report sizes, the backend each op ran on, compile
seconds and the largest error per check; the last line is one JSON object
``{"ok": true, "device": {"platform", "kind", "count"}}``.  Any failed
check exits non-zero without it.  The compile cache lives in
``$JAX_COMPILATION_CACHE_DIR`` when set, else in ``.jax_cache`` here.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import pathlib
import re
import socket
import subprocess
import sys
import threading
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
SRC = ROOT / "src"

N = M = 4096                 # the raster: 16.8M entries
K, EPS = 16, 0.2             # leaves per tree, coreset epsilon
NOISE, SEED = 0.15, 0
OFFSET = 100.0               # DC level of the signal and of every tree label
SCALE = 2.0                  # piecewise_signal's level scale
QUERIES, BATCH, FIT_TREES = 32, 16, 3
DELTA_ROWS, DELTA_TREES = 64, 8
CLUSTER_CHIPS, CLUSTER_QUERIES = 4, 8
BATCH_RTOL = 1e-5            # fused batch vs sequential: same f32 kernel
FIT_RTOL = 1e-2              # forest SSE vs the f64-histogram forest
MOMENT_RTOL = 1e-9           # compressed points' moments vs the signal's


def _log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


# ------------------------------------------------------------ shared pieces
def _signal(n: int, m: int):
    """The raster and its generating tree (rects, labels)."""
    from repro.core.segmentation import random_tree_segmentation
    from repro.data.signals import piecewise_signal
    y = piecewise_signal(n, m, K, noise=NOISE, scale=SCALE, seed=SEED)
    # piecewise_signal draws its tree first from the same seeded stream
    g = random_tree_segmentation(n, m, K, np.random.default_rng(SEED))
    return y + OFFSET, (g.rects, SCALE * g.labels + OFFSET)


def _trees(n: int, m: int, count: int, seed: int) -> list:
    from repro.core.segmentation import random_tree_segmentation
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(count):
        s = random_tree_segmentation(n, m, K, rng)
        out.append((s.rects, SCALE * s.labels + OFFSET))
    return out


def _direct_loss(y: np.ndarray, rects, labels) -> float:
    """The plain reference: sum of squared residuals over the f64 signal,
    leaf by leaf (half-open rects)."""
    total = 0.0
    for (r0, r1, c0, c1), lam in zip(np.asarray(rects, np.int64), labels):
        d = y[r0:r1, c0:c1] - lam
        total += float(np.einsum("ij,ij->", d, d))
    return total


class _Checks:
    """Collects failures and the largest relative error per check."""

    def __init__(self):
        self.failures: list[str] = []
        self.worst: dict[str, tuple[float, float]] = {}

    def rel(self, name: str, got: float, want: float, bound: float,
            scale: float | None = None) -> None:
        """|got - want| / scale (default |want|) must not exceed bound."""
        err = abs(got - want) / (scale if scale is not None else abs(want))
        prev = self.worst.get(name, (0.0, bound))[0]
        self.worst[name] = (max(prev, err), bound)
        if not err <= bound:
            self.failures.append(f"{name}: relative error {err:.3e} > "
                                 f"{bound:.3e} (got {got!r}, want {want!r})")

    def scaled(self, name: str, got, want, bound: float) -> None:
        """max |got - want| over max(max |want|, 1): the integral-image
        measure (``repro.kernels.sat2d.ref.scaled_rel_err``)."""
        got = np.asarray(got, np.float64)
        want = np.asarray(want, np.float64)
        i = int(np.argmax(np.abs(got - want)))
        self.rel(name, float(got.flat[i]), float(want.flat[i]), bound,
                 scale=max(float(np.max(np.abs(want))), 1.0))

    def true(self, name: str, ok: bool, detail: str) -> None:
        if not ok:
            self.failures.append(f"{name}: {detail}")

    def report(self) -> None:
        for name, (err, bound) in self.worst.items():
            _log(f"max rel err  {name}: {err:.3e} (bound {bound:.1e})")


# ------------------------------------------------------------- one chip
def _require_tpu():
    """Set up the compile cache, then refuse anything but a TPU."""
    sys.path.insert(0, str(SRC))
    from repro.launch.serve_coresets import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX's first device is {dev.platform!r}, not a "
                 f"TPU; refusing to run the chip path on the CPU")
    return dev, cache_dir


class _CompileMeter:
    """Backend compile seconds (a persistent-cache hit counts only its
    read) and cache hits, from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.seconds, self.count, self.cache_hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.count += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class _Backends:
    """The backend each dispatched op ran on, outside reference work."""

    def __init__(self):
        self.seen: dict[str, set] = {}
        self._paused = 0

    def __call__(self, op, backend, size, seconds):
        if not self._paused:
            self.seen.setdefault(op, set()).add(backend)

    @contextlib.contextmanager
    def paused(self):
        self._paused += 1
        try:
            yield
        finally:
            self._paused -= 1


def _kernel_parity(checks: _Checks) -> None:
    """Each device path against the f64 numpy oracle on small inputs."""
    from repro import ops
    from repro.core import fitting_loss, signal_coreset
    from repro.kernels.sat2d.ops import sat_stack, sat_stack_comp
    from repro.kernels.sat2d.ref import split_hi_lo

    plain = {"compensated": False}
    rng = np.random.default_rng(SEED + 1)
    y = OFFSET + rng.normal(size=(300, 520))
    want = ops.sat_moments(y, backend="numpy")
    checks.scaled("parity sat_moments[pallas compensated]",
                  ops.sat_moments(y, backend="pallas"), want, 1e-9)
    checks.scaled("parity sat_moments[pallas f32]",
                  ops.sat_moments(y, backend="pallas", config=plain), want,
                  1e-5)
    carry, tail = want[:, 199, :], y[200:]
    want_d = ops.delta_sat(carry, tail, backend="numpy")
    checks.scaled("parity delta_sat[pallas compensated]",
                  ops.delta_sat(carry, tail, backend="pallas"), want_d, 1e-9)
    checks.scaled("parity delta_sat[pallas f32]",
                  ops.delta_sat(carry, tail, backend="pallas", config=plain),
                  want_d, 1e-5)
    stk = OFFSET + rng.normal(size=(2, 3, 100, 200))
    want_s = np.cumsum(np.cumsum(stk, axis=-1), axis=-2)
    hi, lo = sat_stack_comp(*split_hi_lo(stk))
    checks.scaled("parity sat_stack[pallas compensated]",
                  np.asarray(hi, np.float64) + np.asarray(lo, np.float64),
                  want_s, 1e-9)
    checks.scaled("parity sat_stack[pallas f32]",
                  sat_stack(stk.astype(np.float32)), want_s, 1e-5)

    ys, _ = _signal(96, 80)
    cs = signal_coreset(ys, 6, 0.25)
    trees = _trees(96, 80, 5, SEED + 2)
    sr = np.stack([r for r, _ in trees]).astype(np.float64)
    sl = np.stack([lab for _, lab in trees])
    want_l = [fitting_loss(cs, r, lab) for r, lab in trees]
    got_b = ops.fitting_loss_batched(cs, sr, sl, backend="pallas")
    for i, (r, lab) in enumerate(trees):
        checks.rel("parity fitting_loss[pallas]",
                   ops.fitting_loss(cs, r, lab, backend="pallas"),
                   want_l[i], 1e-4)
        checks.rel("parity fitting_loss_batched[pallas]", float(got_b[i]),
                   want_l[i], 1e-4)

    P, B = 5000, 256
    codes = rng.integers(0, B, size=(P, 2)).astype(np.uint8)
    w = rng.uniform(0.5, 1.5, P)
    v = OFFSET + rng.normal(size=P)
    args = (codes, w, w * v, w * v * v, B)
    want_h = ops.hist_split(*args, backend="numpy")
    checks.scaled("parity hist_split[pallas fused]",
                  ops.hist_split(*args, backend="pallas"), want_h, 1e-6)


def run_one_chip() -> int:
    t_start = time.perf_counter()
    dev, cache_dir = _require_tpu()
    meter = _CompileMeter()
    import jax

    from repro.client import CoresetClient
    from repro.kernels.common import default_interpret
    from repro.launch.serve_coresets import build_parser, make_engine
    from repro.obs import profile
    from repro.service import make_server, serve_forever_in_thread
    from repro.trees import RandomForestRegressor

    from repro import ops
    _log(f"device {ops.device_info()}; interpret={default_interpret()}; "
         f"compile cache {cache_dir}")
    if default_interpret():
        sys.exit("chip_smoke: Pallas would run in interpret mode")
    checks = _Checks()
    backends = _Backends()

    with backends.paused():
        _kernel_parity(checks)
    _log(f"kernel parity done ({time.perf_counter() - t_start:.1f}s)")
    profile.add_hook(backends)

    y, truth_tree = _signal(N, M)
    _log(f"signal {N}x{M} = {N * M / 1e6:.1f}M entries "
         f"(f64 {y.nbytes / 1e6:.0f} MB; (3, n, m) f32 integral images "
         f"{3 * y.size * 4 / 1e6:.0f} MB on the device)")
    args = build_parser().parse_args([])
    engine = make_engine(args)
    srv = make_server(engine, host="127.0.0.1", port=0)
    serve_forever_in_thread(srv)
    base = f"http://127.0.0.1:{srv.server_address[1]}"
    client = CoresetClient(base, encoding="binary", timeout=600.0)
    timings: dict[str, float] = {}

    def timed(name, fn, *a, **kw):
        t0 = time.perf_counter()
        out = fn(*a, **kw)
        timings[name] = time.perf_counter() - t0
        return out

    try:
        timed("register", client.register_signal, "raster", values=y)
        rb = timed("build", client.build, "raster", K, EPS)
        _log(f"built k={K} eps={EPS}: {rb.blocks} blocks, {rb.size} points, "
             f"eps_eff={rb.eps_eff}")

        # ---- 32 single-tree losses from 4 concurrent SDK clients
        trees = [truth_tree] + _trees(N, M, QUERIES - 1, SEED + 10)
        with backends.paused():
            truth = [_direct_loss(y, r, lab) for r, lab in trees]
        _log("f64 reference losses computed; querying")
        answers: list = [None] * len(trees)

        def ask(idx: list[int]) -> None:
            cl = CoresetClient(base, encoding="binary", timeout=600.0)
            for i in idx:
                answers[i] = cl.query_loss("raster", *trees[i], eps=EPS)

        t0 = time.perf_counter()
        threads = [threading.Thread(target=ask, args=(list(range(c, len(trees), 4)),))
                   for c in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        timings["32 losses"] = time.perf_counter() - t0
        for i, (a, tl) in enumerate(zip(answers, truth)):
            checks.true("loss answered", a is not None, f"query {i} got no answer")
            if a is not None:
                checks.rel("loss vs f64 truth", a.loss, tl, a.eps_eff)
        checks.rel("generating tree loss vs f64 truth", answers[0].loss,
                   truth[0], answers[0].eps_eff)

        # ---- one fused batch of 16 trees == the sequential answers
        sr = np.stack([r for r, _ in trees[:BATCH]])
        sl = np.stack([lab for _, lab in trees[:BATCH]])
        bt = timed("batch", client.query_loss_batch, "raster", sr, sl, eps=EPS)
        checks.true("batch scoring calls", bt.scoring_calls == 1,
                    f"{bt.scoring_calls} scoring calls for one batch")
        for got, a in zip(bt.losses, answers[:BATCH]):
            checks.rel("batch vs sequential", float(got), a.loss, BATCH_RTOL)

        # ---- forest fit vs the same forest grown on f64 histograms
        cs = engine.get_coreset("raster", K, EPS)[0]
        X, yv, wv = cs.as_points()
        fr = timed("fit", client.fit, "raster", K, EPS,
                   n_estimators=FIT_TREES, predict=X)
        with backends.paused():
            ref = RandomForestRegressor(n_estimators=FIT_TREES, max_leaves=K,
                                        random_state=0, hist_backend="numpy")
            ref.fit(X, yv, sample_weight=wv)
            pred_ref = ref.predict(X)
        pred = np.asarray(fr.predictions, np.float64)
        checks.true("fit predictions", pred.shape == pred_ref.shape
                    and bool(np.all(np.isfinite(pred))),
                    f"predictions {pred.shape} finite={np.isfinite(pred).all()}")
        checks.rel("fit SSE vs f64-histogram forest",
                   float(np.sum(wv * (pred - yv) ** 2)),
                   float(np.sum(wv * (pred_ref - yv) ** 2)), FIT_RTOL)

        # ---- replace delta (dense: patches the integral images) + rebuild
        rng = np.random.default_rng(SEED + 20)
        r0 = N // 2
        y[r0:r0 + DELTA_ROWS] += 0.5 + NOISE * rng.normal(size=(DELTA_ROWS, M))
        timed("replace delta", client.ingest_delta, "raster",
              y[r0:r0 + DELTA_ROWS], row0=r0)
        timed("rebuild after replace", client.build, "raster", K, EPS)
        for rects, lab in _trees(N, M, DELTA_TREES, SEED + 21):
            a = client.query_loss("raster", rects, lab, eps=EPS)
            with backends.paused():
                tl = _direct_loss(y, rects, lab)
            checks.rel("loss after replace vs f64 truth", a.loss, tl,
                       a.eps_eff)

        # ---- append delta (the signal streams from here) + rebuild, vs a
        # one-shot build of the final signal within the composed bound
        band = y[-DELTA_ROWS:] + NOISE * rng.normal(size=(DELTA_ROWS, M))
        timed("append delta", client.ingest_delta, "raster", band)
        rd = timed("rebuild after append", client.build, "raster", K, EPS)
        y = np.vstack([y, band])
        timed("one-shot register", client.register_signal, "oneshot",
              values=y)
        ro = timed("one-shot build", client.build, "oneshot", K, EPS)
        for rects, lab in _trees(N + DELTA_ROWS, M, DELTA_TREES, SEED + 22):
            a = client.query_loss("raster", rects, lab, eps=EPS)
            o = client.query_loss("oneshot", rects, lab, eps=EPS)
            with backends.paused():
                tl = _direct_loss(y, rects, lab)
            checks.rel("loss after append vs f64 truth", a.loss, tl,
                       a.eps_eff)
            checks.rel("one-shot loss vs f64 truth", o.loss, tl, o.eps_eff)
            # both answers sit within their eps_eff of the truth, so they
            # differ by at most the sum of the two (relative to the truth)
            checks.rel("delta vs one-shot (composed bound)", a.loss, o.loss,
                       a.eps_eff + o.eps_eff, scale=tl)
        _log(f"after deltas: eps_eff {rd.eps_eff:.3f} (one-shot "
             f"{ro.eps_eff:.3f})")

        # ---- compress: the exact-moment points carry the signal's moments
        cp = timed("compress", client.compress, "raster", K, EPS,
                   style="caratheodory", max_points=1 << 24)
        checks.true("compress complete", not cp.truncated, "truncated")
        for name, got, want in (
                ("compress sum w", cp.w.sum(), y.size),
                ("compress sum w*y", cp.w @ cp.y, y.sum()),
                ("compress sum w*y^2", cp.w @ (cp.y * cp.y), np.sum(y * y))):
            checks.rel(name, float(got), float(want), MOMENT_RTOL)
    finally:
        srv.shutdown()
        engine.close()
        profile.remove_hook(backends)

    # ---- which path each op ran on: never the host oracle
    for op in sorted(backends.seen):
        _log(f"backend {op}: {', '.join(sorted(backends.seen[op]))}")
    for op in ("sat_moments", "delta_sat", "streaming_compress",
               "fitting_loss_batched", "hist_split"):
        ran = backends.seen.get(op, set())
        checks.true(f"{op} backend", bool(ran) and "numpy" not in ran,
                    f"ran on {sorted(ran) or 'nothing'}")
    for name, secs in timings.items():
        _log(f"time {name}: {secs:.2f}s (host clock, TPU {dev.device_kind})")
    _log(f"compile: {meter.seconds:.1f}s over {meter.count} backend compiles, "
         f"{meter.cache_hits} persistent-cache hits")
    checks.report()
    _log(f"total {time.perf_counter() - t_start:.1f}s")
    if checks.failures:
        for f in checks.failures:
            _log(f"FAIL {f}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


# ------------------------------------------------------------ four chips
# The launcher (``run_four_chips``) imports neither JAX nor anything that
# does; each child below owns at most one chip.

def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _child_env(chip: int | None) -> dict:
    """Environment of a child process.  ``chip=None``: no chip at all (the
    CPU backend, by choice).  Otherwise exactly one chip: libtpu is told
    to open only chip ``chip`` as a one-chip slice, so several processes
    each hold their own chip — which is why the library's single-process
    lock is lifted for them."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(SRC) + (os.pathsep + env["PYTHONPATH"]
                                    if env.get("PYTHONPATH") else "")
    if chip is None:
        env["JAX_PLATFORMS"] = "cpu"
        return env
    port = _free_port()
    env.update(JAX_PLATFORMS="tpu", TPU_VISIBLE_CHIPS=str(chip),
               TPU_CHIPS_PER_PROCESS_BOUNDS="1,1,1",
               TPU_PROCESS_BOUNDS="1,1,1", TPU_PROCESS_PORT=str(port),
               TPU_PROCESS_ADDRESSES=f"localhost:{port}",
               ALLOW_MULTIPLE_LIBTPU_LOAD="1")
    return env


_URL_RE = re.compile(r"listening on (http://[\d.]+:\d+)")


class _Server:
    """A ``serve_coresets`` child plus a thread draining its output."""

    def __init__(self, role_args: list[str], env: dict):
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "repro.launch.serve_coresets",
             "--host", "127.0.0.1", "--port", "0", *role_args],
            cwd=ROOT, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True, bufsize=1)
        self.lines: list[str] = []
        self.url: str | None = None
        threading.Thread(target=self._drain, daemon=True).start()

    def _drain(self) -> None:
        for line in self.proc.stdout:
            self.lines.append(line)
            m = _URL_RE.search(line)
            if m and self.url is None:
                self.url = m.group(1)

    def wait_url(self, timeout: float = 120.0) -> str:
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < timeout:
            if self.url:
                return self.url
            if self.proc.poll() is not None:
                break
            time.sleep(0.05)
        raise RuntimeError("server child never reported its URL:\n"
                           + "".join(self.lines[-40:]))

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=20)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=20)


def _run_child(role: str, env: dict, stdin: str = "",
               timeout: float = 600.0) -> dict:
    """Run ``chip_smoke.py --role ROLE`` to its end; its last stdout line
    is its JSON result."""
    proc = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py"), "--role", role],
        cwd=ROOT, env=env, input=stdin, capture_output=True, text=True,
        timeout=timeout)
    for line in proc.stdout.splitlines()[:-1]:
        print(line, flush=True)
    if proc.returncode != 0:
        raise RuntimeError(f"{role} child exited {proc.returncode}:\n"
                           f"{proc.stdout[-4000:]}\n{proc.stderr[-4000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _role_reference() -> int:
    """Chip 0, before the cluster starts: the single-host band-parallel
    build.  The coordinator holds no chip, so its one full-signal step (the
    shared block tolerance) runs on the f64 host oracle; the reference
    takes its tolerance the same way, and the comparison is of the band
    builds on the chips."""
    sys.path.insert(0, str(SRC))
    from repro import ops
    from repro.core import fitting_loss
    from repro.core.sharded import (band_bounds, sharded_coreset,
                                    shared_sigma, shared_tolerance)
    from repro.launch.serve_coresets import enable_compile_cache
    enable_compile_cache()
    dev = ops.device_info()
    if dev["platform"] != "tpu":
        sys.exit(f"reference: no TPU ({dev['platform']})")
    y, _ = _signal(N, M)
    with ops.backend_override("numpy"):
        sigma = shared_sigma(y, K, band_bounds(N, CLUSTER_CHIPS)).sigma
        tol = shared_tolerance(sigma, K, EPS)
    t0 = time.perf_counter()
    cs = sharded_coreset(y, K, EPS, num_bands=CLUSTER_CHIPS,
                         tolerance_override=tol)
    secs = time.perf_counter() - t0
    losses = [fitting_loss(cs, r, lab)
              for r, lab in _trees(N, M, CLUSTER_QUERIES, SEED + 30)]
    _log(f"reference: single-host build {secs:.1f}s on {dev['kind']}, "
         f"{cs.num_blocks} blocks")
    print(json.dumps({"fingerprint": cs.fingerprint(), "losses": losses,
                      "device": dev}), flush=True)
    return 0


def _role_client() -> int:
    """No chip: register, build and query the cluster through the SDK."""
    sys.path.insert(0, str(SRC))
    from repro.client import CoresetClient
    req = json.loads(sys.stdin.read())
    ref = req["reference"]
    client = CoresetClient(req["url"], encoding="binary", timeout=600.0)
    checks = _Checks()
    y, _ = _signal(N, M)
    t0 = time.perf_counter()
    client.register_signal("raster", values=y)
    rb = client.build("raster", K, EPS)
    secs = time.perf_counter() - t0
    checks.true("cluster fingerprint", rb.fingerprint == ref["fingerprint"],
                f"{rb.fingerprint} != single-host {ref['fingerprint']}")
    trees = _trees(N, M, CLUSTER_QUERIES, SEED + 30)
    for (rects, lab), want in zip(trees, ref["losses"]):
        a = client.query_loss("raster", rects, lab, eps=EPS)
        checks.rel("cluster loss vs single-host", a.loss, want, 1e-9)
        checks.rel("cluster loss vs f64 truth", a.loss,
                   _direct_loss(y, rects, lab), a.eps_eff)
    st = client.stats()["cluster"]
    checks.true("no degraded builds", st["degraded_builds"] == 0,
                f"{st['degraded_builds']} band builds fell back locally")
    _log(f"cluster: register+build {secs:.1f}s (host clock), "
         f"fingerprint {rb.fingerprint[:12]}, gathers={st['gathers']}")
    checks.report()
    print(json.dumps({"failures": checks.failures}), flush=True)
    return 0


def _healthz(url: str) -> dict:
    with urllib.request.urlopen(url + "/v1/healthz", timeout=600) as r:
        return json.loads(r.read())


def run_four_chips() -> int:
    """The cluster plane on four chips, one worker process per chip."""
    failures: list[str] = []
    ref = _run_child("reference", _child_env(0))
    _log(f"reference fingerprint {ref['fingerprint'][:12]} "
         f"({ref['device']['kind']})")
    servers: list[_Server] = []
    try:
        workers = [_Server(["--role", "worker", "--worker-id", f"chip{i}"],
                           _child_env(i)) for i in range(CLUSTER_CHIPS)]
        servers += workers
        urls = [w.wait_url() for w in workers]
        # the first health check initializes each worker's chip: in parallel
        with ThreadPoolExecutor(len(urls)) as pool:
            devices = [h["device"] for h in pool.map(_healthz, urls)]
        for i, dev in enumerate(devices):
            _log(f"worker chip{i} pid {workers[i].proc.pid}: {dev['platform']} "
                 f"{dev['kind']} id={dev['id']} coords={dev['coords']} "
                 f"files={dev['device_files']} (sees {dev['count']})")
        if any(d["platform"] != "tpu" or d["count"] != 1 for d in devices):
            failures.append(f"a worker is not on exactly one TPU: {devices}")
        owned = [tuple(d["device_files"]) for d in devices]
        if len(set(owned)) != CLUSTER_CHIPS or not all(owned):
            failures.append(f"workers do not hold distinct chips: {owned}")
        coord = _Server(["--role", "coordinator", "--peers", ",".join(urls),
                         "--rpc-timeout", "600"], _child_env(None))
        servers.append(coord)
        out = _run_child("client", _child_env(None), stdin=json.dumps(
            {"url": coord.wait_url(), "reference": ref}))
        failures += out["failures"]
    except Exception:
        for s in servers:    # the children's side of the failure
            print("".join(s.lines[-30:]), file=sys.stderr, flush=True)
        raise
    finally:
        for s in servers:
            s.stop()
    if failures:
        for f in failures:
            _log(f"FAIL {f}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0]["platform"], "kind": devices[0]["kind"],
        "count": len(devices)}}), flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, CLUSTER_CHIPS),
                    default=1, help="1: the single-host served path; "
                    "4: the cluster plane, one worker process per chip")
    ap.add_argument("--role", choices=("reference", "client"),
                    help=argparse.SUPPRESS)   # children of --chips 4
    args = ap.parse_args()
    if args.role == "reference":
        return _role_reference()
    if args.role == "client":
        return _role_client()
    return run_four_chips() if args.chips == CLUSTER_CHIPS else run_one_chip()


if __name__ == "__main__":
    sys.exit(main())
