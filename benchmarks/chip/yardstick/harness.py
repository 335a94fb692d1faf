"""One run of one cell: set-up, the measured window, the check, the line.

``run`` starts the real server in this process (``serve_coresets``'s
engine constructor and ``make_server``), registers and builds one record
of the configuration's size to warm every shape a build takes, starts the
load generator
(``benchmarks/chip/loadgen.py``, a process of its own without JAX), opens
the window, and afterwards checks every answer the window produced against
the f64 reference.  Its last stdout line is the result JSON; the numbers
compared, each beside its limit, close both stderr and that line.
"""
from __future__ import annotations

import importlib.util
import json
import os
import pathlib
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

from . import devtrace, reference, signals, traffic
from .peaks import peaks_for
from .spans import clock_offset, collect

ROOT = pathlib.Path(__file__).resolve().parents[3]
BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]

__all__ = ["NoChip", "load_cell", "run", "main_line"]


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def _log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def load_cell(root: pathlib.Path, name: str):
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"cells: {sorted(cells)}")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    mix = traffic.load(root / "benchmarks" / "chip" / "traffic"
                       / f"{cell['traffic']}.json")
    return bench, cell, config, mix


def load_reader(root: pathlib.Path, name: str):
    """The per-layer metric ``name``'s reader: ``read(run) -> number |
    {"value", ...} | None`` from ``benchmarks/chip/metrics/<name>.py``."""
    path = root / "benchmarks" / "chip" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileMeter:
    """Backend compiles (a persistent-cache read counts as one) from JAX's
    monitoring events."""

    def __init__(self):
        import jax
        self.count = 0
        self.seconds = 0.0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.count += 1
                self.seconds += secs


class Run:
    """Everything one run measured: what the readers of the per-layer
    metrics and of the end-to-end metrics take their numbers from."""

    def __init__(self, cell, config, mix, seed, seconds):
        self.cell, self.config, self.mix = cell, config, mix
        self.seed, self.seconds = seed, seconds
        self.t_start = self.t0 = self.t_close = 0.0
        self.records: dict = {}               # loadgen output arrays
        self.spans: list = []                 # program spans (trace runs)
        self.compiles_window = 0
        self.trace = None                     # devtrace.DeviceTrace
        self.trace_ns = (0.0, 0.0)            # the window on the trace clock
        self.peaks = None

    def window_spans(self, name: str | None = None, **attrs):
        return [s for s in self.spans
                if self.t0 <= s.t0 <= self.t_close
                and (name is None or s.name == name)
                and all(s.attrs.get(k) == v for k, v in attrs.items())]


# ------------------------------------------------------------ correctness
class Checks:
    """The numbers compared, each with its limit (value <= limit)."""

    def __init__(self):
        self.items: dict[str, dict] = {}

    def add(self, name: str, value: float, limit: float) -> None:
        self.items[name] = {"value": float(value), "limit": float(limit)}

    @property
    def ok(self) -> bool:
        return bool(self.items) and all(
            np.isfinite(v["value"]) and v["value"] <= v["limit"]
            for v in self.items.values())


def _check_builds(run: Run, engine, url: str, checks: Checks) -> None:
    """Score check trees against every coreset the window built (through
    the served loss path, after the window) and compare with the f64
    losses of the regenerated records.  Each answer is held to the lesser
    of the configuration's limit and the ``eps_eff`` its build stated.
    The scoring has to find each coreset in the engine's cache: a coreset
    built anew for the check is not the one the window timed, and
    ``check_rebuilds`` counts those."""
    from repro.client import CoresetClient
    r = run.records
    cfg = run.config
    k, eps = int(cfg["coreset"]["k"]), float(cfg["coreset"]["eps"])
    tspec = run.mix["check_trees"]
    client = CoresetClient(url, encoding="binary", timeout=300.0)
    limit = float(cfg["limits"]["build_rel_err"])
    builds_before = int(engine.metrics.get("coreset_builds"))
    refs: dict = {}
    worst = 0.0
    for row in np.flatnonzero(r["ok"]):
        c, j, i = int(r["client"][row]), int(r["j"][row]), int(r["record"][row])
        if i not in refs:
            rec = traffic.record_at(run.mix, cfg, i)
            refs[i] = (rec, reference.SignalReference(rec.values))
        rec, ref = refs[i]
        rects, labels = signals.tree_pool(
            cfg["signal"], rec, int(tspec["leaves"]), int(tspec["count"]),
            float(tspec["fit_share"]), float(tspec["label_jitter"]),
            signals.seed_rng(run.seed, traffic.TAG_CHECK, c, j))
        got = client.query_loss_batch(traffic.insert_name(c, j), rects,
                                      labels, k=k, eps=eps)
        worst = max(worst, float(np.max(reference.rel_errors(
            got.losses, ref.losses(rects, labels)))))
        limit = min(limit, float(r["eps_eff"][row]))
    checks.add("build_rel_err", worst, limit)
    checks.add("check_rebuilds",
               int(engine.metrics.get("coreset_builds")) - builds_before, 0)


# -------------------------------------------------------------- the window
def _spawn_loadgen(spec_path: pathlib.Path) -> subprocess.Popen:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"      # it never imports JAX; be sure of it
    return subprocess.Popen(
        [sys.executable, str(BENCH_DIR / "loadgen.py"), str(spec_path)],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
        cwd=str(ROOT), env=env)


def _expect(proc: subprocess.Popen, word: str) -> None:
    line = proc.stdout.readline().strip()
    if line != word:
        proc.kill()
        proc.wait()
        raise RuntimeError(f"load generator said {line!r}, expected {word!r}")


def _device_memory_peak(devices) -> int | None:
    peaks = []
    for d in devices:
        try:
            st = d.memory_stats() or {}
        except Exception:  # noqa: BLE001 - a backend without the call
            st = {}
        if "peak_bytes_in_use" in st:
            peaks.append(int(st["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def _breakdown(run: Run) -> dict:
    """The device ops that took the most time, and the ten longest idle
    gaps, each named by the innermost program span open at its middle."""
    lo, hi = run.trace_ns
    gaps = run.trace.idle_gaps(lo, hi)
    longest = gaps[np.argsort(gaps[:, 0] - gaps[:, 1])[:10]]
    spans = [s for s in run.spans if s.t1 >= run.t0 and s.t0 <= run.t_close]
    named = []
    for g0, g1 in longest:
        mid = run.t0 + ((g0 + g1) / 2 - lo) / 1e9
        open_ = [s for s in spans if s.t0 <= mid <= s.t1]
        named.append([min(open_, key=lambda s: s.seconds).name if open_
                      else "no span open", (g1 - g0) / 1e9])
    return {"device_ops": run.trace.top_ops(lo, hi), "idle_gaps": named}


def run(cell_name: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, root: pathlib.Path = ROOT,
        require_tpu: bool = True, hooks=()) -> dict:
    """One run; returns the result dict (``main_line`` prints it).  Raises
    ``NoChip`` before any work when JAX finds no TPU or too few chips.
    ``hooks`` are called with the engine once the server is up (the
    correctness control and the tests' planted faults use them); a hook
    may return a function, called when the program's state is freed."""
    bench, cell, config, mix = load_cell(root, cell_name)
    run_ = Run(cell, config, mix, seed, seconds)
    run_.t_start = t_start

    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip():
        jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and (dev.platform != "tpu"
                        or len(devices) < int(cell["chips"])):
        raise NoChip(f"JAX finds {len(devices)} {dev.platform} device(s); "
                     f"cell {cell_name} needs {cell['chips']} TPU chip(s)")
    if dev.platform == "tpu":
        run_.peaks = peaks_for(dev.device_kind)
    meter = CompileMeter()
    with tempfile.TemporaryDirectory(prefix="chipbench-") as tmp:
        return _measure(run_, bench, root, devices, meter, trace, hooks,
                        pathlib.Path(tmp))


def _measure(run_, bench, root, devices, meter, trace, hooks, tmp) -> dict:
    """``run`` past the chip check: serve, set up, measure, check; ``tmp``
    holds the load generator's files and the profiler trace."""
    import jax

    from repro import obs
    from repro.launch.serve_coresets import build_parser, make_engine
    from repro.service import make_server, serve_forever_in_thread
    cell, config, mix = run_.cell, run_.config, run_.mix
    seed, seconds = run_.seed, run_.seconds
    dev = devices[0]
    if trace:
        # keep every trace of the window for the per-layer readers
        obs.TRACER.capacity = 1 << 20
    args = build_parser().parse_args(list(config.get("engine_flags", [])))
    engine = make_engine(args)
    srv = make_server(engine, host="127.0.0.1", port=0)
    serve_forever_in_thread(srv)
    url = f"http://127.0.0.1:{srv.server_address[1]}"
    undo = [hook(engine) for hook in hooks]
    spec = {"url": url, "traffic": mix, "config": config, "seed": seed,
            "seconds": seconds, "out": str(tmp / "records.npz")}
    (tmp / "spec.json").write_text(json.dumps(spec))
    gen = _spawn_loadgen(tmp / "spec.json")
    try:
        # ---- set-up: one record registered and built warms every shape a
        # build of the configuration's size takes; the same record in
        # every run, so set-up does the same work whatever the seed
        t_serve = time.monotonic()
        from repro.client import CoresetClient
        warm = CoresetClient(url, encoding="binary", timeout=600.0)
        wrec = signals.record(config["signal"], "warm-0",
                              int(mix["record_seed"]), traffic.TAG_WARM)
        t_drawn = time.monotonic()
        warm.register_signal(wrec.name, values=wrec.values)
        t_registered = time.monotonic()
        warm.build(wrec.name, int(config["coreset"]["k"]),
                   float(config["coreset"]["eps"]))
        t_built = time.monotonic()
        del wrec
        _expect(gen, "READY")
        t_ready = time.monotonic()
        _log(f"set-up {t_ready - run_.t_start:.2f}s: imports and chip "
             f"{t_serve - run_.t_start:.2f}s, warm record drawn "
             f"{t_drawn - t_serve:.2f}s, registered "
             f"{t_registered - t_drawn:.2f}s, built "
             f"{t_built - t_registered:.2f}s, load generator ready "
             f"{t_ready - t_built:.2f}s; {meter.count} compiles "
             f"({meter.seconds:.2f}s)")

        # ---- the window
        offset = clock_offset(obs.TRACER)
        trace_dir = tmp / "trace"
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        compiles_open = meter.count
        run_.t0 = time.monotonic() + 0.05
        gen.stdin.write(f"GO {run_.t0!r}\n")
        gen.stdin.flush()
        if trace:
            time.sleep(max(run_.t0 - time.monotonic(), 0.0))
            with jax.profiler.TraceAnnotation("chipbench.window_open"):
                run_.t0 = time.monotonic()
        time.sleep(max(run_.t0 + seconds - time.monotonic(), 0.0))
        run_.t_close = time.monotonic()
        run_.compiles_window = meter.count - compiles_open
        if trace:
            jax.profiler.stop_trace()
        _expect(gen, "DONE")
        gen.wait(timeout=120)
        with np.load(tmp / "records.npz") as z:
            run_.records = {k: z[k] for k in z.files}
        run_.setup_s = run_.t0 - run_.t_start
        memory_peak = _device_memory_peak(devices[:int(cell["chips"])])

        # ---- answers the window owes us, then the program's state goes
        checks = Checks()
        _check_builds(run_, engine, url, checks)
        if trace:
            run_.spans = collect(obs.TRACER, offset)
    finally:
        if gen.poll() is None:
            gen.kill()
        gen.wait()
        srv.shutdown()
        srv.server_close()
        engine.close()
        for u in undo:
            if callable(u):
                u()

    attempted, failed = _attempts(run_)
    checks.add("unanswered", failed, 0)
    _log(f"window {seconds}s: {attempted} attempted, {failed} failed, "
         f"{run_.compiles_window} compiles inside the window")

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    from .endtoend import END_TO_END
    metrics: dict = {}
    if trace:
        files = list(trace_dir.glob("**/*.xplane.pb"))
        if files:
            run_.trace = devtrace.DeviceTrace.from_file(files[0])
            lo = run_.trace.markers.get("chipbench.window_open")
            if lo is not None:
                run_.trace_ns = (lo, lo + (run_.t_close - run_.t0) * 1e9)
        if run_.trace is not None and run_.trace_ns[1] > 0:
            device["busy_s"] = run_.trace.busy_seconds(*run_.trace_ns)
            device["window_s"] = (run_.trace_ns[1] - run_.trace_ns[0]) / 1e9
            result_breakdown = _breakdown(run_)
        else:
            result_breakdown = None
        for m in bench["per_layer"]:
            if cell["name"] not in m.get("workloads", [cell["name"]]):
                continue
            got = load_reader(root, m["name"])(run_)
            if got is None:
                continue
            entry = dict(got) if isinstance(got, dict) else {"value": got}
            entry["value"] = float(entry["value"])
            metrics[m["name"]] = {"value": entry.pop("value"),
                                  "unit": m["unit"], **entry}
    else:
        result_breakdown = None
        for m in bench["end_to_end"]:
            if cell["name"] not in m.get("workloads", [cell["name"]]):
                continue
            metrics[m["name"]] = {"value": float(END_TO_END[m["name"]](run_)),
                                  "unit": m["unit"]}
    result = {"correct": checks.ok, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if result_breakdown is not None:
        result["breakdown"] = result_breakdown
    result["checks"] = checks.items
    return result


def _attempts(run: Run) -> tuple[int, int]:
    r = run.records
    ok = np.asarray(r["ok"], bool)
    started = np.asarray(r["sent"]) <= run.t0 + run.seconds
    return int(np.sum(started)), int(np.sum(started & ~ok))


def main_line(result: dict) -> None:
    """Print the checks to stderr (last lines there) and the result as the
    last stdout line."""
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
