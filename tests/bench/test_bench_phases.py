"""CPU tests of the readers of the build's phase spans
(``benchmarks/chip/metrics/*.ready.py`` over ``yardstick/phases.py``): each
reads its hand-computed value off a hand-built run, and None off a run
whose program opens none of its spans."""
from __future__ import annotations

import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "benchmarks" / "chip"))

from yardstick import harness, spans  # noqa: E402


def _span(name, trace, sid, parent, t0, t1, **attrs):
    return spans.Span({"name": name, "trace_id": trace, "span_id": sid,
                       "parent_id": parent, "start_us": t0 * 1e6,
                       "duration_us": (t1 - t0) * 1e6, "attrs": attrs}, 0.0)


def _build(trace, t0, tol, bands):
    """One build request: ``POST /v1/build`` > ``engine.compress`` > the
    shared tolerance and one ``core.band`` per entry of ``bands``:
    (wall, cpu, bicriteria cpu, partition cpu, h2d s, d2h s)."""
    out = [_span("POST /v1/build", trace, "r", None, t0, t0 + 10),
           _span("engine.compress", trace, "c", "r", t0 + 0.1, t0 + 9.9),
           _span("core.shared_tolerance", trace, "t", "c", t0 + 0.1,
                 t0 + 0.1 + tol, cpu_s=tol / 2)]
    for i, (wall, cpu, bic, part, h2d, d2h) in enumerate(bands):
        b, a = f"b{i}", t0 + 1.0
        out += [_span("core.band", trace, b, "c", a, a + wall, cpu_s=cpu,
                      band=i),
                _span("ops.dispatch", trace, f"d{i}", b, a, a + 1,
                      op="sat_moments"),
                _span("dispatch.h2d", trace, f"h{i}", f"d{i}", a, a + h2d,
                      bytes=8),
                _span("dispatch.device", trace, f"v{i}", f"d{i}", a + h2d,
                      a + h2d + 0.01),
                _span("dispatch.d2h", trace, f"o{i}", f"d{i}", a + 0.5,
                      a + 0.5 + d2h, bytes=24),
                _span("core.bicriteria", trace, f"x{i}", b, a + 1, a + 3,
                      cpu_s=bic),
                _span("core.partition", trace, f"p{i}", b, a + 3, a + 4,
                      cpu_s=part)]
    return out


def _register(trace, t0, read, decode):
    return [_span("POST /v1/signals", trace, "r", "client", t0, t0 + 1),
            _span("http.read_body", trace, "rb", "r", t0, t0 + read,
                  bytes=100),
            _span("http.decode", trace, "dc", "r", t0 + read,
                  t0 + read + decode)]


def _run(span_list):
    run = harness.Run({"name": "ycsb_load.build"}, {}, {}, 1, 30.0)
    run.t0, run.t_close = 10.0, 40.0
    run.spans = span_list
    return run


def _window_run():
    return _run(
        _build("A", 11.0, 2.0, [(6.0, 2.0, 1.0, 0.5, 0.1, 0.2),
                                (5.0, 4.0, 1.5, 0.75, 0.3, 0.4)])
        + _build("B", 21.0, 3.0, [(4.0, 1.0, 2.0, 1.0, 0.2, 0.2),
                                  (4.0, 3.5, 2.5, 1.25, 0.1, 0.1)])
        + _build("C", 29.0, 4.0, [(8.0, 2.0, 3.0, 2.0, 0.5, 0.5)])
        # a build and a register that start before the window: left out
        + _build("D", 0.5, 100.0, [(9.0, 0.0, 9.0, 9.0, 9.0, 9.0)])
        + _register("E", 12.0, 0.25, 0.5)
        + _register("F", 13.0, 0.5, 0.75)
        + _register("G", 14.0, 1.0, 1.0)
        + _register("H", 1.0, 9.0, 9.0))


# per build (or request) in the window: A, B, C (E, F, G) -> median
EXPECTED = {
    "tolerance_s.ready": 3.0,                          # 2, 3, 4
    "bicriteria_cpu_s.ready": 3.0,                     # 2.5, 4.5, 3
    "partition_cpu_s.ready": 2.0,                      # 1.25, 2.25, 2
    "band_wait_s.ready": 5.0,                          # 4+1, 3+0.5, 6
    "transfer_s.ready": 1.0,                           # 1.0, 0.6, 1.0
    "decode_s.ready": 1.25,                            # 0.75, 1.25, 2
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_phase_reader_by_hand(name):
    got = harness.load_reader(ROOT, name)(_window_run())
    assert got == pytest.approx(EXPECTED[name], rel=1e-9, abs=1e-9)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_phase_reader_finds_nothing_on_a_program_without_the_spans(name):
    read = harness.load_reader(ROOT, name)
    assert read(_run([])) is None
    # the parent program's build: engine.compress over untraced bands
    bare = [s for s in _window_run().spans
            if s.name in ("POST /v1/build", "engine.compress",
                          "ops.dispatch", "POST /v1/signals")]
    assert read(_run(bare)) is None
