"""CPU tests of the readers of the k=16 cell's shared sigma
(``benchmarks/chip/metrics/{blocks,certified_share}.k16.py``): each reads
its hand-computed value off a hand-built run, and None off a run whose
program records neither attribute, as the program before they came in."""
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


def _build(trace, t0, certified, blocks):
    """``engine.compress`` > ``core.shared_tolerance``, ``core.bands``,
    ``core.compose``; ``certified`` / ``blocks`` None leave the attribute
    out, as the program before them does."""
    tol = {} if certified is None else {"certified": certified,
                                        "sigma_greedy": 2.0,
                                        "sigma_bound": 1.0}
    comp = {} if blocks is None else {"blocks": blocks}
    return [_span("engine.compress", trace, "c", None, t0, t0 + 5),
            _span("core.shared_tolerance", trace, "t", "c", t0, t0 + 1,
                  cpu_s=0.5, **tol),
            _span("core.bands", trace, "b", "c", t0 + 1, t0 + 4, cpu_s=0.1),
            _span("core.compose", trace, "o", "c", t0 + 4, t0 + 5,
                  cpu_s=0.2, sigma_shared=1.0, sigma_bound=1.0, **comp)]


def _run(span_list):
    run = harness.Run({"name": "ycsb_load.build.k16"}, {}, {}, 1, 30.0)
    run.t0, run.t_close = 10.0, 40.0
    run.spans = span_list
    return run


def _window_run(certified=(True, True, False, True),
                blocks=(1100, 1200, 430, 1150)):
    out = []
    for i, (c, b) in enumerate(zip(certified, blocks)):
        out += _build(f"T{i}", 11.0 + 6 * i, c, b)
    # a build before the window: left out
    return _run(out + _build("W", 1.0, False, 99))


EXPECTED = {"blocks.k16": 1125.0,          # 430, 1100, 1150, 1200
            "certified_share.k16": 75.0}   # 3 of 4


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_k16_reader_by_hand(name):
    got = harness.load_reader(ROOT, name)(_window_run())
    assert got == pytest.approx(EXPECTED[name], rel=1e-12)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_k16_reader_finds_nothing_without_the_attributes(name):
    read = harness.load_reader(ROOT, name)
    assert read(_run([])) is None
    assert read(_window_run(certified=(None,) * 4,
                            blocks=(None,) * 4)) is None


def test_certified_share_reads_none_certified_as_zero():
    read = harness.load_reader(ROOT, "certified_share.k16")
    assert read(_window_run(certified=(False,) * 4)) == 0.0
