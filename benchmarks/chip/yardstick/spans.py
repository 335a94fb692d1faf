"""The program's spans, as the per-layer readers see them.

``collect`` copies every finished trace out of a ``repro.obs`` tracer
(its public ``recent``/``get`` reads) into flat records on the
monotonic-clock seconds that the rest of the benchmark uses: the tracer
stamps spans on the wall clock through one fixed anchor, and a marker span
opened at a known ``perf_counter`` reading gives the offset.
"""
from __future__ import annotations

import time

import numpy as np

__all__ = ["clock_offset", "collect", "Span", "median"]


class Span:
    __slots__ = ("name", "trace_id", "span_id", "parent_id", "t0", "t1",
                 "attrs", "links")

    def __init__(self, d: dict, offset: float):
        self.name = d["name"]
        self.trace_id = d["trace_id"]
        self.span_id = d["span_id"]
        self.parent_id = d.get("parent_id")
        self.t0 = d["start_us"] / 1e6 - offset
        self.t1 = self.t0 + d["duration_us"] / 1e6
        self.attrs = d.get("attrs") or {}
        self.links = d.get("links") or []

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


def clock_offset(tracer) -> float:
    """Tracer time (s) minus ``time.perf_counter()`` for this tracer."""
    sp = tracer.start_trace("bench.clock_marker")
    pc = sp.start_pc
    sp.end()
    rec = tracer.get(sp.trace_id, resolve_links=False)
    if rec is None:
        return time.time() - time.perf_counter()
    return rec["start_us"] / 1e6 - pc


def collect(tracer, offset: float) -> list:
    out = []
    for summary in tracer.recent(0):
        t = tracer.get(summary["trace_id"], resolve_links=False)
        if t is None:
            continue
        out += [Span(s, offset) for s in t["spans"]]
    return out


def median(values) -> float | None:
    v = np.asarray(list(values), np.float64)
    return float(np.median(v)) if v.size else None
