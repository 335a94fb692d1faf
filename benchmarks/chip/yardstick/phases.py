"""Shared arithmetic of the readers of the build's phase spans.

A build is one ``engine.compress`` span in the window; "beneath" it means
its descendants (``layers._descendants``).  The program opens the phase
spans (``core.*``, with ``cpu_s``: the thread's CPU seconds over the span),
the transfer spans (``dispatch.h2d``/``dispatch.d2h``) and the register
path's spans (``http.read_body``, ``http.decode``).  Each reader returns
None where it finds no span to read, as on a program that opens none.
"""
from __future__ import annotations

from .layers import _descendants
from .spans import median

__all__ = ["per_build", "per_root", "seconds", "cpu_s", "wait_s"]


def seconds(s) -> float:
    return s.seconds


def cpu_s(s) -> float | None:
    return s.attrs.get("cpu_s")


def wait_s(s) -> float | None:
    """Wall seconds the thread was runnable but not running."""
    c = s.attrs.get("cpu_s")
    return None if c is None else s.seconds - c


def _summed(spans, names: tuple, value):
    got = [value(d) for d in spans if d.name in names]
    got = [v for v in got if v is not None]
    return sum(got) if got else None


def per_build(run, names: tuple, value=seconds):
    """Median over the window's builds of the summed ``value`` of the
    ``names`` spans beneath each; builds with none are left out."""
    vals = [_summed(_descendants(run, b), names, value)
            for b in run.window_spans("engine.compress")]
    return median(v for v in vals if v is not None)


def per_root(run, root_name: str, names: tuple, value=seconds):
    """Median over the window's ``root_name`` spans (one per request) of
    the summed ``value`` of the ``names`` spans beneath each."""
    vals = [_summed(_descendants(run, r), names, value)
            for r in run.window_spans(root_name)]
    return median(v for v in vals if v is not None)
