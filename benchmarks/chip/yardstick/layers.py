"""Shared arithmetic of the per-layer readers in ``benchmarks/chip/metrics``.

Each reader is a file of its own that calls one of these with its cell's
parameters.  All of them read a ``harness.Run``: its program spans (window
only), the load generator's records, the compiles counted in the window
and the reduced device trace.  Each returns None where it finds
nothing to read, never 0 for a share.
"""
from __future__ import annotations

import numpy as np

from . import costs
from .spans import median

__all__ = ["client_median", "host_self_median", "dispatch_per_trace",
           "sat_moments_roofline", "idle_pct", "compiles"]


def client_median(run, start: str, end: str):
    """Median of ``records[end] - records[start]`` (seconds) over answered
    requests."""
    r = run.records
    ok = np.asarray(r["ok"], bool)
    return median((np.asarray(r[end]) - np.asarray(r[start]))[ok])


def _descendants(run, root):
    kids: dict[str, list] = {}
    for s in run.spans:
        if s.trace_id == root.trace_id and s.parent_id:
            kids.setdefault(s.parent_id, []).append(s)
    out, stack = [], [root.span_id]
    while stack:
        for k in kids.get(stack.pop(), []):
            out.append(k)
            stack.append(k.span_id)
    return out


def host_self_median(run, name: str, minus: str):
    """Median over the window's ``name`` spans of their duration minus the
    ``minus`` spans beneath them (seconds)."""
    vals = []
    for s in run.window_spans(name):
        sub = sum(d.seconds for d in _descendants(run, s) if d.name == minus)
        vals.append(s.seconds - sub)
    return median(vals)


def dispatch_per_trace(run, op: str, root_name: str):
    """Median over the window's ``root_name`` spans (one per request) of
    the summed ``ops.dispatch`` seconds of ``op`` beneath each."""
    vals = []
    for s in run.window_spans(root_name):
        ds = [d.seconds for d in _descendants(run, s)
              if d.name == "ops.dispatch" and d.attrs.get("op") == op]
        if ds:
            vals.append(sum(ds))
    return median(vals)


def _roofline(run, least: float, bound: str, module_prefix: str):
    if run.trace is None or run.peaks is None or least <= 0:
        return None
    lo, hi = run.trace_ns
    dev = run.trace.module_seconds(module_prefix, lo, hi)
    if dev <= 0:
        return None
    return {"value": 100.0 * least / dev, "bound": bound,
            "device_s": dev, "least_s": least}


def sat_moments_roofline(run):
    """Share of the roofline of the compensated integral-image program
    over the window.  The work a build needs is one integral image of its
    record (n x m cells from the configuration), once per ``engine.compress``
    span in the window; the device time is every run of the program in the
    window (a build that computes images more than once, as per-band builds
    do today, reads as a lower share)."""
    if run.peaks is None:
        return None
    builds = len(run.window_spans("engine.compress"))
    if not builds:
        return None
    sig = run.config["signal"]
    o, b = costs.sat_moments_comp(int(sig["n"]), int(sig["m"]))
    least, bound = costs.least_seconds(builds * o, builds * b, run.peaks)
    return _roofline(run, least, bound, "jit_sat_moments_comp(")


def idle_pct(run):
    """100 x (1 - busy / window) from the device trace."""
    if run.trace is None:
        return None
    lo, hi = run.trace_ns
    window = (hi - lo) / 1e9
    if window <= 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_seconds(lo, hi) / window)


def compiles(run):
    """Backend compiles (persistent-cache reads included) inside the
    window: the target is none."""
    return run.compiles_window
