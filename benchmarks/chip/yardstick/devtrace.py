"""Reduction of a JAX profiler trace (``.xplane.pb``) to device metrics.

On a TPU the trace has one plane per chip (``/device:TPU:<i>``) with an
``XLA Modules`` line (one event per jitted program run, named
``jit_<function>(<fingerprint>)``) and an ``XLA Ops`` line (one event per
HLO op; a Pallas kernel is its ``custom-call``), on the same nanosecond
timeline as the host plane's events.  A CPU trace has no device plane: its
XLA ops are host-thread events carrying ``hlo_op`` and ``hlo_module``
stats, and the reduction reads those instead (the tests record one).

* busy: the union of op intervals inside the window, per device plane,
  averaged over the planes;
* module seconds: summed durations of the module events whose name starts
  with a prefix (a kernel's jitted program), inside the window;
* top ops: device seconds per op name (the HLO instruction name without
  its ``%`` and numeric suffix);
* idle gaps: the holes in the union of op intervals inside the window.
"""
from __future__ import annotations

import re

import numpy as np

__all__ = ["DeviceTrace", "union_seconds", "gaps"]

_SUFFIX = re.compile(r"\.\d+$")


def _op_name(raw: str) -> str:
    head = raw.split(" = ", 1)[0].strip().lstrip("%")
    head = head.replace(".clone", "")
    return _SUFFIX.sub("", head)


def _merge(iv: np.ndarray) -> np.ndarray:
    """Sorted, disjoint union of (start, end) intervals."""
    if iv.size == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0])]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out)


def _clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    iv = iv.reshape(-1, 2)
    c = np.stack([np.maximum(iv[:, 0], lo), np.minimum(iv[:, 1], hi)], 1)
    return c[c[:, 1] > c[:, 0]]


def union_seconds(iv: np.ndarray, lo: float, hi: float) -> float:
    """Length in seconds of the union of ns intervals clipped to [lo, hi]."""
    u = _merge(_clip(np.asarray(iv, np.float64), lo, hi))
    return float(np.sum(u[:, 1] - u[:, 0])) / 1e9 if u.size else 0.0


def gaps(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """The (start, end) ns holes of the union of ``iv`` inside [lo, hi]."""
    u = _merge(_clip(np.asarray(iv, np.float64), lo, hi))
    edges = np.concatenate([[lo], u.ravel(), [hi]]).reshape(-1, 2)
    return edges[edges[:, 1] > edges[:, 0]]


class DeviceTrace:
    """Ops, modules and host markers of one profiler trace."""

    def __init__(self, planes: dict, modules: list, markers: dict):
        # planes: device name -> list of (op name, start_ns, end_ns)
        self.planes = planes
        self.modules = modules          # (device, name, start_ns, end_ns)
        self.markers = markers          # host event name -> first start_ns

    @classmethod
    def from_file(cls, path) -> "DeviceTrace":
        from jax.profiler import ProfileData
        return cls.from_profile(ProfileData.from_file(str(path)))

    @classmethod
    def from_profile(cls, pd) -> "DeviceTrace":
        planes: dict[str, list] = {}
        modules: list = []
        markers: dict[str, float] = {}
        hosts = []
        for pl in pd.planes:
            if pl.name.startswith("/device:") and "CUSTOM" not in pl.name:
                for ln in pl.lines:
                    if ln.name == "XLA Ops":
                        planes[pl.name] = [
                            (_op_name(ev.name), ev.start_ns,
                             ev.start_ns + ev.duration_ns) for ev in ln.events]
                    elif ln.name == "XLA Modules":
                        modules += [(pl.name, ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns)
                                    for ev in ln.events]
            elif pl.name.startswith("/host"):
                hosts.append(pl)
        for pl in hosts:
            for ln in pl.lines:
                for ev in ln.events:
                    markers.setdefault(ev.name, ev.start_ns)
        if not planes:
            cls._host_ops(hosts, planes, modules)
        return cls(planes, modules, markers)

    @staticmethod
    def _host_ops(hosts, planes: dict, modules: list) -> None:
        """CPU: ops run on host threads as events with ``hlo_op`` stats;
        one "device", each module spanned by the union of its ops."""
        ops = []
        for pl in hosts:
            for ln in pl.lines:
                for ev in ln.events:
                    stats = dict(ev.stats)
                    if "hlo_op" in stats:
                        ops.append((str(stats.get("hlo_module", "?")),
                                    _op_name(str(stats["hlo_op"])),
                                    ev.start_ns, ev.start_ns + ev.duration_ns))
        if not ops:
            return
        planes["/host:CPU"] = [(o, s, e) for _, o, s, e in ops]
        by_mod: dict[str, list] = {}
        for mod, _, s, e in ops:
            by_mod.setdefault(mod, []).append((s, e))
        for mod, iv in by_mod.items():
            for s, e in _merge(np.asarray(iv, np.float64)):
                modules.append(("/host:CPU", mod, s, e))

    # ------------------------------------------------------------ readings
    def _iv(self, dev: str) -> np.ndarray:
        return np.asarray([(s, e) for _, s, e in self.planes[dev]],
                          np.float64).reshape(-1, 2)

    def busy_seconds(self, lo: float, hi: float) -> float:
        """Seconds in [lo, hi] (ns) in which an op ran, averaged over the
        device planes."""
        if not self.planes:
            return 0.0
        return float(np.mean([union_seconds(self._iv(d), lo, hi)
                              for d in self.planes]))

    def module_seconds(self, prefix: str, lo: float, hi: float) -> float:
        """Summed device seconds of module events named ``prefix...`` that
        start inside [lo, hi]."""
        return sum(e - s for _, name, s, e in self.modules
                   if name.startswith(prefix) and lo <= s <= hi) / 1e9

    def top_ops(self, lo: float, hi: float, n: int = 10) -> list:
        """[[op name, device seconds], ...], most time first, over every
        device plane."""
        tot: dict[str, float] = {}
        for ops in self.planes.values():
            for name, s, e in ops:
                if lo <= s <= hi:
                    tot[name] = tot.get(name, 0.0) + (min(e, hi) - s) / 1e9
        return [[k, v] for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, lo: float, hi: float) -> np.ndarray:
        """Holes of the first device plane's busy union inside [lo, hi]."""
        if not self.planes:
            return np.zeros((0, 2))
        return gaps(self._iv(sorted(self.planes)[0]), lo, hi)
