"""Backend registry + dispatch for the canonical op surface.

Every hot-path primitive in this repo exists (or existed) several times:
a numpy oracle, a jitted dense-jnp variant, and a Pallas TPU kernel.  The
registry makes that structure explicit instead of ad hoc: each *op* name
maps to up to three registered *backends*,

    numpy   — float64 host oracle (ground truth; fastest for small inputs)
    xla     — jitted dense jnp (the dry-run / CPU-compiled path)
    pallas  — the TPU kernel (interpret-mode on CPU)

and callers go through :func:`dispatch`, never through a kernel module
directly.  An explicit ``backend=`` argument wins; otherwise
:func:`select_backend` applies one rule:

  1. the innermost :func:`backend_override` context, if one is active
     (tests);
  2. on a TPU host, ``pallas`` (the kernels are written for it; the
     integral-image kernels run compensated two-float scans by default —
     plain f32 images lose the S2 - S1^2/S0 cancellation on signals with
     a DC offset);
  3. ``xla`` where the op has an ``XLA_SIZE_THRESHOLD`` and the problem
     reaches it;
  4. otherwise the numpy oracle.

Precision-critical ops (``XLA_SIZE_THRESHOLD[op] is None``) therefore
never size-promote to the float32 accelerator backends off a TPU, and
interpret-mode Pallas is never selected on the CPU — there it is a
correctness path, not a fast one.  Selection reads neither the
environment nor any file: it depends on the platform and the input only.

Implementations are registered as *factories* resolved on first use, so
importing ``repro.ops`` pulls in neither jax nor the kernel packages and
the registry stays import-cycle free (backends import ``repro.core`` /
``repro.kernels`` lazily).
"""
from __future__ import annotations

import contextlib
import functools
import os
import threading
import time
from typing import Callable

from repro.obs import profile as _profile
from repro.obs import trace as _trace

__all__ = [
    "OPS", "BACKENDS", "BackendError", "register",
    "available_backends", "select_backend", "resolve", "dispatch",
    "backend_override", "snapshot", "device_info",
]

OPS = ("sat_moments", "delta_sat", "fitting_loss", "fitting_loss_batched",
       "hist_split", "streaming_compress")
BACKENDS = ("numpy", "xla", "pallas")

# auto-selection crossover (problem "size" is op-specific, computed by the
# public wrappers in __init__): below -> numpy oracle, above -> jitted xla.
# None = NEVER size-promote: sat_moments, delta_sat, hist_split and
# streaming_compress feed the variance identity S2 - S1^2/S0, which is
# catastrophically cancellation-sensitive — their float32 xla/pallas
# backends are only used when explicitly pinned (argument/override) or on
# TPU, where the integral-image backends default to their compensated
# configs.  The two loss ops sum non-negative terms, so f32 promotion is
# safe.
XLA_SIZE_THRESHOLD = {
    "sat_moments": None,               # precision-critical (f64 oracle)
    "delta_sat": None,                 # patches the same integral images
    "fitting_loss": 1 << 16,           # blocks * leaves
    "fitting_loss_batched": 1 << 16,   # trees * blocks * leaves
    "hist_split": None,                # precision-critical (f64 oracle)
    "streaming_compress": None,        # rebuilds prefix stats (opt1 feed)
}


class BackendError(KeyError):
    """Unknown op/backend pair requested from the registry."""


_FACTORIES: dict[tuple[str, str], Callable[[], Callable]] = {}
_RESOLVED: dict[tuple[str, str], Callable] = {}
_RESOLVE_LOCK = threading.Lock()
_OVERRIDE: list[str] = []   # backend_override stack (innermost last)


def register(op: str, backend: str):
    """Decorator: register a lazy factory for (op, backend)."""
    if op not in OPS:
        raise BackendError(f"unknown op {op!r}; ops are {OPS}")
    if backend not in BACKENDS:
        raise BackendError(f"unknown backend {backend!r}; backends are {BACKENDS}")

    def deco(factory: Callable[[], Callable]) -> Callable[[], Callable]:
        _FACTORIES[(op, backend)] = factory
        return factory

    return deco


def available_backends(op: str) -> tuple[str, ...]:
    return tuple(b for b in BACKENDS if (op, b) in _FACTORIES)


@functools.cache
def _platform_is_tpu() -> bool:
    # cached: the platform cannot change mid-process, and the first
    # jax.default_backend() call forces XLA client init — pure-numpy hot
    # paths (PrefixStats.build, per-node hist_split) must pay it only once
    import jax
    return jax.default_backend() == "tpu"


def device_info() -> dict:
    """The device this process computes on, as JAX reports it, plus the
    accelerator device files the process holds open (Linux): two processes
    that each own one chip show different files even where each numbers
    its only device 0."""
    import jax
    d = jax.devices()[0]
    files = set()
    fd_dir = "/proc/self/fd"
    if os.path.isdir(fd_dir):
        for fd in os.listdir(fd_dir):
            try:
                target = os.readlink(os.path.join(fd_dir, fd))
            except OSError:
                continue
            if target.startswith(("/dev/accel", "/dev/vfio/")) \
                    and target != "/dev/vfio/vfio":
                files.add(target)
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices()), "id": int(d.id),
            "coords": list(getattr(d, "coords", ()) or ()),
            "device_files": sorted(files)}


def select_backend(op: str, size: int | None = None) -> str:
    """The backend :func:`dispatch` would use for ``op`` at ``size``."""
    if op not in OPS:
        raise BackendError(f"unknown op {op!r}; ops are {OPS}")
    if _OVERRIDE:
        return _OVERRIDE[-1]
    if _platform_is_tpu():
        return "pallas"
    thr = XLA_SIZE_THRESHOLD[op]
    if thr is not None and size is not None and size >= thr:
        return "xla"
    return "numpy"


def resolve(op: str, backend: str | None = None,
            size: int | None = None) -> tuple[str, Callable]:
    """(backend name, callable) after selection + lazy factory resolution."""
    name = backend or select_backend(op, size)
    key = (op, name)
    fn = _RESOLVED.get(key)
    if fn is None:
        with _RESOLVE_LOCK:
            fn = _RESOLVED.get(key)
            if fn is None:
                factory = _FACTORIES.get(key)
                if factory is None:
                    raise BackendError(
                        f"no {name!r} backend registered for op {op!r}; "
                        f"available: {available_backends(op)}")
                fn = _RESOLVED[key] = factory()
                if name != "numpy":
                    # the accelerator factories have loaded JAX: count its
                    # compiles from here on
                    _profile.install_compile_listener()
    return name, fn


def dispatch(op: str, *args, backend: str | None = None,
             size: int | None = None, **kw):
    name, fn = resolve(op, backend, size)
    # observability seam: every backend call crosses this line, so this is
    # where per-(op, backend, shape) wall time becomes a span + a profile
    # sample.  Outside a trace the span is the NOOP singleton and with no
    # hooks installed the profile branch is one falsy check — the pure-
    # library hot paths (per-node hist_split) pay two perf_counter reads.
    # A live span is made current, so the backend can split it
    # (``dispatch.*``) and a compile lands on it.
    span = _trace.TRACER.child_span("ops.dispatch")
    t0 = time.perf_counter()
    try:
        if span:
            with _trace.TRACER.attach(span):
                return fn(*args, **kw)
        return fn(*args, **kw)
    finally:
        dt = time.perf_counter() - t0
        if span:
            span.set_attr("op", op)
            span.set_attr("backend", name)
            span.set_attr("size", size)
            span.set_attr("shape_bucket", _profile.shape_bucket(size))
            span.end()
        if _profile._HOOKS:
            _profile.record(op, name, size, dt)


@contextlib.contextmanager
def backend_override(backend: str):
    """Force every dispatch inside the context onto one backend (tests)."""
    if backend not in BACKENDS:
        raise BackendError(f"unknown backend {backend!r}; backends are {BACKENDS}")
    _OVERRIDE.append(backend)
    try:
        yield
    finally:
        _OVERRIDE.pop()


def snapshot() -> dict:
    """Selection state per op — surfaced in ``/v1/stats`` and bench output.

    ``selected`` is the small-problem choice (size-unaware); large problems
    auto-promote to ``xla`` at ``xla_threshold`` unless pinned.
    """
    out = {}
    for op in OPS:
        out[op] = {
            "available": list(available_backends(op)),
            "selected": select_backend(op),
            "xla_threshold": XLA_SIZE_THRESHOLD[op],
        }
    return out
