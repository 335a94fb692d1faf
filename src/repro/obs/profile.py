"""Op-level profiling hooks for ``repro.ops.dispatch``.

The dispatcher is the one chokepoint every backend call crosses, so this is
where per-(op, backend, shape-bucket) wall time becomes observable.  The
registry stays dependency-free: it calls :func:`record` after each dispatch
and whoever wants the numbers (the serving engine, a bench) registers a
hook.  With no hooks installed the cost is one ``if not _HOOKS`` check.

Compiles: :func:`install_compile_listener` (called where JAX is already
loaded, when the first accelerator backend resolves) registers one
``jax.monitoring`` listener for backend compiles.  Each compile adds to a
``compiles`` attribute on the compiling thread's current span and is fanned
out to the compile hooks (the engine counts ``jit_compiles_total``).

Shape buckets: problem "size" (op-specific, see ``repro.ops``) collapses to
its power-of-two ceiling — ``le_2^12`` means ``2^11 < size <= 2^12`` — so
the Prometheus label space stays bounded (~20 buckets) while still
separating the tiny dispatches the numpy oracle should win from the large
ones that should have promoted to an accelerator backend.
"""
from __future__ import annotations

import threading
from typing import Callable

from . import trace as _trace

__all__ = ["add_hook", "remove_hook", "record", "shape_bucket", "hooks",
           "add_compile_hook", "remove_compile_hook",
           "install_compile_listener"]

# fn(op: str, backend: str, size: int | None, seconds: float)
_HOOKS: list[Callable] = []
_LOCK = threading.Lock()

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# fn(seconds: float), once per backend compile in this process
_COMPILE_HOOKS: list[Callable] = []
_LISTENING = False


def add_hook(fn: Callable) -> Callable:
    """Register a dispatch observer; returns ``fn`` for symmetry."""
    with _LOCK:
        if fn not in _HOOKS:
            _HOOKS.append(fn)
    return fn


def remove_hook(fn: Callable) -> None:
    with _LOCK:
        try:
            _HOOKS.remove(fn)
        except ValueError:
            pass


def hooks() -> tuple:
    return tuple(_HOOKS)


def record(op: str, backend: str, size: int | None, seconds: float) -> None:
    """Fan one dispatch observation out to every hook.  Hook exceptions are
    swallowed: telemetry must never fail the computation it observes."""
    for fn in tuple(_HOOKS):
        try:
            fn(op, backend, size, seconds)
        except Exception:  # noqa: BLE001 - observability is best-effort
            pass


def add_compile_hook(fn: Callable) -> Callable:
    """Register a backend-compile observer; returns ``fn``."""
    with _LOCK:
        if fn not in _COMPILE_HOOKS:
            _COMPILE_HOOKS.append(fn)
    return fn


def remove_compile_hook(fn: Callable) -> None:
    with _LOCK:
        try:
            _COMPILE_HOOKS.remove(fn)
        except ValueError:
            pass


def _on_jax_event(event: str, seconds: float, **_) -> None:
    if event != _COMPILE_EVENT:
        return
    # JAX compiles on the thread that called the jitted function, so the
    # current span is the step that caused the compile
    sp = _trace.current_span()
    if sp:
        sp.set_attr("compiles", (sp.attrs or {}).get("compiles", 0) + 1)
    for fn in tuple(_COMPILE_HOOKS):
        try:
            fn(seconds)
        except Exception:  # noqa: BLE001 - observability is best-effort
            pass


def install_compile_listener() -> None:
    """Register the compile listener with ``jax.monitoring`` (once per
    process).  Imports JAX: call it only where JAX is already loaded."""
    global _LISTENING
    with _LOCK:
        if _LISTENING:
            return
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(_on_jax_event)
        _LISTENING = True


def shape_bucket(size: int | None) -> str:
    """Power-of-two ceiling label for a problem size (``le_2^b``)."""
    if size is None:
        return "none"
    size = int(size)
    if size <= 1:
        return "le_2^0"
    return f"le_2^{(size - 1).bit_length()}"
