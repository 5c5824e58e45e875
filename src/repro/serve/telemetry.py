"""Spans and compile counters of the served path (DESIGN.md §7).

``span(name, **ids)`` marks one stage of the served path in the JAX
profiler's trace: a host event named ``<thread>/<name>`` whose metadata
carries ``ids`` (``wave=``, and ``ticket=`` on request-level spans), on
the clock of the device's own events, so every idle stretch of the
device can be put down to what each host thread was doing.  With no
profiler session active the annotation records nothing and costs about
a microsecond.  The profiler is the only exporter; counts go into the
dicts ``RetrievalEngine.maintenance_stats`` already surfaces.

``compile_stats`` is the one process-wide count, because JAX reports
compilation to process-wide listeners: programs lowered (every new
shape of a jit or an eager op, whether or not the persistent cache then
holds it) and the milliseconds spent lowering and compiling.
"""

from __future__ import annotations

import threading
from typing import Dict

import jax

_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE = "/jax/core/compile/backend_compile_duration"

_compiles: Dict[str, float] = {"jit_compiles": 0, "jit_compile_ms": 0.0}
_compiles_lock = threading.Lock()


def span(name: str, **ids) -> jax.profiler.TraceAnnotation:
    """A profiler span named after the calling thread and ``name``; use
    it as a context manager.  ``set_metadata`` on the entered span adds
    an id that is known only inside it (a ticket)."""
    return jax.profiler.TraceAnnotation(
        f"{threading.current_thread().name}/{name}", **ids)


def phase_span(name: str) -> jax.profiler.TraceAnnotation:
    """A profiler span named ``name`` as given, for a phase that runs on
    no thread of the served path (``build/esam`` while an index builds)."""
    return jax.profiler.TraceAnnotation(name)


def _on_duration(event: str, duration_s: float, **_) -> None:
    if event not in (_LOWER, _COMPILE):
        return
    with _compiles_lock:
        if event == _LOWER:
            _compiles["jit_compiles"] += 1
        _compiles["jit_compile_ms"] += duration_s * 1e3


def compile_stats() -> Dict[str, float]:
    """``jit_compiles`` and ``jit_compile_ms`` since the process began
    serving (the listener is registered on this module's import)."""
    with _compiles_lock:
        return dict(_compiles)


jax.monitoring.register_event_duration_secs_listener(_on_duration)
