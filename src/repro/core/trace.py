"""The program's spans and counters.

``span(name, **stats)`` marks one phase of the data plane. Inside a JAX
profiler trace (``jax.profiler.trace(dir)``) it is a host event
``repro.<name>`` with each keyword as an event stat, on the same clock as
the device's ops; with no profiler running it costs about a microsecond.
Every span also counts into process-wide counters: ``<name>.n`` and, where
``bytes=`` is given, ``<name>.bytes``. ``counters()`` returns them with no
profiler needed; ``reset()`` clears them.

JAX's compile phases become spans too. Importing this module registers
listeners on ``jax.monitoring`` that open ``repro.compile`` (stats ``event``:
``trace``, ``lower`` or ``backend``, and ``fun``) when a phase starts and
close it when the phase ends, on the same thread. They count
``compile.<event>.n`` and ``compile.<event>.s``, the latter only for a phase
that no other compile phase on its thread encloses, so nested tracing is
counted once and the three sum to the thread's compile seconds; and the
persistent cache's ``compile.cache_hits`` and ``compile.cache_misses``.

Span names start with ``repro.`` and with nothing else: a span named like
the benchmark's own annotations (``window``, ``stage.``, ``unit.``,
``ingest.batch``) would be taken for one of them.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict

import jax
from jax.profiler import TraceAnnotation

PREFIX = "repro."
COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "compile.cache_hits",
    "/jax/compilation_cache/cache_misses": "compile.cache_misses",
}

_lock = threading.Lock()
_counters: Dict[str, float] = {}
_local = threading.local()          # this thread's open compile spans


def _add(key: str, value: float = 1) -> None:
    with _lock:
        _counters[key] = _counters.get(key, 0) + value


@contextlib.contextmanager
def span(name: str, **stats):
    """A ``repro.<name>`` span around the block. Give only stats known when
    it opens (sizes from ``stat()``, ``nbytes``, ``len``); ``bytes`` also
    counts into ``<name>.bytes``."""
    _add(name + ".n")
    if "bytes" in stats:
        _add(name + ".bytes", int(stats["bytes"]))
    with TraceAnnotation(PREFIX + name, **stats):
        yield


def counters() -> Dict[str, float]:
    """A copy of every counter so far."""
    with _lock:
        return dict(_counters)


def reset() -> None:
    with _lock:
        _counters.clear()


def _open_compiles() -> list:
    if not hasattr(_local, "stack"):
        _local.stack = []
    return _local.stack


def _compile_started(event, value, **kw):
    kind = COMPILE_EVENTS.get(event)
    if kind is None:
        return
    ann = TraceAnnotation(PREFIX + "compile", event=kind,
                          fun=str(kw.get("fun_name", "")))
    ann.__enter__()
    _open_compiles().append((kind, ann))


def _compile_ended(event, start, end, **_):
    kind = COMPILE_EVENTS.get(event)
    stack = _open_compiles()
    if kind is None or not stack or stack[-1][0] != kind:
        return                      # a phase that began before the import
    stack.pop()[1].__exit__(None, None, None)
    _add(f"compile.{kind}.n")
    if not stack:
        _add(f"compile.{kind}.s", end - start)


def _cache_event(event, **_):
    if event in CACHE_EVENTS:
        _add(CACHE_EVENTS[event])


jax.monitoring.register_scalar_listener(_compile_started)
jax.monitoring.register_event_time_span_listener(_compile_ended)
jax.monitoring.register_event_listener(_cache_event)
