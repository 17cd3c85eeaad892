"""Compile counting from JAX's own monitoring events (DESIGN.md section 9).

JAX records ``/jax/core/compile/backend_compile_duration`` around every
backend compile of a program — a jitted function's first call at a new
signature, an eager op at a new shape — whether the compiler ran or the
persistent compilation cache supplied the executable, and
``/jax/compilation_cache/cache_hits`` when it was the cache. One listener
on each turns them into:

* the counters ``compiles`` and ``compile_cache_hits`` and the histogram
  ``compile_s`` of the registry's ``compile`` component;
* a ``compile`` span, recorded when it ends and nested under the open
  span path of the thread that compiled (``step/launch/compile``);
* a per-thread count, :func:`thread_compiles`, whose difference across a
  session step is that step's ``compiles``.

The listeners are registered once, when ``repro.obs`` is imported, and run
only while JAX compiles.
"""
from __future__ import annotations

import threading

from jax import monitoring

from . import tracing
from .lifecycle import on_reset
from .registry import REGISTRY

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

_tls = threading.local()
_metrics = REGISTRY.metric_set("compile", keep=True)
_installed = False


def thread_compiles() -> int:
    """Compiles recorded on the calling thread since it started."""
    return getattr(_tls, "compiles", 0)


def _on_duration(event: str, duration_secs: float, **kwargs) -> None:
    if event != BACKEND_COMPILE_EVENT:
        return
    _tls.compiles = thread_compiles() + 1
    _metrics.count("compiles")
    _metrics.observe("compile_s", duration_secs)
    tracing.record_span("compile", duration_secs,
                        fun=str(kwargs.get("fun_name", "")))


def _on_event(event: str, **kwargs) -> None:
    if event == CACHE_HIT_EVENT:
        _metrics.count("compile_cache_hits")


def _fresh_metrics() -> None:
    # obs.reset() drops every live metric set; count into a new one
    global _metrics
    _metrics = REGISTRY.metric_set("compile", keep=True)


def install() -> None:
    """Register the listeners with ``jax.monitoring`` (idempotent)."""
    global _installed
    if _installed:
        return
    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)
    on_reset(_fresh_metrics)
    _installed = True
