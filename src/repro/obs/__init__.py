"""Unified observability layer (DESIGN.md §14).

One ``Observability`` bundle ties the instruments together:

- ``tracer``/``recorder`` — explicit-parent span tracing into a
  bounded flight-recorder ring (obs/trace.py), threaded through the
  full query lifecycle, plan builds/patches, deltas and XLA compiles.
- ``registry`` — the typed metrics registry (obs/metrics.py) that
  cross-cutting counters/gauges/histograms report into; per-scheduler
  ``ServeMetrics`` keep their OWN registries (reconciliation is
  per-scheduler) and the gateway scrape endpoint merges all of them.

Off by default: nothing constructs a bundle unless
``EngineConfig(observe=True)`` / ``Session.observe()`` /
``SlotScheduler(obs=...)`` asks, and every hot-path hook is a single
``is None`` branch.  What is always on costs nothing per iteration:
the named scopes of the device pass (compile-time metadata) and one
profiler annotation per coarse phase (``obs.trace.phase``).
"""
from __future__ import annotations

import itertools
import os
import threading
import weakref
from typing import Optional

import jax

from .comm import CommBreakdown, measure_plan, vs_model
from .metrics import (DEFAULT_BUCKETS, Counter, Gauge, Histogram,
                      MetricsRegistry, render_prometheus)
from .trace import (TRACE_SCHEMA_VERSION, FlightRecorder, QuerySpans,
                    Span, SpanRecord, Tracer, now_ns, phase)

__all__ = [
    "Observability", "Tracer", "Span", "SpanRecord", "QuerySpans",
    "FlightRecorder", "MetricsRegistry", "Counter", "Gauge",
    "Histogram", "render_prometheus", "DEFAULT_BUCKETS",
    "CommBreakdown", "measure_plan", "vs_model",
    "TRACE_SCHEMA_VERSION", "now_ns", "phase",
]

# JAX reports each backend compile as a duration event; one listener
# per process fans it out to the live bundles (held weakly).
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_COMPILE_OBSERVERS: "weakref.WeakSet" = weakref.WeakSet()
_compile_listener_lock = threading.Lock()
_compile_listener_on = False


def _on_duration(event: str, duration_s: float, **kwargs) -> None:
    if event != COMPILE_EVENT:
        return
    for obs in list(_COMPILE_OBSERVERS):
        obs.compile_event(duration_s, fun=kwargs.get("fun_name"))


def _watch_compiles(obs) -> None:
    global _compile_listener_on
    with _compile_listener_lock:
        if not _compile_listener_on:
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            _compile_listener_on = True
    _COMPILE_OBSERVERS.add(obs)


class Observability:
    """The bundle a Session/SlotScheduler/Gateway reports through."""

    def __init__(self, *, capacity: int = 8192,
                 dump_dir: Optional[str] = None, clock=None):
        kw = {} if clock is None else {"clock": clock}
        self.recorder = FlightRecorder(capacity)
        self.tracer = Tracer(self.recorder, **kw)
        self.registry = MetricsRegistry()
        self.dump_dir = dump_dir
        self._dump_seq = itertools.count(1)
        self._dump_lock = threading.Lock()
        # Plan build/hit/patch events fan in from core/plan.py (weak
        # registration: dropping the bundle detaches it).
        from ..core import plan as _plan
        self._plan_mod = _plan
        _plan.add_plan_observer(self)
        _watch_compiles(self)

    # ------------------------------------------------------------- events
    def plan_event(self, name: str, **attrs) -> None:
        """Callback target for ``core.plan.notify_plan_event``."""
        self.tracer.event(name, trace="plan", **attrs)
        self.registry.counter("plan_events_total",
                              "plan build/hit/patch events",
                              event=name).inc()

    def compile_event(self, duration_s: float, fun=None) -> None:
        """Callback target for JAX's backend-compile duration event."""
        self.tracer.event("compile", trace="plan", fun=fun,
                          duration_s=duration_s)
        self.registry.counter("xla_compiles_total",
                              "XLA backend compiles").inc()

    # -------------------------------------------------------------- dumps
    def dump(self, path: str) -> str:
        """Flight-recorder JSONL on demand."""
        return self.recorder.dump(path)

    def crash_dump(self, reason: str) -> Optional[str]:
        """Automatic dump on quarantine/stepper failure (PR 6's
        resilience path).  Records a ``crash_dump`` event either way;
        writes a file only when ``dump_dir`` is configured."""
        self.registry.counter("crash_dumps_total",
                              "automatic flight-recorder dumps").inc()
        if self.dump_dir is None:
            self.tracer.event("crash_dump", trace="crash",
                              reason=reason, path=None)
            return None
        with self._dump_lock:
            seq = next(self._dump_seq)
        path = os.path.join(self.dump_dir, f"flight-{seq:04d}.jsonl")
        self.tracer.event("crash_dump", trace="crash", reason=reason,
                          path=path)
        try:
            os.makedirs(self.dump_dir, exist_ok=True)
            return self.recorder.dump(path)
        except OSError:
            return None

    # ------------------------------------------------------------ exports
    def prometheus(self) -> str:
        return self.registry.prometheus_text()

    def stats(self) -> dict:
        return {"metrics": self.registry.to_json(),
                "flight_recorder": {
                    "held": len(self.recorder),
                    "recorded": self.recorder.recorded,
                    "dropped": self.recorder.dropped,
                    "capacity": self.recorder.capacity}}

    def close(self) -> None:
        self._plan_mod.remove_plan_observer(self)
        _COMPILE_OBSERVERS.discard(self)
