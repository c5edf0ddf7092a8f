"""Unified observability layer (DESIGN.md §17).

One subsystem, three surfaces:

  * ``obs.metrics`` — the typed metrics registry every layer reports
    into (counters / gauges / fixed-bucket histograms + scrape-time
    collectors + Prometheus text rendering).
  * ``obs.trace``   — per-query traces with thread-local ambient
    propagation, a recent-traces ring, and a slow-query log.
  * ``obs.profile`` — ``profile(site)`` contexts around jit dispatch,
    device sync, WAL fsync, and compaction.

``Observability`` bundles them per server: the ``QueryServer`` owns one
and folds every finished trace's spans into ``span_seconds{name=}``
histograms, which ``/metrics`` exposes. Layering contract: this package imports nothing from ``repro``
(stdlib only), so core, persist, and serve can all depend on it while
core stays importable without the serving stack. The layers that
import JAX install the profiler hooks (``trace.install_hooks``)."""
from __future__ import annotations

import threading
from typing import Optional

# NOTE: import the submodule without rebinding the package attribute —
# ``repro.obs.profile`` must stay the MODULE (consumers import it for
# record/bind_registry/set_enabled); the ``profile(site)`` context is
# ``repro.obs.profile.profile`` / the ``profile_site`` alias below
from . import profile as profile_mod
from .metrics import (AGE_BUCKETS_S, Counter, Gauge, Histogram,
                      LATENCY_BUCKETS_S, MetricsRegistry,
                      default_registry)
from .profile import bind_registry
from .profile import profile as profile_site
from .trace import (Span, Trace, TraceStore, active, attach, new_trace_id,
                    round_mark, round_scope, span)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "LATENCY_BUCKETS_S", "AGE_BUCKETS_S", "default_registry",
    "Span", "Trace", "TraceStore", "attach", "active", "span",
    "round_scope", "round_mark", "new_trace_id",
    "profile_site", "bind_registry", "Observability",
]

class Observability:
    """Per-server bundle: registry + trace store + enable switches.

    ``metrics_enabled`` gates collector registration and span-duration
    folding; ``tracing_enabled`` gates Trace creation at admission.
    Both off → the hot path sees only the thread-local null-context
    checks. ``observe_trace`` is called once per finished trace by the
    server and is the single source for the ``span_seconds`` and
    ``request_seconds`` histograms."""

    def __init__(self, metrics_enabled: bool = True,
                 tracing_enabled: bool = True,
                 registry: Optional[MetricsRegistry] = None,
                 trace_capacity: int = 256,
                 slow_query_s: float = 1.0,
                 slow_log_path: Optional[str] = None):
        self.metrics_enabled = bool(metrics_enabled)
        self.tracing_enabled = bool(tracing_enabled)
        self.registry = registry or MetricsRegistry()
        self.traces = TraceStore(capacity=trace_capacity,
                                 slow_s=slow_query_s,
                                 slow_log_path=slow_log_path)
        self._lock = threading.Lock()
        if self.metrics_enabled:
            profile_mod.set_enabled(True)
        self.span_seconds = self.registry.histogram(
            "span_seconds", "Per-stage span durations", ("name",))
        self.request_seconds = self.registry.histogram(
            "request_seconds", "End-to-end traced request wall",
            ("status",))

    @property
    def enabled(self) -> bool:
        return self.metrics_enabled or self.tracing_enabled

    def new_trace(self, trace_id: Optional[str] = None, *,
                  t0: Optional[float] = None,
                  held: bool = False) -> Optional[Trace]:
        """A fresh trace with its ``request`` root open from ``t0``
        (default now) when tracing is on; None (caller skips all trace
        work) otherwise. ``held``: the caller (the HTTP front end)
        finishes it, not the server."""
        if not self.tracing_enabled:
            return None
        return Trace(trace_id, t0=t0, held=held)

    def observe_trace(self, trace: Trace, status: str = "ok") -> None:
        """Finish + archive a trace, once: status stamped, root closed,
        spans folded into the per-stage histograms, ring/slow-log
        updated. A trace already finished is left as it is."""
        if trace.finished_s is not None:
            return
        trace.finish(status)
        if self.metrics_enabled:
            for sp in list(trace.spans):
                self.span_seconds.labels(name=sp.name).observe(sp.dur_s)
            self.request_seconds.labels(
                status=trace.status or "ok").observe(trace.wall_s)
        self.traces.add(trace)

    def render_prometheus(self) -> str:
        return self.registry.render_prometheus()
