"""Unified observability plane.

One :class:`Obs` object bundles the three planes every layer shares:

* :class:`~repro.obs.metrics.MetricsRegistry` — counters, gauges,
  log-bucket latency histograms; Prometheus text exposition + JSON
  snapshot export; the shared :data:`DRIVER_STAT_SCHEMA` behind every
  engine's ``stats`` mapping.
* :class:`~repro.obs.trace.Tracer` — per-tick structured trace events
  (JSONL ring buffer + optional file sink) emitted by every planner
  with reasons.
* :class:`~repro.obs.probe.RecallProbe` — sampled live-recall probe
  (built by the serving engine on demand via :meth:`Obs.make_probe`).

Drivers default-construct an ``Obs()`` when none is injected; the
serving engine reuses its index's plane so one exposition covers driver
internals and request spans.  ``Obs(enabled=False)`` keeps the stats
mapping (the drivers need it) but turns tracing and span recording into
no-ops — that delta is what the figserve obs-overhead row measures.
"""
from __future__ import annotations

import time
from contextlib import contextmanager
from typing import Callable, Optional

from .metrics import (DRIVER_STAT_SCHEMA, GAUGE_STAT_KEYS, Counter, Gauge,
                      Histogram, MetricsRegistry, StatsMap, parse_exposition,
                      required_series)
from .probe import RecallProbe
from .trace import Tracer

__all__ = [
    "Obs", "MetricsRegistry", "Tracer", "RecallProbe", "Counter", "Gauge",
    "Histogram", "StatsMap", "DRIVER_STAT_SCHEMA", "GAUGE_STAT_KEYS",
    "parse_exposition", "required_series",
]


class Obs:
    """Bundle of metrics registry + tracer (+ profiler hook)."""

    def __init__(self, *, enabled: bool = True,
                 trace_capacity: int = 4096,
                 trace_path: Optional[str] = None,
                 clock: Callable[[], float] = time.perf_counter):
        self.enabled = enabled
        self.registry = MetricsRegistry()
        self.tracer = Tracer(capacity=trace_capacity, path=trace_path,
                             clock=clock, enabled=enabled)

    # ---- construction passthrough ------------------------------------

    def driver_stats(self, prefix: str = "index") -> StatsMap:
        """The shared-schema stats mapping a driver exposes as
        ``.stats`` — registered so every key rides the exposition."""
        return self.registry.stats_map(prefix, DRIVER_STAT_SCHEMA)

    def counter(self, name: str) -> Counter:
        return self.registry.counter(name)

    def gauge(self, name: str) -> Gauge:
        return self.registry.gauge(name)

    def histogram(self, name: str, **kw) -> Histogram:
        return self.registry.histogram(name, **kw)

    def make_probe(self, index, **kw) -> RecallProbe:
        return RecallProbe(index, self.registry, **kw)

    # ---- tracing ------------------------------------------------------

    def emit(self, kind: str, **fields) -> None:
        self.tracer.emit(kind, **fields)

    def events(self, kind: Optional[str] = None):
        return self.tracer.events(kind)

    # ---- export -------------------------------------------------------

    def snapshot(self):
        return self.registry.snapshot()

    def to_prometheus(self) -> str:
        return self.registry.to_prometheus()

    # ---- device profiler hook -----------------------------------------

    @contextmanager
    def profile(self, trace_dir: Optional[str]):
        """Wrap a block in a ``jax.profiler`` trace written under
        ``trace_dir`` (``None``: run the block untraced).  A trace that
        was asked for and cannot start raises — a run that silently
        carries on untraced would be read as a traced one."""
        if not trace_dir:
            yield
            return
        import jax
        jax.profiler.start_trace(str(trace_dir))
        try:
            yield
        finally:
            jax.profiler.stop_trace()
