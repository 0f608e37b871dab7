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
* :meth:`Obs.span` — a named block (:data:`SPAN_NAMES`) timed into a
  ``span_<name>_seconds`` histogram and annotated for ``jax.profiler``;
  :meth:`Obs.fetch` is the driver's one device->host read, inside a
  ``sync.<site>`` span.

Drivers default-construct an ``Obs()`` when none is injected; the
serving engine reuses its index's plane so one exposition covers driver
internals and request spans.  ``Obs(enabled=False)`` keeps the stats
mapping (the drivers need it) but turns tracing and span recording into
no-ops — that delta is what the figserve obs-overhead row measures.  Its
spans still time their block (the stats' ``*_time`` keys read them) but
record nothing and annotate nothing.
"""
from __future__ import annotations

import time
from contextlib import contextmanager
from typing import Callable, Optional

import jax

from .metrics import (DRIVER_STAT_SCHEMA, GAUGE_STAT_KEYS, Counter, Gauge,
                      Histogram, MetricsRegistry, StatsMap, parse_exposition,
                      required_series)
from .probe import RecallProbe
from .span import PREFIX as SPAN_PREFIX, SPAN_NAMES, Span, histogram_name
from .trace import Tracer

__all__ = [
    "Obs", "MetricsRegistry", "Tracer", "RecallProbe", "Counter", "Gauge",
    "Histogram", "StatsMap", "Span", "DRIVER_STAT_SCHEMA", "GAUGE_STAT_KEYS",
    "SPAN_NAMES", "SPAN_PREFIX", "parse_exposition", "required_series",
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
        self._span_hists: dict = {}

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

    # ---- spans and device reads ---------------------------------------

    def span(self, name: str) -> Span:
        """A context manager timing one block of :data:`SPAN_NAMES`;
        its ``seconds`` hold the duration once it exits."""
        if not self.enabled:
            return Span(self.tracer.clock)
        hist = self._span_hists.get(name)
        if hist is None:
            if name not in SPAN_NAMES:
                raise ValueError(f"unknown span {name!r} (not in "
                                 "repro.obs.SPAN_NAMES)")
            hist = self._span_hists[name] = self.registry.histogram(
                histogram_name(name))
        return Span(self.tracer.clock, hist, SPAN_PREFIX + name)

    def fetch(self, site: str, *arrays):
        """The one blocking device->host read: ``jax.device_get`` of
        ``arrays`` together, inside span ``sync.<site>``.  Returns the
        host value of a single array, else a tuple."""
        with self.span("sync." + site):
            out = jax.device_get(arrays)
        return out[0] if len(arrays) == 1 else out

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
        jax.profiler.start_trace(str(trace_dir))
        try:
            yield
        finally:
            jax.profiler.stop_trace()
