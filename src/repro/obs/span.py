"""Program spans: timed blocks that also land in a profiler trace.

``Obs.span(name)`` opens one of the names in :data:`SPAN_NAMES`.  While
the plane is enabled a span enters ``jax.profiler.TraceAnnotation``
``"ubis." + name`` (so it sits in any profiler trace on the device ops'
clock, nested inside whatever the caller annotated) and records its
duration into the registry histogram ``span_<name>_seconds`` (dots
become underscores).  A span never reads from the device, never blocks
and writes nothing to the tracer's ring.

``sync.<site>`` spans wrap the driver's blocking device->host reads
(``Obs.fetch``); their histogram counts are the host-sync counts.
"""
from __future__ import annotations

from typing import Callable, Optional

from jax.profiler import TraceAnnotation

from .metrics import Histogram

PREFIX = "ubis."

#: Every span a program may open.  Driver tick and its phases (in the
#: order a tick runs them), driver updates and search, the driver's
#: device->host read sites, then the serving engine's lanes.
SPAN_NAMES = (
    "tick", "tick.execute", "tick.drain", "tick.mark", "tick.gc",
    "tick.retrain", "tick.tier",
    "insert", "delete", "search.dispatch", "search.collect",
    "sync.round", "sync.drain", "sync.detect", "sync.gc", "sync.retrain",
    "sync.insert", "sync.delete", "sync.search",
    "serve.search", "serve.flush", "serve.overlap",
)


def histogram_name(name: str) -> str:
    return "span_" + name.replace(".", "_") + "_seconds"


class Span:
    """One timed block; ``seconds`` holds its duration once it exits.

    With ``hist`` None (the plane disabled) it only times: the drivers'
    ``*_time`` stats and result ``seconds`` read it either way."""

    __slots__ = ("_clock", "_hist", "_label", "_ann", "_t0", "seconds")

    def __init__(self, clock: Callable[[], float],
                 hist: Optional[Histogram] = None, label: str = ""):
        self._clock = clock
        self._hist = hist
        self._label = label
        self.seconds = 0.0

    def __enter__(self) -> "Span":
        if self._hist is not None:
            self._ann = TraceAnnotation(self._label)
            self._ann.__enter__()
        self._t0 = self._clock()
        return self

    def __exit__(self, *exc) -> bool:
        self.seconds = self._clock() - self._t0
        if self._hist is not None:
            self._hist.record(self.seconds)
            self._ann.__exit__(*exc)
        return False
