"""Reduce a profiler trace to device time, idle gaps and kernel time.

``load(dir)`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote
under ``dir`` into plain ``Event`` rows.  The reductions take those rows,
so they are tested on hand-made events as well as on a recorded trace.

* Device events are the "XLA Ops" line of each ``/device:<kind>:<n>``
  plane; "XLA Modules" events give the program each op ran in.
* Host spans are the harness's own ``TraceAnnotation`` events, whose
  names start with ``bench.`` (see ``proxy.SPAN_PREFIX``).
* The window is the ``bench.window`` annotation: every reduction clips
  to it.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

WINDOW = "bench.window"


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load(trace_dir: str) -> list:
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(paths)}")
    data = ProfileData.from_file(paths[0])
    return [Event(p.name, ln.name, e.name, float(e.start_ns),
                  float(e.duration_ns))
            for p in data.planes for ln in p.lines for e in ln.events]


def is_device_plane(name: str) -> bool:
    return re.fullmatch(r"/device:[A-Z]+:\d+", name) is not None


def window(events) -> tuple:
    w = [e for e in events if e.name == WINDOW]
    if len(w) != 1:
        raise RuntimeError(f"expected one {WINDOW} span, found {len(w)}")
    return w[0].start_ns, w[0].end_ns


def _clip(events, lo, hi):
    out = []
    for e in events:
        s, t = max(e.start_ns, lo), min(e.end_ns, hi)
        if t > s:
            out.append(dataclasses.replace(e, start_ns=s, dur_ns=t - s))
    return out


def device_ops(events, lo, hi) -> dict:
    """Op events per device plane, clipped to [lo, hi]."""
    planes: dict = {}
    for e in events:
        if is_device_plane(e.plane) and e.line == "XLA Ops":
            planes.setdefault(e.plane, []).append(e)
    return {p: _clip(v, lo, hi) for p, v in planes.items()}


def union(intervals) -> list:
    """Merged, sorted (start, end) intervals."""
    out: list = []
    for s, t in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return [(s, t) for s, t in out]


def busy_ns(ops) -> float:
    return sum(t - s for s, t in union((e.start_ns, e.end_ns) for e in ops))


def base_name(name: str) -> str:
    """An op's or program's name without its instance number.  TPU op
    events carry the HLO instruction's text:
    ``%pq_scan_topk.1 = (f32[...]) custom-call(...)`` -> ``pq_scan_topk``;
    ``jit_search(12)`` -> ``jit_search``."""
    name = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"(\.\d+|\(\d+\))$", "", name)


def op_seconds(ops) -> tuple:
    """Device seconds and event count per op base name."""
    secs: dict = {}
    count: dict = {}
    for e in ops:
        k = base_name(e.name)
        secs[k] = secs.get(k, 0.0) + e.dur_ns * 1e-9
        count[k] = count.get(k, 0) + 1
    return secs, count


def module_events(events, plane: str, lo, hi) -> list:
    return _clip([e for e in events
                  if e.plane == plane and e.line == "XLA Modules"], lo, hi)


def module_seconds(events, plane: str, lo, hi) -> dict:
    out: dict = {}
    for e in module_events(events, plane, lo, hi):
        k = base_name(e.name)
        out[k] = out.get(k, 0.0) + e.dur_ns * 1e-9
    return out


def host_spans(events, lo, hi) -> list:
    return _clip([e for e in events
                  if e.name.startswith("bench.") and e.name != WINDOW],
                 lo, hi)


def idle_gaps(ops, spans, lo, hi, top: int = 10) -> list:
    """The longest gaps in which no op ran, each labelled by the host
    span that covers most of it ("host" where none does)."""
    gaps, t = [], lo
    for s, e in union((o.start_ns, o.end_ns) for o in ops):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:top]:
        cover: dict = {}
        for sp in spans:
            c = min(e, sp.end_ns) - max(s, sp.start_ns)
            if c > 0:
                cover[sp.name] = cover.get(sp.name, 0.0) + c
        label = (max(cover, key=cover.get)[len("bench."):]
                 if cover else "host")
        out.append([label, (e - s) * 1e-9])
    return out


@dataclasses.dataclass
class Reduced:
    """What the per-layer readers need from one trace."""
    window_s: float
    busy_s: float                 # averaged over device planes
    op_s: dict                    # op base name -> seconds (all planes)
    op_n: dict                    # op base name -> events
    module_s: dict                # program base name -> seconds
    top_ops: list                 # [[name, seconds]] longest first
    gaps: list                    # [[host span, seconds]] longest first
    spans: dict                   # host span name -> count in the window


def reduce(events) -> Reduced:
    lo, hi = window(events)
    planes = device_ops(events, lo, hi)
    if not planes:
        raise RuntimeError("the trace holds no device op")
    busy = sum(busy_ns(v) for v in planes.values()) / len(planes) * 1e-9
    op_s: dict = {}
    op_n: dict = {}
    module_s: dict = {}
    for p, ops in planes.items():
        secs, count = op_seconds(ops)
        for k, v in secs.items():
            op_s[k] = op_s.get(k, 0.0) + v
            op_n[k] = op_n.get(k, 0) + count[k]
        for k, v in module_seconds(events, p, lo, hi).items():
            module_s[k] = module_s.get(k, 0.0) + v
    first = sorted(planes)[0]
    top = sorted(op_s.items(), key=lambda kv: -kv[1])[:10]
    spans = host_spans(events, lo, hi)
    counts: dict = {}
    for sp in spans:
        k = sp.name[len("bench."):]
        counts[k] = counts.get(k, 0) + 1
    return Reduced(
        window_s=(hi - lo) * 1e-9, busy_s=busy, op_s=op_s, op_n=op_n,
        module_s=module_s, top_ops=[[k, v] for k, v in top],
        gaps=idle_gaps(planes[first], spans, lo, hi), spans=counts)
