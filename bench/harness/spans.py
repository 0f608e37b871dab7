"""The program's own spans in a profiler trace.

The driver and the serving engine annotate their work as
``jax.profiler.TraceAnnotation`` events named ``ubis.<span>`` (see
``repro.obs.SPAN_NAMES``): the tick and its phases, inserts, deletes,
search dispatch and collect, and each blocking device->host read as
``ubis.sync.<site>``.  They share the trace's clock with the device ops,
so the device's idle time can be laid against them.

``program_spans`` reduces them over the window; ``idle_in`` measures
the device-idle time inside a set of them; ``readings`` turns both into
the per-layer numbers the tick, sync and idle metrics read.  A trace
with no ``ubis.*`` event (a program without the spans) gives empty
counts, and every reading that needs them is None.
"""
from __future__ import annotations

import bisect
import dataclasses

from .tracefile import _clip, device_ops, union, window

PREFIX = "ubis."
SYNC = "sync."
#: the spans whose sync reads are counted: each sync span belongs to the
#: innermost of these that holds it
CONTAINERS = ("tick", "insert", "delete", "search.collect")


@dataclasses.dataclass
class ProgramSpans:
    count: dict        # span name (without "ubis.") -> spans in the window
    seconds: dict      # span name -> seconds inside the window
    syncs_in: dict     # container name -> sync spans it holds (innermost)
    spans: list        # the clipped events, "ubis." prefix kept


def program_spans(events, lo, hi) -> ProgramSpans:
    """The ``ubis.*`` annotations clipped to [lo, hi]: count and seconds
    per name, and which ``sync.*`` spans lie inside which container."""
    spans = _clip([e for e in events if e.name.startswith(PREFIX)], lo, hi)
    count: dict = {}
    seconds: dict = {}
    for e in spans:
        k = e.name[len(PREFIX):]
        count[k] = count.get(k, 0) + 1
        seconds[k] = seconds.get(k, 0.0) + e.dur_ns * 1e-9
    # per thread, the containers by start; a sync span belongs to the
    # latest-starting one that still holds it (they nest on a thread)
    holders: dict = {}
    for e in sorted(spans, key=lambda e: e.start_ns):
        if e.name[len(PREFIX):] in CONTAINERS:
            holders.setdefault((e.plane, e.line), []).append(e)
    starts = {k: [e.start_ns for e in v] for k, v in holders.items()}
    syncs_in = dict.fromkeys(CONTAINERS, 0)
    for e in spans:
        key = (e.plane, e.line)
        if not e.name.startswith(PREFIX + SYNC) or key not in holders:
            continue
        held = holders[key]
        for j in range(bisect.bisect_right(starts[key], e.start_ns) - 1,
                       -1, -1):
            c = held[j]
            if e.end_ns <= c.end_ns:
                syncs_in[c.name[len(PREFIX):]] += 1
                break
    return ProgramSpans(count, seconds, syncs_in, spans)


def idle_in(ops, spans, lo, hi) -> float:
    """Seconds inside the union of ``spans`` (clipped to [lo, hi]) in
    which no op of ``ops`` ran."""
    held = union((max(s.start_ns, lo), min(s.end_ns, hi)) for s in spans
                 if min(s.end_ns, hi) > max(s.start_ns, lo))
    busy = union((o.start_ns, o.end_ns) for o in ops)
    idle, j = 0.0, 0
    for s, t in held:              # both lists sorted and disjoint
        while j < len(busy) and busy[j][1] <= s:
            j += 1
        covered, k = 0.0, j
        while k < len(busy) and busy[k][0] < t:
            covered += min(t, busy[k][1]) - max(s, busy[k][0])
            k += 1
        idle += (t - s) - covered
    return idle * 1e-9


def _per(num, den, scale=1.0):
    return num * scale / den if den else None


def readings(events, acked_updates: int) -> dict:
    """The tick, sync and idle readings of one trace's window.

    ``acked_updates``: inserts and deletes acknowledged in the window
    (the harness's tally), for the syncs per 1,000 of them."""
    lo, hi = window(events)
    ps = program_spans(events, lo, hi)
    n, s = ps.count, ps.seconds
    planes = device_ops(events, lo, hi)
    window_s = (hi - lo) * 1e-9

    def idle_share(name):
        held = [e for e in ps.spans if e.name == PREFIX + name]
        if not held or not planes:
            return None
        idle = sum(idle_in(v, held, lo, hi) for v in planes.values())
        return 100.0 * idle / len(planes) / window_s

    ticks = n.get("tick", 0)
    updates = n.get("insert", 0) + n.get("delete", 0)

    def per_tick_ms(name):         # None where the phase never ran
        return _per(s[name], ticks, 1e3) if name in s else None

    return {
        "tick_ms": per_tick_ms("tick"),
        "tick.execute_ms": per_tick_ms("tick.execute"),
        "tick.drain_ms": per_tick_ms("tick.drain"),
        "tick.mark_ms": per_tick_ms("tick.mark"),
        "tick.gc_ms": per_tick_ms("tick.gc"),
        "tick.retrain_ms": _per(s.get("tick.retrain", 0.0),
                                n.get("tick.retrain", 0), 1e3),
        "tick.tier_ms": per_tick_ms("tick.tier"),
        "syncs_per_tick": _per(ps.syncs_in["tick"], ticks),
        "syncs_per_kvec": (_per(ps.syncs_in["insert"]
                                + ps.syncs_in["delete"],
                                acked_updates, 1e3) if updates else None),
        "search_fetch_ms": _per(s.get("sync.search", 0.0),
                                n.get("search.collect", 0), 1e3),
        "idle_in_tick_share": idle_share("tick"),
        "idle_in_collect_share": idle_share("search.collect"),
        "count": n,
        "seconds": s,
    }
