"""A thin proxy around the index: host spans and the order of updates.

``ServingEngine`` calls the index through this proxy.  For every call
into the driver it records one host span (name, start, end on the host
clock) and, while the profiler runs, the same span as a
``jax.profiler.TraceAnnotation`` so that device gaps can be labelled by
what the host was doing.  It also keeps the order of events the
reference needs: each acknowledged insert and each delete gets the next
update number, and each search dispatch records the update number it
saw, so the live multiset "as of that dispatch" can be rebuilt after
the window.  Which ids of an insert the index refused it reads, only
when the insert reports refusals, from the id map of the index's
``snapshot()`` (the protocol's result carries counts, not ids).

The proxy observes; it changes no argument and no result.
"""
from __future__ import annotations

import time

import jax
import numpy as np

SPAN_PREFIX = "bench."


class IndexProxy:
    def __init__(self, index, n_ids: int, clock=time.perf_counter):
        self.index = index
        self.obs = getattr(index, "obs", None)
        self.clock = clock
        self.annotate = False        # TraceAnnotation while tracing
        self.spans: list = []        # (name, t0, t1)
        # per id: the update number that inserted / deleted it (the
        # int64 maximum: never)
        self.ins_seq = np.full(n_ids, np.iinfo(np.int64).max, np.int64)
        self.del_seq = np.full(n_ids, np.iinfo(np.int64).max, np.int64)
        self.seq = 0
        self.dispatches: list = []   # (update number, t0) per search batch
        self.probes: list = []       # probed posting ids per batch (traced)
        self.keep_probes = False
        self.update_log: list = []   # (kind, end time, acknowledged)
        self._acked: list = []       # insert ids acknowledged, not taken
        self._rejected: list = []    # ids the driver refused, not taken
        self.refused = 0             # ids refused, in all
        self.refused_unmatched = 0   # reported refusals the map disagrees on

    def _span(self, name, fn, *args):
        t0 = self.clock()
        if self.annotate:
            with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
                out = fn(*args)
        else:
            out = fn(*args)
        self.spans.append((name, t0, self.clock()))
        return out

    # -- what the serving engine calls ---------------------------------

    def insert(self, vecs, ids):
        res = self._span("insert", self.index.insert, vecs, ids)
        ids = np.asarray(ids, np.int64)
        held = ids
        if res.rejected:
            ok = np.asarray(self.index.snapshot().id_loc)[ids] != -1
            held = ids[ok]
            self._rejected.append(ids[~ok])
            self.refused += int((~ok).sum())
            self.refused_unmatched += abs(int((~ok).sum()) - res.rejected)
        self.ins_seq[held] = self.seq
        self.seq += 1
        self._acked.append(held)
        self.update_log.append(("insert", self.spans[-1][2], len(held)))
        return res

    def delete(self, ids):
        res = self._span("delete", self.index.delete, ids)
        ids = np.asarray(ids, np.int64)
        self.del_seq[ids] = self.seq
        self.seq += 1
        self.update_log.append(("delete", self.spans[-1][2],
                                int(res.deleted)))
        return res

    def take_acked(self) -> list:
        out, self._acked = self._acked, []
        return out

    def take_rejected(self) -> list:
        out, self._rejected = self._rejected, []
        return out

    def dispatch_search(self, queries, k):
        self.dispatches.append((self.seq, self.clock()))
        return self._span("dispatch_search", self.index.dispatch_search,
                          queries, k)

    def collect_search(self, disp):
        res = self._span("collect_search", self.index.collect_search, disp)
        if self.keep_probes:
            self.probes.append(np.asarray(disp.probe))
        return res

    def tick(self):
        return self._span("tick", self.index.tick)

    # -- passthroughs used by set-up and the checks --------------------

    def flush(self, max_ticks: int = 200):
        return self.index.flush(max_ticks)

    def live_count(self) -> int:
        return self.index.live_count()
