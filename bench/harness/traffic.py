"""The one traffic generator: a mix file's parameters, driven on the
real clock through ``ServingEngine``.

A mix (``bench/traffic/<mix>.json``) says:

* ``searches``: single-query k-NN searches, Poisson at ``rate`` per
  second (or the cell's ``search_rate``); every ``readback_every``-th
  search queries the vector of an acknowledged insert instead of a
  fresh query;
* ``updates``: ``"loop": "open"`` submits ``insert_n`` fresh vectors
  every ``insert_every_s`` and deletes ``delete_n`` live ids every
  ``delete_every_s`` (half a period later), whatever the acks;
  ``"loop": "closed"`` submits ``insert_n`` fresh vectors, then, once
  they are acknowledged, deletes the ``delete_n`` oldest live ids, and
  so on.  ``delete_pick`` is ``uniform`` or ``oldest``; ``order`` is
  the order fresh vectors arrive in, ``random`` or ``clustered``;
* ``fresh_pool``: fresh vectors made for the run; ``warmup_s``: traffic
  served before the window opens; ``recall_sample``: searches of the
  window compared with the reference.

Every seed gets the same number of searches and the same gaps between
them, in a seeded order, so the seed changes which vectors and which
order, not how much work.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np


@dataclasses.dataclass
class Mix:
    rate: float
    k: int
    readback_every: int
    loop: str
    insert_n: int
    delete_n: int
    delete_pick: str
    order: str
    insert_every_s: float
    delete_every_s: float
    fresh_pool: int
    warmup_s: float
    recall_sample: int

    @classmethod
    def from_files(cls, mix: dict, cell: dict) -> "Mix":
        s, u = mix["searches"], mix["updates"]
        return cls(
            rate=float(cell.get("search_rate", s.get("rate", 0.0))),
            k=int(s["k"]), readback_every=int(s["readback_every"]),
            loop=u["loop"], insert_n=int(u["insert_n"]),
            delete_n=int(u["delete_n"]), delete_pick=u["delete_pick"],
            order=u["order"],
            insert_every_s=float(u.get("insert_every_s", 0.0)),
            delete_every_s=float(u.get("delete_every_s", 0.0)),
            fresh_pool=int(mix["fresh_pool"]),
            warmup_s=float(mix["warmup_s"]),
            recall_sample=int(mix["recall_sample"]))


def arrival_times(rate: float, seconds: float, seed: int) -> np.ndarray:
    """``round(rate * seconds)`` arrival times in [0, seconds): the gaps
    of one fixed Poisson draw, in an order drawn from ``seed``."""
    n = int(round(rate * seconds))
    fixed = np.sort(np.random.default_rng(0).uniform(0.0, seconds, n))
    gaps = np.diff(fixed, prepend=0.0)
    return np.cumsum(np.random.default_rng([seed, 5]).permutation(gaps))


@dataclasses.dataclass
class SearchReq:
    due: float                 # absolute host-clock time it was due
    ticket: object
    target: int                # read-back id, -1 for a query
    query: int                 # row of the query pool, -1 for a read-back
    in_window: bool


class Traffic:
    """One run's traffic over a ``ServingEngine`` whose index is an
    ``IndexProxy``.  ``vecs`` holds every id's vector (base ids first,
    then the fresh pool); ``queries`` the query pool."""

    def __init__(self, mix: Mix, engine, proxy, vecs, queries, *,
                 base_ids: np.ndarray, fresh_ids: np.ndarray, seed: int,
                 clock=time.perf_counter, sleep=time.sleep):
        self.mix, self.engine, self.proxy = mix, engine, proxy
        self.vecs, self.queries = vecs, queries
        self.clock, self.sleep = clock, sleep
        self.rng = np.random.default_rng([seed, 7])
        self.fresh = fresh_ids
        self.next_fresh = 0
        n_ids = len(vecs)
        # the generator's own view: acknowledged and not yet picked for
        # deletion, in acknowledgement order (for "oldest")
        self.alive = np.zeros(n_ids, bool)
        self.alive[base_ids] = True
        self.fifo = [base_ids]
        self.fifo_head = 0
        self.last_acked = base_ids[-mix.insert_n:]
        self.protected: dict = {}          # read-back id -> open searches
        self._open_rb: list = []           # read-backs not yet answered
        self.resubmit: list = []
        self.searches: list = []
        self.updates: list = []            # (kind, due, ticket, n)
        self.outstanding = None
        self.next_is_insert = True

    # -- picks ---------------------------------------------------------

    def _take_fresh(self, n: int) -> np.ndarray:
        again = (np.concatenate(self.resubmit) if self.resubmit
                 else np.zeros(0, np.int64))
        self.resubmit = []
        n_new = max(n - len(again), 0)
        if self.next_fresh + n_new > len(self.fresh):
            raise RuntimeError("the fresh pool ran out: raise fresh_pool")
        new = self.fresh[self.next_fresh:self.next_fresh + n_new]
        self.next_fresh += n_new
        return np.concatenate([again, new]).astype(np.int64)

    def _pick_deletes(self, n: int) -> np.ndarray:
        if self.mix.delete_pick == "oldest":
            out, need = [], n
            while need:
                arr = self.fifo[0]
                seg = arr[self.fifo_head:self.fifo_head + need]
                self.fifo_head += len(seg)
                if self.fifo_head >= len(arr):
                    self.fifo.pop(0)
                    self.fifo_head = 0
                seg = seg[self.alive[seg]]
                out.append(seg)
                need -= len(seg)
            ids = np.concatenate(out)
        else:
            live = np.flatnonzero(self.alive)
            ids = self.rng.choice(live, n + len(self.protected) + 8,
                                  replace=False)
            ids = ids[[i not in self.protected for i in ids]][:n]
        self.alive[ids] = False
        return ids.astype(np.int64)

    def _absorb_acks(self) -> None:
        for ids in self.proxy.take_acked():
            if not len(ids):
                continue
            self.alive[ids] = True
            self.fifo.append(ids)
            self.last_acked = ids
        for ids in self.proxy.take_rejected():
            self.resubmit.append(ids)

    # -- submissions ---------------------------------------------------

    def _submit_search(self, i: int, due: float, in_window: bool) -> None:
        m = self.mix
        fresh = self.last_acked[self.alive[self.last_acked]]
        if (m.readback_every and len(fresh)
                and i % m.readback_every == m.readback_every - 1):
            target = int(self.rng.choice(fresh))
            self.protected[target] = self.protected.get(target, 0) + 1
            t = self.engine.submit_search(self.vecs[target], m.k)
            self.searches.append(SearchReq(due, t, target, -1, in_window))
        else:
            q = i % len(self.queries)
            t = self.engine.submit_search(self.queries[q], m.k)
            self.searches.append(SearchReq(due, t, -1, q, in_window))

    def _submit_insert(self, due: float) -> None:
        ids = self._take_fresh(self.mix.insert_n)
        t = self.engine.submit_insert(self.vecs[ids], ids)
        self.updates.append(("insert", due, t, len(ids)))
        self.outstanding = t

    def _submit_delete(self, due: float) -> None:
        ids = self._pick_deletes(self.mix.delete_n)
        t = self.engine.submit_delete(ids)
        self.updates.append(("delete", due, t, len(ids)))
        self.outstanding = t

    def _release(self) -> None:
        """Unprotect read-back targets whose searches are answered."""
        while self._open_rb and self._open_rb[0].ticket.done():
            r = self._open_rb.pop(0)
            self.protected[r.target] -= 1
            if not self.protected[r.target]:
                del self.protected[r.target]

    def prime_merge(self, ids: np.ndarray) -> None:
        """Delete ``ids`` through the engine and let the index settle,
        then insert as many fresh vectors and settle again.  ``ids`` are
        whole small clusters: the postings that held them fall under
        ``l_min`` and merge, so the merge path runs (and compiles)
        before the window, whose traffic may merge at any time."""
        self.alive[ids] = False
        self.engine.submit_delete(ids)
        self.engine.drain()
        self.proxy.flush()
        fresh = self._take_fresh(len(ids))
        self.engine.submit_insert(self.vecs[fresh], fresh)
        self.engine.drain()
        self.proxy.flush()
        self._absorb_acks()

    def prime(self) -> None:
        """Serve one of each request the mix sends (a full search batch,
        an insert, a delete, then a tick) and wait for the answers, so
        that every program the window runs is compiled before the open
        loop starts."""
        now = self.clock()
        for i in range(self.engine.cfg.search_batch):
            q = self.engine.submit_search(self.queries[i], self.mix.k)
            self.searches.append(SearchReq(now, q, -1, i, False))
        self._submit_insert(now)
        self.engine.drain()
        self._absorb_acks()
        self._submit_delete(now)
        self.engine.drain()
        self.engine.tick()
        self.outstanding = None

    # -- the loop ------------------------------------------------------

    def drive(self, seconds: float, seed: int, *, marks=()) -> tuple:
        """Serve ``warmup_s`` and then a window of ``seconds``; returns
        (window open, window close) on the host clock.  ``marks`` are
        (seconds after the window opens, callback) pairs, each run once
        between two pumps (the profiler's start and stop)."""
        m = self.mix
        total = m.warmup_s + seconds
        s_due = arrival_times(m.rate, total, seed)
        t0 = self.clock()
        w_open, w_close = t0 + m.warmup_s, t0 + total
        ins_next = del_next = None
        if m.loop == "open":
            ins_next, del_next = 0.0, m.delete_every_s / 2
        i_s = 0
        marks = sorted(((w_open + t, fn) for t, fn in marks),
                       key=lambda m_: m_[0])
        while True:
            now = self.clock()
            while marks and now >= marks[0][0]:
                marks.pop(0)[1]()
                now = self.clock()
            if now >= w_close:
                break
            rel = now - t0
            while i_s < len(s_due) and s_due[i_s] <= rel:
                due = t0 + s_due[i_s]
                self._submit_search(i_s, due, due >= w_open)
                if self.searches[-1].target >= 0:
                    self._open_rb.append(self.searches[-1])
                i_s += 1
            if m.loop == "open":
                if rel >= ins_next:
                    self._submit_insert(t0 + ins_next)
                    ins_next += m.insert_every_s
                if rel >= del_next:
                    self._submit_delete(t0 + del_next)
                    del_next += m.delete_every_s
            elif self.outstanding is None or self.outstanding.done():
                if self.next_is_insert:
                    self._submit_insert(now)
                else:
                    self._submit_delete(now)
                self.next_is_insert = not self.next_is_insert
            fired = self.engine.pump()
            self._absorb_acks()
            self._release()
            if fired:
                continue
            nxt = [w_close - t0]
            if i_s < len(s_due):
                nxt.append(s_due[i_s])
            if ins_next is not None:
                nxt += [ins_next, del_next]
            wake = t0 + min(nxt)
            d = self.engine.next_deadline()
            if d is not None:
                wake = min(wake, d)
            if marks:
                wake = min(wake, marks[0][0])
            dt = wake - self.clock()
            if dt > 0:
                self.sleep(min(dt, 0.001))
        for _, fn in marks:
            fn()
        return w_open, w_close

    def finish(self, limit_s: float = 60.0) -> None:
        """Answer what is still queued (a minute at most)."""
        end = self.clock() + limit_s
        while not self.engine.idle and self.clock() < end:
            self.engine.pump(force=True)
            self._absorb_acks()
        self._absorb_acks()
        self._release()
