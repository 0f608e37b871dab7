"""The harness around the index: the proxy's bookkeeping of refused
inserts, the merge set-up runs before the window, and the profiler's
start and stop kept out of the window."""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2] / "bench"))

import time

import jax
import numpy as np

import tiny_cell
from harness import cell, tracefile
from harness.proxy import IndexProxy
from harness.traffic import Traffic
from repro.api.types import UpdateResult

SEED = 2**31 + 4321


class RefusingIndex:
    """Holds every inserted id but those in ``refuse``; reports
    ``miscount`` more refusals than it made."""

    def __init__(self, refuse, miscount=0):
        self.id_loc = np.full(32, -1)
        self.refuse, self.miscount = set(refuse), miscount

    def insert(self, vecs, ids):
        n = 0
        for i in ids:
            if int(i) in self.refuse:
                n += 1
            else:
                self.id_loc[i] = 7
        return UpdateResult(accepted=len(ids) - n, rejected=n + self.miscount)

    def snapshot(self):
        return self


def insert_20(index):
    vecs = np.random.default_rng(0).integers(0, 256, (20, 8)).astype(
        np.float32)
    proxy = IndexProxy(index, 32)
    proxy.insert(vecs, np.arange(20))
    return proxy


def test_proxy_learns_refused_ids_from_the_index_snapshot():
    proxy = insert_20(RefusingIndex(refuse={3, 17}))
    assert [r.tolist() for r in proxy.take_rejected()] == [[3, 17]]
    held = proxy.ins_seq < np.iinfo(np.int64).max
    assert np.flatnonzero(held).tolist() == [i for i in range(20)
                                             if i not in (3, 17)]
    assert proxy.refused == 2 and proxy.refused_unmatched == 0


def test_proxy_counts_refusals_that_the_id_map_does_not_confirm():
    proxy = insert_20(RefusingIndex(refuse={5}, miscount=1))
    assert proxy.refused == 1 and proxy.refused_unmatched == 1


def test_set_up_runs_a_merge_before_the_window(monkeypatch):
    merges = []
    prime_merge = Traffic.prime_merge

    def spy(self, ids):
        before = self.proxy.index.stats["bg_merge"]
        prime_merge(self, ids)
        merges.append(self.proxy.index.stats["bg_merge"] - before)
        assert self.proxy.live_count() == len(self.alive.nonzero()[0])

    monkeypatch.setattr(Traffic, "prime_merge", spy)
    cell.build(tiny_cell.files("search"), seed=SEED,
               t_start=time.perf_counter(), log=lambda s: None)
    assert merges and merges[0] > 0


def test_profiler_starts_and_stops_outside_the_window(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace", lambda *a, **k:
                        calls.append(("start", time.perf_counter())))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda:
                        calls.append(("stop", time.perf_counter())))
    monkeypatch.setattr(cell._Tracer, "reduce", lambda self: tracefile.Reduced(
        window_s=1.5, busy_s=1.0, op_s={}, op_n={}, module_s={},
        top_ops=[], gaps=[], spans={}))
    seen = {}
    drive = Traffic.drive

    def spy(self, *a, **k):
        seen["window"], seen["traffic"] = drive(self, *a, **k), self
        return seen["window"]

    monkeypatch.setattr(Traffic, "drive", spy)
    out = cell.run_cell(tiny_cell.files("search"), seed=SEED, seconds=1.5,
                        trace=True, t_start=time.perf_counter(),
                        log=lambda s: None)
    assert [c[0] for c in calls] == ["start", "stop"]
    w_open, w_close = seen["window"]
    last = max(cell.answered(r.ticket) for r in seen["traffic"].searches
               if r.in_window)
    assert calls[0][1] < w_open
    assert calls[1][1] > max(w_close, last)
    assert out["correct"], out["checks"]
    assert out["metrics"]["driver.search_call_ms"]["value"] > 0
