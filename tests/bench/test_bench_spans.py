"""The program's spans in a trace (``bench/harness/spans.py``): clipping,
containment and idle time on hand-made events, and the spans of a real
traced run of a tiny cell on the CPU."""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2] / "bench"))

import time

import pytest

import tiny_cell
from harness import cell, spans as sp, tracefile
from harness.tracefile import Event

DATA = pathlib.Path(__file__).resolve().parent / "data"
DEV = "/device:TPU:0"
HOST = "/host:CPU"
SEED = 2**31 + 777


def synthetic():
    """Window [0, 100) ns.  On the main thread: an insert [0, 40) holding
    a tick [10, 30) (two syncs inside it) and a sync of its own [32, 36);
    a tick [50, 90) with its execute phase [50, 70) and a sync [60, 62);
    a collect [95, 120) running past the close, with its sync [96, 99).
    A sync on another thread [12, 14) belongs to no container.  Device
    ops: [5, 25) and [55, 80)."""
    m = (HOST, "main")
    ev = [Event(*m, "bench.window", 0, 100),
          Event(*m, "ubis.insert", 0, 40),
          Event(*m, "ubis.tick", 10, 20),
          Event(*m, "ubis.sync.drain", 12, 2),
          Event(*m, "ubis.sync.gc", 20, 5),
          Event(*m, "ubis.sync.insert", 32, 4),
          Event(*m, "ubis.tick", 50, 40),
          Event(*m, "ubis.tick.execute", 50, 20),
          Event(*m, "ubis.sync.round", 60, 2),
          Event(*m, "ubis.search.collect", 95, 25),
          Event(*m, "ubis.sync.search", 96, 3),
          Event(HOST, "other", "ubis.sync.detect", 12, 2),
          Event(*m, "ubis.tick", 130, 10)]            # after the close
    ev += [Event(DEV, "XLA Ops", "fusion.1", 5, 20),
           Event(DEV, "XLA Ops", "copy.2", 55, 25)]
    return ev


def test_program_spans_clip_count_and_seconds():
    ps = sp.program_spans(synthetic(), 0, 100)
    assert ps.count["tick"] == 2                    # the third is outside
    assert ps.count["search.collect"] == 1
    assert ps.seconds["tick"] == pytest.approx(60e-9)
    # the collect is clipped at the close: [95, 100)
    assert ps.seconds["search.collect"] == pytest.approx(5e-9)
    assert ps.count["sync.detect"] == 1


def test_syncs_belong_to_their_innermost_container_on_one_thread():
    ps = sp.program_spans(synthetic(), 0, 100)
    # tick 1 holds drain + gc, tick 2 holds round; the insert holds its
    # own sync only (the tick inside it takes the other two); the sync
    # on another thread is nobody's
    assert ps.syncs_in == {"tick": 3, "insert": 1, "delete": 0,
                           "search.collect": 1}


def test_idle_inside_spans_versus_outside_them():
    ev = synthetic()
    ops = [e for e in ev if e.plane == DEV]
    ticks = [e for e in sp.program_spans(ev, 0, 100).spans
             if e.name == "ubis.tick"]
    # tick [10, 30): busy to 25 -> 5 idle; tick [50, 90): busy 55-80
    # -> 5 + 10 idle.  The idle [25, 55) outside the ticks is not counted
    assert sp.idle_in(ops, ticks, 0, 100) == pytest.approx(20e-9)
    # clipped to the window, and spans that overlap count once
    assert sp.idle_in(ops, ticks + ticks, 0, 60) == pytest.approx(10e-9)
    assert sp.idle_in(ops, [], 0, 100) == 0.0


def test_readings_of_the_synthetic_trace():
    r = sp.readings(synthetic(), acked_updates=2000)
    assert r["tick_ms"] == pytest.approx(30e-6)        # 60 ns over 2
    assert r["tick.execute_ms"] == pytest.approx(10e-6)
    assert r["tick.retrain_ms"] is None                # none ran
    assert r["syncs_per_tick"] == pytest.approx(1.5)
    assert r["syncs_per_kvec"] == pytest.approx(0.5)   # 1 sync, 2,000
    assert r["search_fetch_ms"] == pytest.approx(3e-6)
    assert r["idle_in_tick_share"] == pytest.approx(20.0)
    # the collect [95, 100) is idle throughout
    assert r["idle_in_collect_share"] == pytest.approx(5.0)


def test_a_trace_without_program_spans_reads_none():
    """A program that opens no ``ubis.*`` span (the recorded trace
    predates them) gives no reading and raises nothing."""
    r = sp.readings(tracefile.load(str(DATA)), acked_updates=100)
    for k, v in r.items():
        assert v in (None, {}), (k, v)


@pytest.mark.parametrize("traffic,use_pq", [("churn", False),
                                            ("search", True)])
def test_traced_tiny_cell_holds_the_program_spans(monkeypatch, traffic,
                                                  use_pq):
    """The harness's own traced window, on the CPU: the program's spans
    are in the trace, inside the window, and every tick, sync and
    search reading is there.  (A CPU trace has no device plane, so the
    idle shares have nothing to read.)"""
    got = {}

    def reduce(self):
        ev = tracefile.load(self.dir)
        got["r"] = sp.readings(ev, acked_updates=1000)
        lo, hi = tracefile.window(ev)
        got["bench_ticks"] = sum(
            1 for s in tracefile.host_spans(ev, lo, hi)
            if s.name == "bench.tick")
        # an insert whose rounds refused vectors ticks between retries:
        # a program tick with no harness tick around it
        held = sp.program_spans(ev, lo, hi).spans
        inserts = [e for e in held if e.name == "ubis.insert"]
        got["retry_ticks"] = sum(
            1 for t in held if t.name == "ubis.tick"
            and any(i.start_ns <= t.start_ns and t.end_ns <= i.end_ns
                    for i in inserts))
        return tracefile.Reduced(window_s=(hi - lo) * 1e-9, busy_s=0.0,
                                 op_s={}, op_n={}, module_s={}, top_ops=[],
                                 gaps=[], spans={})

    monkeypatch.setattr(cell._Tracer, "reduce", reduce)
    out = cell.run_cell(tiny_cell.files(traffic, use_pq), seed=SEED,
                        seconds=1.5, trace=True, t_start=time.perf_counter(),
                        log=lambda s: None)
    assert out["correct"], out["checks"]
    r = got["r"]
    for k in ("tick_ms", "tick.execute_ms", "tick.drain_ms", "tick.mark_ms",
              "syncs_per_tick", "syncs_per_kvec", "search_fetch_ms"):
        assert r[k] is not None and r[k] > 0, (k, r[k])
    assert r["idle_in_tick_share"] is None
    # every harness tick holds one program tick (at each edge of the
    # window a harness tick may be cut in while its program tick is
    # not); the others ran inside inserts
    assert abs(r["count"]["tick"] - got["retry_ticks"]
               - got["bench_ticks"]) <= 2
    assert r["count"]["sync.search"] == r["count"]["search.collect"]
    phases = sum(r[k] for k in ("tick.execute_ms", "tick.drain_ms",
                                "tick.mark_ms", "tick.gc_ms"))
    if use_pq and r["tick.retrain_ms"] is not None:
        phases += (r["tick.retrain_ms"] * r["count"]["tick.retrain"]
                   / r["count"]["tick"])
    assert 0.9 * r["tick_ms"] <= phases <= r["tick_ms"]
