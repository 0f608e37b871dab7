"""Tests for the unified observability plane (``repro.obs``).

Covers the metrics registry (log-bucket histograms, exposition
round-trip, the shared driver-stat schema across every engine), the
structured tracer (reasons on every planner decision), the serving
request spans, the sampled live-recall probe, and the program spans
(tick phases, host syncs, their place in a profiler trace).
"""
from __future__ import annotations

import json

import numpy as np
import pytest

from repro.api.registry import list_engines
from repro.core.types import UBISConfig
from repro.obs import (DRIVER_STAT_SCHEMA, SPAN_NAMES, Histogram, Obs,
                       StatsMap, Tracer, parse_exposition, required_series)


def small_cfg(**kw):
    kw.setdefault("dim", 16)
    kw.setdefault("max_postings", 16)
    kw.setdefault("nprobe", 8)
    kw.setdefault("capacity", 96)
    kw.setdefault("max_ids", 1 << 12)
    kw.setdefault("use_pallas", "off")
    return UBISConfig(**kw)


def seeds(n=64, dim=16, seed=0):
    return np.random.default_rng(seed).normal(size=(n, dim)).astype(
        np.float32)


# ---------------------------------------------------------------- metrics


def test_histogram_summary_and_quantiles():
    h = Histogram("lat")
    vals = [0.001, 0.002, 0.004, 0.008, 0.1]
    for v in vals:
        h.record(v)
    s = h.summary()
    assert s["count"] == 5
    assert s["sum"] == pytest.approx(sum(vals))
    assert s["mean"] == pytest.approx(np.mean(vals))
    # log-bucket quantiles are bucket midpoints: exact to within one
    # bucket's growth factor (2**0.25), clamped to the observed range
    assert s["p50"] == pytest.approx(0.004, rel=2 ** 0.25 - 1)
    assert s["p99"] <= 0.1 + 1e-12
    assert h.quantile(0.0) >= min(vals)


def test_histogram_empty():
    s = Histogram("empty").summary()
    assert s == {"count": 0, "sum": 0.0, "mean": 0.0,
                 "p50": 0.0, "p95": 0.0, "p99": 0.0}


def test_registry_exposition_round_trip():
    obs = Obs()
    obs.counter("reqs").inc(3)
    obs.gauge("fill").set(0.75)
    h = obs.histogram("lat_seconds")
    h.record(0.01)
    h.record(0.02)
    series = parse_exposition(obs.to_prometheus())
    assert series["reqs"] == 3.0
    assert series["fill"] == 0.75
    assert series["lat_seconds_count"] == 2.0
    assert series["lat_seconds_sum"] == pytest.approx(0.03)
    assert not required_series(series, ("reqs", "fill", "lat_seconds_count"))
    assert required_series(series, ("reqs", "nope")) == ["nope"]


def test_parse_exposition_rejects_malformed():
    with pytest.raises(ValueError):
        parse_exposition("this is { not prometheus\n")


def test_stats_map_is_defaultdict_compatible():
    obs = Obs()
    s = obs.driver_stats()
    assert s["inserted"] == 0.0          # missing reads are 0.0
    s["inserted"] += 5
    s["bg_time"] += 0.25
    assert float(s["inserted"]) == 5.0
    assert set(dict(s)) == set(DRIVER_STAT_SCHEMA)
    # same prefix -> the SAME map (driver and tier manager share it)
    assert obs.driver_stats() is s
    snap = obs.snapshot()
    assert snap["index_inserted"] == 5.0
    assert isinstance(StatsMap.__slots__, tuple)


def test_snapshot_is_json_ready():
    obs = Obs()
    obs.driver_stats()["queries"] += 2
    obs.histogram("h").record(0.5)
    json.dumps(obs.snapshot(), allow_nan=False)


# ---------------------------------------------------------------- tracer


def test_tracer_ring_and_seq():
    tr = Tracer(capacity=4)
    for i in range(6):
        tr.emit("tick", i=i)
    evs = tr.events()
    assert len(evs) == 4                       # oldest dropped
    assert [e["i"] for e in evs] == [2, 3, 4, 5]
    assert [e["seq"] for e in evs] == [2, 3, 4, 5]
    assert tr.events("tick") == evs and tr.events("other") == []


def test_tracer_disabled_is_noop():
    tr = Tracer(enabled=False)
    tr.emit("tick", huge=list(range(1000)))
    assert len(tr) == 0


def test_tracer_jsonl_sink_and_numpy(tmp_path):
    p = tmp_path / "trace.jsonl"
    tr = Tracer(path=str(p))
    tr.emit("plan", pids=np.array([1, 2]), n=np.int64(2),
            frac=np.float32(0.5))
    tr.close()
    ev = json.loads(p.read_text().strip())
    assert ev["kind"] == "plan" and ev["pids"] == [1, 2]
    assert ev["n"] == 2 and isinstance(ev["frac"], float)


# ------------------------------------------------- shared driver schema


def test_every_engine_exposes_the_shared_stat_schema():
    """Satellite (a): the stats key drift across engines is gone — one
    schema, every ``make_index`` engine, keys identical and readable
    before any operation touched them."""
    cfg = small_cfg()
    sv = seeds()
    for spec in list_engines():
        idx = spec.make(cfg, sv, round_size=64)
        assert set(dict(idx.stats)) == set(DRIVER_STAT_SCHEMA), spec.name
        # snapshot exports the same keys under the index_ prefix
        snap = idx.obs.snapshot()
        missing = [k for k in DRIVER_STAT_SCHEMA
                   if f"index_{k}" not in snap]
        assert not missing, (spec.name, missing)


def test_driver_emits_reasoned_planner_events():
    from repro.core.driver import UBISDriver
    drv = UBISDriver(small_cfg(), seeds(), round_size=64,
                     bg_ops_per_round=4)
    rng = np.random.default_rng(1)
    drv.insert(rng.normal(size=(48, 16)).astype(np.float32),
               np.arange(48))
    drv.flush(max_ticks=8)
    drv.delete(np.arange(8))
    drv.flush(max_ticks=8)
    kinds = {e["kind"] for e in drv.obs.events()}
    assert {"insert", "delete", "tick"} <= kinds
    for e in drv.obs.events("bg_mark"):
        assert e["reason"], e                  # every decision says why
    for e in drv.obs.events("insert"):
        assert {"accepted", "cached", "rejected"} <= set(e)
    ins = sum(e["accepted"] + e["cached"]
              for e in drv.obs.events("insert"))
    dels = sum(e["deleted"] for e in drv.obs.events("delete"))
    assert ins - dels == drv.live_count()


def test_search_introspection_counters():
    from repro.core.driver import UBISDriver
    drv = UBISDriver(small_cfg(), seeds(), round_size=64)
    drv.insert(seeds(32, seed=2), np.arange(32))
    drv.flush(max_ticks=4)
    drv.search(seeds(8, seed=3), 4)
    s = drv.stats
    assert s["queries"] == 8
    assert s["search_probed"] > 0
    assert s["search_results"] > 0
    assert s["search_exact_batches"] == 1      # no PQ in this config
    assert s["search_adc_batches"] == 0


def test_rebalance_planner_records_move_triggers():
    from repro.api.rebalance import RebalancePlanner
    S, pool = 2, 8
    pl = RebalancePlanner(n_shards=S, pool_per_shard=pool,
                          watermark=0.85, min_gap=1, max_moves=4)
    lengths = np.zeros(S * pool, np.int32)
    lengths[:pool] = 40                        # shard 0 holds all mass
    movable = np.zeros(S * pool, bool)
    movable[:pool] = True
    # pressure rows: live, free, backlog, occ
    pressure = np.array([[8, 0, 0, 320.0], [1, 7, 0, 40.0]])
    src, dst = pl.plan(pressure, lengths, movable)
    assert len(src) == len(pl.last_moves) > 0
    for mv in pl.last_moves:
        assert mv["trigger"] in ("watermark", "spread")
        assert mv["donor"] == 0 and mv["dst"] == 1


# ---------------------------------------------------------- serving spans


def _drain(eng, tickets, n=50):
    for _ in range(n):
        eng.pump()
        if all(t.done() for t in tickets):
            return True
    return False


def test_serving_request_spans_and_probe():
    from repro.api.registry import make_index
    from repro.serving.engine import ServingConfig, ServingEngine
    idx = make_index("ubis", small_cfg(), seeds(), round_size=64)
    eng = ServingEngine(idx, ServingConfig(
        search_batch=4, search_deadline_s=0.0, recall_probe=1.0,
        recall_probe_rows=4))
    assert eng.obs is idx.obs                  # one plane, both layers
    qs = seeds(6, seed=5)
    tickets = [eng.submit_search(q[None], 4) for q in qs]
    assert _drain(eng, tickets)
    snap = eng.obs.snapshot()
    assert snap["serve_queue_wait_seconds"]["count"] == 6
    assert snap["serve_latency_seconds"]["count"] == 6
    assert snap["serve_service_seconds"]["count"] >= 1
    assert 0 < snap["serve_batch_fill"] <= 1.0
    assert snap["live_recall_probes"] >= 1
    assert 0.0 <= snap["live_recall"] <= 1.0
    assert eng.probe.rolling_recall == snap["live_recall"]


def test_serving_spans_disabled_with_plane_off():
    from repro.api.registry import make_index
    from repro.serving.engine import ServingConfig, ServingEngine
    obs = Obs(enabled=False)
    idx = make_index("ubis", small_cfg(), seeds(), round_size=64,
                     obs=obs)
    eng = ServingEngine(idx, ServingConfig(search_batch=4,
                                           search_deadline_s=0.0),
                        obs=obs)
    tickets = [eng.submit_search(seeds(1, seed=7), 4)]
    assert _drain(eng, tickets)
    snap = eng.obs.snapshot()
    assert snap["serve_latency_seconds"]["count"] == 0
    assert len(obs.tracer) == 0
    # the stats plane stays live even with tracing/spans off (the
    # driver counts padded batch rows, so >= the 1 real request)
    assert idx.stats["queries"] >= 1


def test_probe_sampling_is_seeded_and_bounded():
    from repro.obs import RecallProbe

    class FakeIndex:
        calls = 0

        def exact(self, q, k):
            FakeIndex.calls += 1
            ids = np.tile(np.arange(k), (len(q), 1))
            return type("R", (), {"ids": ids})()

    obs = Obs()
    pr = RecallProbe(FakeIndex(), obs.registry, fraction=0.5,
                     window=8, max_rows=2, seed=42)
    q = np.zeros((4, 8), np.float32)
    found = np.tile(np.arange(4), (4, 1))
    rs = [pr.maybe_probe(q, 4, found) for _ in range(40)]
    fired = [r for r in rs if r is not None]
    assert 0 < len(fired) < 40                 # sampled, not all/none
    assert FakeIndex.calls == len(fired)
    assert all(r == 1.0 for r in fired)
    assert pr.rolling_recall == 1.0
    # fraction=0 never probes and never builds device work
    pr0 = RecallProbe(FakeIndex(), Obs().registry, fraction=0.0)
    before = FakeIndex.calls
    assert pr0.maybe_probe(q, 4, found) is None
    assert FakeIndex.calls == before


def test_profile_hook_is_best_effort(tmp_path):
    obs = Obs()
    ran = []
    with obs.profile(None):
        ran.append(1)                          # no dir -> plain block
    with obs.profile(str(tmp_path / "prof")):
        ran.append(2)
    assert ran == [1, 2]


def test_profile_raises_when_the_trace_cannot_start(tmp_path, monkeypatch):
    """A trace directory was asked for: a capture that cannot start
    must not degrade to an untraced run."""
    import jax

    def refuse(*a, **k):
        raise RuntimeError("profiler busy")

    monkeypatch.setattr(jax.profiler, "start_trace", refuse)
    ran = []
    with pytest.raises(RuntimeError, match="profiler busy"):
        with Obs().profile(str(tmp_path / "prof")):
            ran.append(1)
    assert ran == []
    with Obs().profile(None):                  # no dir asked: no trace
        ran.append(2)
    assert ran == [2]


# ------------------------------------------------------- program spans


def test_span_names_are_pinned():
    assert SPAN_NAMES == (
        "tick", "tick.execute", "tick.drain", "tick.mark", "tick.gc",
        "tick.retrain", "tick.tier",
        "insert", "delete", "search.dispatch", "search.collect",
        "sync.round", "sync.drain", "sync.detect", "sync.gc",
        "sync.retrain", "sync.insert", "sync.delete", "sync.search",
        "serve.search", "serve.flush", "serve.overlap")
    obs = Obs()
    with obs.span("tick") as sp:
        pass
    assert sp.seconds >= 0.0
    assert obs.snapshot()["span_tick_seconds"]["count"] == 1
    with pytest.raises(ValueError, match="unknown span"):
        obs.span("tick.unlisted")


@pytest.fixture
def ann_log(monkeypatch):
    """Stands in for ``jax.profiler.TraceAnnotation``: the returned list
    logs each enter and exit, in order."""
    from repro.obs import span
    log = []

    class Ann:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            log.append(("enter", self.name))

        def __exit__(self, *exc):
            log.append(("exit", self.name))

    monkeypatch.setattr(span, "TraceAnnotation", Ann)
    return log


def _pq_driver(obs=None, **kw):
    from repro.core.driver import UBISDriver
    cfg = small_cfg(use_pq=True, pq_m=4, pq_ksub=16)
    return UBISDriver(cfg, seeds(), round_size=64, bg_ops_per_round=4,
                      pq_retrain_every=3, obs=obs, **kw)


def _ticks(log):
    """The annotation log cut into one list per ``ubis.tick``."""
    out, cur, depth = [], None, 0
    for ev, name in log:
        if ev == "enter" and name == "ubis.tick" and depth == 0:
            cur = []
        if cur is not None:
            cur.append((ev, name))
            depth += 1 if ev == "enter" else -1
            if depth == 0:
                out.append(cur)
                cur = None
    return out


def test_tick_opens_its_phases_nested_and_in_order(ann_log):
    drv = _pq_driver()
    drv.insert(seeds(200, seed=1), np.arange(200), tick_between=False)
    ann_log.clear()
    for _ in range(7):
        drv.tick()
    ticks = _ticks(ann_log)
    assert len(ticks) == 7
    for i, t in enumerate(ticks):
        assert t[0] == ("enter", "ubis.tick") and t[-1] == ("exit",
                                                            "ubis.tick")
        # the phases are the tick's children, each closed before the
        # next opens, in the order a tick runs them
        depth, children = 0, []
        for ev, name in t[1:-1]:
            if ev == "enter":
                if depth == 0:
                    children.append(name[len("ubis."):])
                depth += 1
            else:
                depth -= 1
        want = ["tick.execute", "tick.drain", "tick.mark", "tick.gc"]
        if (i + 1) % 3 == 0:                   # pq_retrain_every=3
            want.append("tick.retrain")
        assert children == want, (i, children)
    snap = drv.obs.snapshot()
    assert snap["span_tick_retrain_seconds"]["count"] == 2
    assert drv.stats["pq_retrains"] == 2


@pytest.mark.parametrize("mode", ["fused", "spfresh", "tier",
                                  "tier_async"])
def test_tick_phases_in_each_driver_mode(ann_log, mode):
    """The fused device mark, SPFresh's strict triggers (no cache, so no
    drain) and the cold tier (a last ``tick.tier`` phase, sync or
    dispatched at the tick's start) each open their phases in order,
    and the mark reads the device once a tick."""
    from repro.core.driver import UBISDriver
    cfg_kw, drv_kw = {}, {}
    if mode == "fused":
        drv_kw["fused_tick"] = True
    elif mode == "spfresh":
        cfg_kw["mode"] = "spfresh"
    else:
        cfg_kw.update(use_tier=True, tier_hot_max=0, max_postings=64,
                      use_pq=True, pq_m=4, pq_ksub=16)
        drv_kw["tier_async"] = mode == "tier_async"
    drv = UBISDriver(small_cfg(**cfg_kw), seeds(), round_size=64,
                     bg_ops_per_round=4, **drv_kw)
    drv.insert(seeds(200, seed=1), np.arange(200), tick_between=False)
    ann_log.clear()
    for _ in range(4):
        drv.tick()
    want = ["tick.execute", "tick.drain", "tick.mark", "tick.gc"]
    if mode == "spfresh":
        want.remove("tick.drain")
    if mode.startswith("tier"):
        want.append("tick.tier")
    ticks = _ticks(ann_log)
    assert len(ticks) == 4
    for t in ticks:
        depth, children = 0, []
        for ev, name in t[1:-1]:
            if ev == "enter" and depth == 0:
                children.append(name[len("ubis."):])
            depth += 1 if ev == "enter" else -1
        assert children == want, (mode, children)
        assert sum(1 for ev, n in t
                   if ev == "enter" and n == "ubis.sync.detect") == 1


def test_span_counts_equal_the_calls_and_feed_the_timings():
    from repro.core.driver import UBISDriver
    drv = UBISDriver(small_cfg(), seeds(), round_size=64)
    for i in range(3):
        drv.insert(seeds(40, seed=10 + i), np.arange(40 * i, 40 * i + 40),
                   tick_between=False)
    drv.delete(np.arange(10))
    drv.delete(np.arange(10, 20))
    for _ in range(4):
        drv.tick()
    drv.search(seeds(8, seed=3), 4)
    snap = drv.obs.snapshot()

    def h(name):
        return snap[f"span_{name.replace('.', '_')}_seconds"]
    assert h("insert")["count"] == 3 and h("delete")["count"] == 2
    assert h("tick")["count"] == 4
    assert h("search.dispatch")["count"] == h("search.collect")["count"] == 1
    # the stats and the events read the spans, not a second clock
    s = drv.stats
    assert s["insert_time"] == pytest.approx(h("insert")["sum"])
    assert s["delete_time"] == pytest.approx(h("delete")["sum"])
    assert s["bg_time"] == pytest.approx(h("tick")["sum"])
    assert s["bg_exec_time"] == pytest.approx(h("tick.execute")["sum"])
    ev_ticks = sum(e["seconds"] for e in drv.obs.events("tick"))
    assert ev_ticks == pytest.approx(h("tick")["sum"], abs=1e-5)
    # the exposition carries one histogram per span
    series = parse_exposition(drv.obs.to_prometheus())
    assert series["span_tick_execute_seconds_count"] == 4.0


def test_disabled_plane_records_no_span(ann_log):
    from repro.core.driver import UBISDriver
    obs = Obs(enabled=False)
    drv = UBISDriver(small_cfg(), seeds(), round_size=64, obs=obs)
    drv.insert(seeds(40, seed=1), np.arange(40))
    drv.tick()
    drv.delete(np.arange(5))
    drv.search(seeds(4, seed=2), 4)
    assert ann_log == []
    assert not [k for k in obs.snapshot() if k.startswith("span_")]
    assert len(obs.tracer) == 0
    # the spans still time their block: the stats plane stays live
    assert drv.stats["insert_time"] > 0 and drv.stats["bg_time"] > 0


def _sync_counts(obs):
    snap = obs.snapshot()
    return {n: snap.get(f"span_{n.replace('.', '_')}_seconds",
                        {"count": 0})["count"]
            for n in SPAN_NAMES if n.startswith("sync.")}


def test_sync_counts_per_tick_and_per_update_round():
    """The blocking reads, site by site.  An insert or delete reads its
    round's results once per ``round_size`` rows plus one final wait; a
    tick reads the round's counters when a marked batch ran, the cache
    count (and the drained count when the cache held vectors), the
    detector's masks with the lengths, the version (and the reclaimed
    count past ``gc_lag``), and on the retrain cadence the active slot
    and the new generation."""
    drv = _pq_driver(gc_lag=4)
    obs = drv.obs
    before = _sync_counts(obs)
    drv.insert(seeds(200, seed=1), np.arange(200), tick_between=False)
    after = _sync_counts(obs)
    assert after["sync.insert"] - before["sync.insert"] == 4 + 1
    assert sum(after.values()) - sum(before.values()) == 5
    per_tick = []
    for i in range(9):
        had_marked = bool(drv._marked)
        cache_n = int(np.asarray(drv.state.cache_valid).sum())
        before = _sync_counts(obs)
        r = drv.tick()
        after = _sync_counts(obs)
        d = {k: after[k] - before[k] for k in after}
        ver = int(drv.state.global_version)
        assert d["sync.round"] == int(had_marked), i
        assert d["sync.drain"] == 1 + int(cache_n > 0 or r.drained > 0), i
        assert d["sync.detect"] == 1
        assert d["sync.gc"] == 1 + int(ver > drv.gc_lag), i
        assert d["sync.retrain"] == (2 if (i + 1) % 3 == 0 else 0), i
        assert d["sync.insert"] == d["sync.delete"] == d["sync.search"] == 0
        per_tick.append(sum(d.values()))
    # 3 (nothing to run) to 8 (a marked batch, a full cache, GC and a
    # retrain)
    assert min(per_tick) >= 3 and max(per_tick) <= 8
    assert max(per_tick) > min(per_tick)
    before = _sync_counts(obs)
    drv.delete(np.arange(100))
    after = _sync_counts(obs)
    assert after["sync.delete"] - before["sync.delete"] == 2 + 1
    assert sum(after.values()) - sum(before.values()) == 3
    before = _sync_counts(obs)
    drv.search(seeds(8, seed=3), 4)
    after = _sync_counts(obs)
    assert after["sync.search"] - before["sync.search"] == 1
    assert sum(after.values()) - sum(before.values()) == 1


def test_profiler_trace_holds_the_tick_inside_the_callers_span(tmp_path):
    """On the profiler's clock: ``ubis.tick`` sits inside the caller's
    ``bench.tick`` annotation, and its phases inside it."""
    import glob

    import jax
    from jax.profiler import ProfileData, TraceAnnotation
    from repro.core.driver import UBISDriver
    drv = UBISDriver(small_cfg(), seeds(), round_size=64)
    drv.insert(seeds(100, seed=1), np.arange(100), tick_between=False)
    drv.tick()                                 # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation("bench.tick"):
            drv.tick()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    ev = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
          for p in ProfileData.from_file(path).planes for ln in p.lines
          for e in ln.events if e.name.startswith(("bench.", "ubis."))]
    (outer,) = [e for e in ev if e[0] == "bench.tick"]
    (tick,) = [e for e in ev if e[0] == "ubis.tick"]
    assert outer[1] <= tick[1] and tick[2] <= outer[2]
    phases = sorted((e for e in ev if e[0].startswith("ubis.tick.")),
                    key=lambda e: e[1])
    assert [e[0] for e in phases] == ["ubis.tick.execute", "ubis.tick.drain",
                                      "ubis.tick.mark", "ubis.tick.gc"]
    assert all(tick[1] <= e[1] and e[2] <= tick[2] for e in phases)
    assert any(e[0].startswith("ubis.sync.") for e in ev)


def test_engine_spans_cover_its_lanes_and_the_overlap():
    from repro.api.registry import make_index
    from repro.serving.engine import ServingConfig, ServingEngine
    idx = make_index("ubis", small_cfg(), seeds(), round_size=64)
    eng = ServingEngine(idx, ServingConfig(
        search_batch=4, search_deadline_s=0.0, insert_deadline_s=0.0,
        tick_every=1))
    tickets = [eng.submit_insert(seeds(16, seed=4), np.arange(16))]
    tickets += [eng.submit_search(q[None], 4) for q in seeds(4, seed=5)]
    assert _drain(eng, tickets)
    snap = eng.obs.snapshot()
    # one pump: the flush (and its tick) ran inside the search's
    # dispatch -> collect window
    assert snap["span_serve_search_seconds"]["count"] == 1
    assert snap["span_serve_overlap_seconds"]["count"] == 1
    assert snap["span_serve_flush_seconds"]["count"] == 1
    assert snap["span_tick_seconds"]["count"] == 1
    assert "serve_flush_overlap_seconds" not in snap
    assert (snap["span_serve_flush_seconds"]["sum"]
            <= snap["span_serve_overlap_seconds"]["sum"]
            <= snap["span_serve_search_seconds"]["sum"])


def test_serve_profile_dir_captures_one_trace(tmp_path):
    """``--obs-profile-dir``: the engine's first working pump is the one
    capture (a driver-level capture inside it used to raise "Profile has
    already been started")."""
    import glob

    from repro.launch.serve import RetrievalServer, ServeConfig
    prof = tmp_path / "prof"
    srv = RetrievalServer(
        ServeConfig(embed_dim=16, obs_profile_dir=str(prof)),
        index_cfg=small_cfg(), seed_vectors=seeds())
    ids = srv.ingest_vectors(seeds(32, seed=1))
    res = srv.query_vectors(seeds(2, seed=1)[:1], k=4)
    assert res.ids.shape == (1, 4) and len(ids) == 32
    assert len(glob.glob(str(prof / "**" / "*.xplane.pb"),
                         recursive=True)) == 1
