"""Tests for the unified observability plane (``repro.obs``).

Covers the metrics registry (log-bucket histograms, exposition
round-trip, the shared driver-stat schema across every engine), the
structured tracer (reasons on every planner decision), the serving
request spans, and the sampled live-recall probe.
"""
from __future__ import annotations

import json

import numpy as np
import pytest

from repro.api.registry import list_engines
from repro.core.types import UBISConfig
from repro.obs import (DRIVER_STAT_SCHEMA, Histogram, Obs, StatsMap, Tracer,
                       parse_exposition, required_series)


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
