"""Cluster plane tests: protocol codec, LocalBackend bit-identity vs
the in-process ShardedUBISDriver, straggler/kill/restart recovery, the
checkpoint manifest's loud failure modes, and (slow) the multi-process
backend: separate-process contract harness, Local==MultiProcess
equivalence, mid-stream worker kill, and 2-worker occupancy balance.
"""
import dataclasses
import io
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))
from contract_harness import live_map, make_clustered, run_program  # noqa: E402

from repro.api.sharded_driver import ShardedUBISDriver  # noqa: E402
from repro.checkpoint.manager import (ClusterManifestError,  # noqa: E402
                                      load_cluster_checkpoint)
from repro.cluster import (ClusterCoordinator, LocalBackend,  # noqa: E402
                           MultiProcessBackend, ProtocolError,
                           WorkerLost, combine_digests, plan_insert_split,
                           protocol)
from repro.core.types import UBISConfig  # noqa: E402
from repro.obs import Obs  # noqa: E402


def _cfg(**kw):
    base = dict(dim=16, max_postings=64, capacity=96, l_min=10, l_max=80,
                nprobe=64, max_ids=1 << 13, cache_capacity=2048,
                use_pallas="off")
    base.update(kw)
    return UBISConfig(**base)


TIER_KW = dict(use_pq=True, pq_m=4, pq_ksub=16, rerank_k=256,
               use_tier=True, tier_hot_max=8)


# ---------------------------------------------------------------- protocol


def test_codec_roundtrip_is_lossless():
    rng = np.random.default_rng(0)
    payload = {
        "f32": rng.standard_normal((3, 5)).astype(np.float32),
        "i64": rng.integers(-5, 5, 7),
        "bools": np.array([True, False]),
        "nested": {"x": np.arange(4, dtype=np.int32), "s": "hi",
                   "none": None, "f": 1.5, "list": [1, "a", None]},
        "scalar": np.float32(2.5),
    }
    msg = protocol.decode_message(
        protocol.encode_message("test", payload, 7))
    assert msg["kind"] == "test" and msg["seq"] == 7
    out = msg["payload"]
    assert out["f32"].tobytes() == payload["f32"].tobytes()
    assert out["f32"].dtype == np.float32
    assert np.array_equal(out["i64"], payload["i64"])
    assert np.array_equal(out["bools"], payload["bools"])
    assert np.array_equal(out["nested"]["x"], payload["nested"]["x"])
    assert out["nested"]["s"] == "hi" and out["nested"]["none"] is None
    assert out["nested"]["list"] == [1, "a", None]
    assert out["scalar"] == 2.5


def test_codec_rejects_foreign_schema_version():
    buf = protocol.encode_message("ping", {}, 1, v=protocol.SCHEMA_VERSION + 1)
    with pytest.raises(ProtocolError, match="schema version"):
        protocol.decode_message(buf)


def test_frame_roundtrip_and_truncation():
    bio = io.BytesIO()
    for seq in range(3):
        protocol.write_frame(bio, protocol.encode_message(
            "m", {"a": np.arange(seq + 1)}, seq))
    bio.seek(0)
    for seq in range(3):
        msg = protocol.decode_message(protocol.read_frame(bio))
        assert msg["seq"] == seq
        assert np.array_equal(msg["payload"]["a"], np.arange(seq + 1))
    assert protocol.read_frame(bio) is None           # clean EOF
    trunc = io.BytesIO(bio.getvalue()[:-3])           # mid-frame EOF
    trunc.read(0)
    protocol.read_frame(trunc)
    protocol.read_frame(trunc)
    with pytest.raises(ProtocolError):
        protocol.read_frame(trunc)


def test_digest_is_order_independent_and_combinable():
    rng = np.random.default_rng(1)
    sv = make_clustered(400)
    cfg = _cfg()
    drv = ShardedUBISDriver(cfg, sv[:100], round_size=128, seed=0)
    vecs, ids = sv[100:300], np.arange(200, dtype=np.int64)
    perm = rng.permutation(200)
    drv.insert(vecs[perm], ids[perm])
    d1 = protocol.live_multiset_digest(drv.snapshot())
    drv2 = ShardedUBISDriver(cfg, sv[:100], round_size=128, seed=1)
    drv2.insert(vecs, ids)
    assert d1 == protocol.live_multiset_digest(drv2.snapshot())
    assert combine_digests([d1, 0]) == d1
    assert combine_digests([d1, d1]) != d1


def test_plan_insert_split_waterfills():
    counts = plan_insert_split([100, 10, 10], 30)
    assert counts.sum() == 30
    assert counts[0] == 0 and counts[1] + counts[2] == 30
    assert abs(int(counts[1]) - int(counts[2])) <= 1
    counts = plan_insert_split([0, 0], 5)
    assert counts.tolist() == [3, 2]
    assert plan_insert_split([7, 3], 4).tolist() == [0, 4]
    big = plan_insert_split([5, 900, 40], 2000)
    assert big.sum() == 2000 and big.max() - big.min() <= 1 + 900 - 5


# --------------------------------------------- LocalBackend == in-process


def _interleaving(idx, data, seed, *, tiered=False):
    """Drive one seeded op tape; return per-op search results."""
    rng = np.random.default_rng(seed)
    out = []
    next_id = 0
    live = []
    for _ in range(10):
        op = rng.choice(["insert", "delete", "tick", "search"]
                        + (["spill", "promote"] if tiered else []))
        if op == "insert":
            n = int(rng.integers(8, 64))
            r = idx.insert(data[next_id:next_id + n],
                           np.arange(next_id, next_id + n))
            live.extend(range(next_id, next_id + n))
            next_id += n
            out.append(("insert", r.accepted, r.cached, r.rejected))
        elif op == "delete" and live:
            take = rng.choice(len(live), size=min(9, len(live)),
                              replace=False)
            ids = [live[i] for i in take]
            live = [x for x in live if x not in set(ids)]
            r = idx.delete(np.asarray(ids))
            out.append(("delete", r.deleted))
        elif op == "tick":
            r = idx.tick()
            out.append(("tick", r.executed, r.migrated, r.spilled,
                        r.promoted))
        elif op == "spill":
            out.append(("spill", idx.force_spill(int(rng.integers(1, 6)))))
        elif op == "promote":
            out.append(("promote", idx.force_promote()))
        else:
            q = data[rng.integers(0, next_id + 300, 6)]
            r = idx.search(q, 8)
            out.append(("search", np.asarray(r.ids).copy(),
                        np.asarray(r.scores).copy()))
    idx.flush()
    return out


def _assert_tapes_equal(a, b):
    assert len(a) == len(b)
    for ra, rb in zip(a, b):
        assert ra[0] == rb[0]
        if ra[0] == "search":
            np.testing.assert_array_equal(ra[1], rb[1])
            np.testing.assert_array_equal(ra[2], rb[2])
        else:
            assert ra[1:] == rb[1:], (ra, rb)


@pytest.mark.parametrize("tiered", [False, True],
                         ids=["plain", "tiered"])
def test_local_w1_bit_identical_to_sharded_driver(tiered):
    cfg = _cfg(**(TIER_KW if tiered else {}))
    data = make_clustered(1600, seed=3)
    kw = dict(round_size=128, bg_ops_per_round=8, insert_retries=2,
              pq_retrain_every=4, seed=0)
    drv = ShardedUBISDriver(cfg, data[:200], **kw)
    coord = ClusterCoordinator(cfg, data[:200], workers=1,
                               backend="local", **kw)
    tape_a = _interleaving(drv, data[200:], 11, tiered=tiered)
    tape_b = _interleaving(coord, data[200:], 11, tiered=tiered)
    _assert_tapes_equal(tape_a, tape_b)
    sa, sb = drv.snapshot(), coord.snapshot()
    for f in dataclasses.fields(sa):
        np.testing.assert_array_equal(
            np.asarray(getattr(sa, f.name)),
            np.asarray(getattr(sb, f.name)), err_msg=f.name)
    assert (protocol.live_multiset_digest(sa)
            == protocol.live_multiset_digest(sb))
    coord.close()


# ------------------------------------------------------ failure plane


def test_straggler_rpc_fires_worker_slow_event():
    cfg = _cfg()
    sv = make_clustered(300, seed=5)
    obs = Obs()
    coord = ClusterCoordinator(cfg, sv, workers=1, backend="local",
                               round_size=128, obs=obs)
    # drop the build/compile RPCs from the EWMA: measure steady state
    from repro.distributed.straggler import StragglerMonitor
    coord.backend.monitors[0] = StragglerMonitor()
    for _ in range(6):                       # past monitor warmup
        coord.backend.call(0, "ping", {})
    coord.backend.call(0, "sleep", {"seconds": 0.25})
    slow = obs.events("worker_slow")
    assert slow and slow[-1]["command"] == "sleep"
    assert slow[-1]["seconds"] >= 0.25
    coord.close()


def test_worker_kill_recovers_via_journal_replay():
    cfg = _cfg()
    data = make_clustered(900, seed=7)
    obs = Obs()
    coord = ClusterCoordinator(cfg, data[:200], workers=1,
                               backend="local", round_size=128, obs=obs)
    coord.insert(data[200:500], np.arange(300))
    coord.delete(np.arange(40))
    coord.tick()
    before = protocol.live_multiset_digest(coord.snapshot())
    live_before = coord.live_count()
    coord.backend.kill_worker(0)
    with pytest.raises(WorkerLost):
        coord.backend.call(0, "ping", {})
    # next coordinator call trips WorkerLost -> restart -> replay
    assert coord.live_count() == live_before
    assert protocol.live_multiset_digest(coord.snapshot()) == before
    assert obs.events("worker_lost")
    restarts = obs.events("worker_restarted")
    assert restarts and restarts[-1]["replayed"] > 0
    assert not restarts[-1]["from_checkpoint"]
    coord.close()


def test_checkpoint_restore_and_kill_after_checkpoint(tmp_path):
    cfg = _cfg()
    data = make_clustered(900, seed=9)
    obs = Obs()
    coord = ClusterCoordinator(cfg, data[:200], workers=1,
                               backend="local", round_size=128, obs=obs)
    coord.insert(data[200:500], np.arange(300))
    coord.flush()
    manifest = coord.checkpoint(str(tmp_path / "ck"))
    assert manifest["n_workers"] == 1
    # post-checkpoint mutations live only in the journal
    coord.delete(np.arange(25))
    digest = protocol.live_multiset_digest(coord.snapshot())
    coord.backend.kill_worker(0)
    assert protocol.live_multiset_digest(coord.snapshot()) == digest
    assert obs.events("worker_restarted")[-1]["from_checkpoint"]
    # a fresh cluster restores the manifest exactly
    coord2 = ClusterCoordinator(cfg, data[:200], workers=1,
                                backend="local", round_size=128)
    coord2.restore(str(tmp_path / "ck"))
    assert (protocol.live_multiset_digest(coord2.snapshot())
            == manifest["combined_digest"])
    coord.close()
    coord2.close()


def test_partial_or_corrupt_checkpoint_fails_loudly(tmp_path):
    cfg = _cfg()
    data = make_clustered(600, seed=13)
    coord = ClusterCoordinator(cfg, data[:200], workers=1,
                               backend="local", round_size=128)
    coord.insert(data[200:400], np.arange(200))
    ck = str(tmp_path / "ck")
    coord.checkpoint(ck)
    coord.close()
    # no manifest at all (partial write)
    with pytest.raises(ClusterManifestError, match="manifest"):
        load_cluster_checkpoint(str(tmp_path / "empty"))
    # missing worker file
    import json
    import shutil
    broken = str(tmp_path / "broken")
    shutil.copytree(ck, broken)
    os.remove(os.path.join(broken, "worker_000.npz"))
    with pytest.raises(ClusterManifestError, match="missing"):
        load_cluster_checkpoint(broken)
    # digest mismatch (tampered manifest)
    tampered = str(tmp_path / "tampered")
    shutil.copytree(ck, tampered)
    mp = os.path.join(tampered, "manifest.json")
    with open(mp) as f:
        m = json.load(f)
    m["digests"][0] = (m["digests"][0] + 1) & 0xFFFFFFFFFFFFFFFF
    with open(mp, "w") as f:
        json.dump(m, f)
    with pytest.raises(ClusterManifestError, match="digest mismatch"):
        load_cluster_checkpoint(tampered)
    # foreign schema version
    foreign = str(tmp_path / "foreign")
    shutil.copytree(ck, foreign)
    mp = os.path.join(foreign, "manifest.json")
    with open(mp) as f:
        m = json.load(f)
    m["schema_version"] += 1
    with open(mp, "w") as f:
        json.dump(m, f)
    with pytest.raises(ClusterManifestError, match="schema"):
        load_cluster_checkpoint(foreign)
    # worker-count mismatch
    with pytest.raises(ClusterManifestError, match="workers"):
        load_cluster_checkpoint(ck, expect_workers=2)


# -------------------------------------------- multi-process (separate


def _mp_coord(cfg, seeds, *, workers=2, obs=None, **kw):
    kw.setdefault("round_size", 128)
    kw.setdefault("spread_per_tick", 64)
    return ClusterCoordinator(cfg, seeds, workers=workers,
                              backend="multiprocess", obs=obs, **kw)


@pytest.mark.slow
def test_contract_harness_across_processes():
    """The full random-interleaving contract with the coordinator here
    and the worker in a separate OS process."""
    cfg = _cfg(max_postings=128, nprobe=128)
    data = make_clustered(2600, seed=0)
    coord = _mp_coord(cfg, data[:300], workers=1, insert_retries=4)
    try:
        run_program("ubis-cluster", coord, data, 0, n_ops=10)
    finally:
        coord.close()


@pytest.mark.slow
def test_local_equals_multiprocess_on_seeded_stream():
    cfg = _cfg()
    data = make_clustered(1600, seed=21)
    kw = dict(round_size=128, seed=0, insert_retries=2)
    a = ClusterCoordinator(cfg, data[:200], workers=2, backend="local",
                           **kw)
    b = _mp_coord(cfg, data[:200], workers=2, **kw)
    try:
        tape_a = _interleaving(a, data[200:], 17)
        tape_b = _interleaving(b, data[200:], 17)
        _assert_tapes_equal(tape_a, tape_b)
        da = a.snapshot().digest
        db = b.snapshot().digest
        assert da == db
    finally:
        a.close()
        b.close()


@pytest.mark.slow
def test_multiprocess_worker_kill_midstream_preserves_multiset():
    cfg = _cfg()
    data = make_clustered(1400, seed=23)
    obs = Obs()
    coord = _mp_coord(cfg, data[:200], workers=2, obs=obs, seed=0)
    try:
        coord.insert(data[200:700], np.arange(500))
        coord.flush()
        before = coord.snapshot()
        coord.backend.kill_worker(0)          # SIGKILL mid-stream
        after = coord.snapshot()              # triggers recovery
        assert after.digest == before.digest
        assert obs.events("worker_lost")
        assert obs.events("worker_restarted")
        # the restarted worker still serves: recall vs exact merge
        q = data[300:320]
        found = coord.search(q, 8).ids
        true = coord.exact(q, 8).ids
        hits = sum(len(set(map(int, f)) & set(map(int, t)))
                   for f, t in zip(found, true))
        assert hits / true.size >= 0.9
    finally:
        coord.close()


@pytest.mark.slow
def test_two_workers_stay_occupancy_balanced_on_zipf_stream():
    """<=1.5 max/min live-vector ratio across 2 simulated hosts under a
    skewed (Zipfian-cluster) insert stream."""
    rng = np.random.default_rng(31)
    cfg = _cfg()
    # zipf-weighted cluster draw: most inserts land near few centroids
    cents = rng.normal(size=(20, 16)) * 5.0
    ranks = np.arange(1, 21, dtype=np.float64)
    pz = (1.0 / ranks ** 1.2)
    pz /= pz.sum()
    a = rng.choice(20, size=1200, p=pz)
    data = (cents[a] + rng.normal(size=(1200, 16))).astype(np.float32)
    coord = ClusterCoordinator(cfg, data[:200], workers=2,
                               backend="local", round_size=128,
                               spread_per_tick=64, seed=0)
    try:
        next_id = 0
        for _ in range(10):
            n = 100
            coord.insert(data[next_id:next_id + n],
                         np.arange(next_id, next_id + n))
            next_id += n
            coord.tick()
        live = coord.worker_live()
        assert live.min() > 0
        assert live.max() / live.min() <= 1.5, live
        assert coord.live_count() == next_id
    finally:
        coord.close()


def test_multiprocess_backend_refuses_a_tpu_host(monkeypatch):
    """Workers inherit the parent's environment: on a TPU host each would
    reach for the chips the parent holds, so start() fails at once, with
    the reason, before spawning anything."""
    import jax
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    backend = MultiProcessBackend(2)
    with pytest.raises(RuntimeError, match="TPU host"):
        backend.start()
    assert backend._procs == [None, None]
    # workers pinned to the CPU are fine on any host
    MultiProcessBackend._check_host({"JAX_PLATFORMS": "cpu"})
