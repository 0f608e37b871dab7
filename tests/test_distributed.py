"""Multi-device tests: run in a subprocess with 8 fake CPU devices so
the main pytest process keeps its single-device platform."""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str):
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(ROOT, "src"),
               TF_CPP_MIN_LOG_LEVEL="2")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env,
                       timeout=540)
    assert r.returncode == 0, f"STDOUT:{r.stdout}\nSTDERR:{r.stderr[-3000:]}"
    return r.stdout


@pytest.mark.slow
def test_sharded_ubis_matches_single_device():
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec
        from repro.core import UBISConfig, UBISDriver, brute_force, metrics
        from repro.core.sharded import (index_specs, make_sharded_insert,
                                        make_sharded_search)
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        cfg = UBISConfig(dim=16, max_postings=256, capacity=96,
                         max_ids=1 << 14, use_pallas="off")
        r = np.random.default_rng(1)
        cents = r.normal(size=(12, 16)) * 5
        data = (cents[r.integers(0, 12, 3000)]
                + r.normal(size=(3000, 16))).astype(np.float32)
        drv = UBISDriver(cfg, data[:500], round_size=256,
                         bg_ops_per_round=8)
        drv.insert(data[:2000], np.arange(2000)); drv.flush()
        sh = jax.tree_util.tree_map(
            lambda sp: NamedSharding(mesh, sp), index_specs(cfg),
            is_leaf=lambda x: isinstance(x, PartitionSpec))
        st = jax.device_put(drv.state, sh)
        search = make_sharded_search(cfg, mesh, k=10)
        q = (cents[r.integers(0, 12, 64)]
             + r.normal(size=(64, 16))).astype(np.float32)
        found, _ = search(st, jnp.asarray(q))
        true, _ = brute_force(drv.state, cfg, jnp.asarray(q), 10)
        rec = metrics.recall_at_k(np.asarray(found), np.asarray(true))
        assert rec > 0.95, rec
        ins = make_sharded_insert(cfg, mesh)
        nv = (cents[r.integers(0, 12, 128)]
              + r.normal(size=(128, 16))).astype(np.float32)
        st2, accm, routed = ins(st, jnp.asarray(nv),
                                jnp.arange(2000, 2128, dtype=jnp.int32),
                                jnp.ones(128, bool))
        accm = np.asarray(accm)
        routed = np.asarray(routed)
        assert accm.shape == (128,)
        assert int(accm.sum()) > 64
        # routed pids are GLOBAL and in range wherever a job landed
        assert ((routed[accm] >= 0) & (routed[accm] < 256)).all()
        found2, _ = search(st2, jnp.asarray(nv[:32]))
        hits = sum(2000 + i in set(f.tolist())
                   for i, f in enumerate(np.asarray(found2)))
        assert hits >= 30, hits
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_sharded_search_pq_phase2():
    """With cfg.use_pq, the sharded search's phase 2 is served from the
    PQ codes (per-shard ADC scan + exact rerank); recall vs the float
    brute force stays high and the float sharded path agrees."""
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp, dataclasses
        from jax.sharding import NamedSharding, PartitionSpec
        from repro.core import UBISConfig, UBISDriver, brute_force, metrics
        from repro.core.sharded import index_specs, make_sharded_search
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        cfg = UBISConfig(dim=16, max_postings=256, capacity=96,
                         max_ids=1 << 14, use_pallas="off", use_pq=True,
                         pq_m=4, pq_ksub=32, rerank_k=128)
        r = np.random.default_rng(2)
        cents = r.normal(size=(10, 16)) * 6
        data = (cents[r.integers(0, 10, 2500)]
                + r.normal(size=(2500, 16))).astype(np.float32)
        drv = UBISDriver(cfg, data[:500], round_size=256,
                         bg_ops_per_round=8)
        drv.insert(data, np.arange(2500)); drv.flush()
        sh = jax.tree_util.tree_map(
            lambda sp: NamedSharding(mesh, sp), index_specs(cfg),
            is_leaf=lambda x: isinstance(x, PartitionSpec))
        st = jax.device_put(drv.state, sh)
        q = (cents[r.integers(0, 10, 64)]
             + r.normal(size=(64, 16))).astype(np.float32)
        found_pq, _ = make_sharded_search(cfg, mesh, k=10)(
            st, jnp.asarray(q))
        true, _ = brute_force(drv.state, cfg, jnp.asarray(q), 10)
        rec = metrics.recall_at_k(np.asarray(found_pq), np.asarray(true))
        # apples to apples: the sharded ADC path must not trail the
        # single-device ADC path (it reranks rerank_k PER SHARD, so it
        # usually leads slightly); coarse m=4 codes cap both ~0.88
        found_1 = drv.search(q, 10).ids
        rec_1 = metrics.recall_at_k(np.asarray(found_1),
                                    np.asarray(true))
        assert rec >= rec_1 - 0.02, (rec, rec_1)
        assert rec > 0.8, rec
        # the float sharded path on the same state stays exact-grade
        cfg_f = dataclasses.replace(cfg, use_pq=False)
        found_f, _ = make_sharded_search(cfg_f, mesh, k=10)(
            st, jnp.asarray(q))
        rec_f = metrics.recall_at_k(np.asarray(found_f),
                                    np.asarray(true))
        assert rec_f > 0.95, rec_f
        print("OK", rec, rec_1, rec_f)
    """)
    assert "OK" in out


@pytest.mark.slow
def test_sharded_background_round_splits_and_stays_consistent():
    """The batched background round, shard-mapped: per-shard detect ->
    select -> execute in one collective-free device call; oversize
    postings come down, ids are never lost or duplicated, and the
    replicated id map stays in sync after the psum merge."""
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec
        from repro.core import UBISConfig, UBISDriver
        from repro.core import version_manager as vm
        from repro.core.sharded import index_specs, make_sharded_background
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        cfg = UBISConfig(dim=16, max_postings=256, capacity=96,
                         max_ids=1 << 14, use_pallas="off")
        r = np.random.default_rng(1)
        cents = r.normal(size=(12, 16)) * 5
        data = (cents[r.integers(0, 12, 3000)]
                + r.normal(size=(3000, 16))).astype(np.float32)
        drv = UBISDriver(cfg, data[:500], round_size=256,
                         bg_ops_per_round=8)
        # no ticks: leave oversize postings for the background plane
        drv.insert(data[:2500], np.arange(2500), tick_between=False)
        pre_over = int((np.asarray(drv.state.lengths) > cfg.l_max).sum())
        assert pre_over > 0, "schedule built no oversize postings"
        sh = jax.tree_util.tree_map(
            lambda sp: NamedSharding(mesh, sp), index_specs(cfg),
            is_leaf=lambda x: isinstance(x, PartitionSpec))
        st = jax.device_put(drv.state, sh)
        bg = make_sharded_background(cfg, mesh, bg_ops=8)
        total = 0
        for _ in range(12):
            st, ex, _gc, press = bg(st, jnp.uint32(0))
            total += int(ex)
            if int(ex) == 0:
                break
        assert total > 0
        # pressure rows: one per shard, live+free bounded by the pool
        press = np.asarray(press)
        assert press.shape == (4, 4)
        assert (press[:, 0] + press[:, 1] <= 64).all()
        assert press[:, 0].sum() > 0
        # a quiescent tick must round-trip rec_succ EXACTLY — the
        # entry-localize/exit-rebase may only rewrite words the round
        # touched (cross-shard successor pointers survive untouched)
        st2, ex2, _gc2, _p2 = bg(st, jnp.uint32(0))
        assert int(ex2) == 0
        assert (np.asarray(jax.device_get(st).rec_succ)
                == np.asarray(jax.device_get(st2).rec_succ)).all()
        st = st2
        full = jax.device_get(st)
        status = np.asarray(vm.unpack_status(full.rec_meta))
        vis = np.asarray(full.allocated) & (status != 3)
        lens = np.asarray(full.lengths)
        assert (lens[vis] <= cfg.l_max).all(), lens[vis].max()
        # audit: live ids (postings + cache) == id_loc, no duplicates
        ids = np.asarray(full.ids); sv = np.asarray(full.slot_valid)
        where = {}
        for p in np.flatnonzero(vis):
            for c in np.flatnonzero(sv[p]):
                i = int(ids[p, c])
                assert i not in where, f"dup id {i}"
                where[i] = p * cfg.capacity + c
        cv = np.asarray(full.cache_valid)
        ci = np.asarray(full.cache_ids)
        for s in np.flatnonzero(cv):
            where[int(ci[s])] = -2 - s
        il = np.asarray(full.id_loc)
        tracked = {int(i): int(il[i]) for i in np.flatnonzero(il != -1)}
        assert tracked == where, (len(tracked), len(where))
        # successor pointers must be GLOBAL pids after gather: every
        # retired posting's successors land on allocated postings
        s1, s2 = (np.asarray(x) for x in vm.succ_ids(full.rec_succ))
        alloc = np.asarray(full.allocated)
        retired = np.flatnonzero(alloc & (status == 3))
        assert len(retired), "no retirements despite executed ops"
        n_succ = 0
        for p in retired:
            for s in (int(s1[p]), int(s2[p])):
                if s >= 0:
                    n_succ += 1
                    assert alloc[s], f"successor {s} of {p} not allocated"
        assert n_succ > 0
        # exit free stack is fail-safe empty; rebuild restores the
        # canonical single-device invariant
        assert int(full.free_top) == 0
        from repro.core.update import rebuild_free_stack
        full = rebuild_free_stack(full)
        top = int(full.free_top)
        free = np.asarray(full.free_list)[:top]
        alloc = np.asarray(full.allocated)
        assert len(np.unique(free)) == top
        assert not alloc[free].any()
        assert top + alloc.sum() == cfg.max_postings
        print("OK", total, "ops")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_sharded_driver_end_to_end_multishard():
    """ShardedUBISDriver on a real 4-shard mesh: the full protocol
    surface (insert with retries, sharded deletes, search, ticks with
    in-round GC, flush, canonical snapshot) with an id->vector audit."""
    out = _run("""
        import numpy as np, jax
        from repro.api import ShardedUBISDriver
        from repro.core import UBISConfig
        from repro.core import version_manager as vm
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        cfg = UBISConfig(dim=16, max_postings=256, capacity=96,
                         max_ids=1 << 14, use_pallas="off")
        r = np.random.default_rng(3)
        cents = r.normal(size=(12, 16)) * 5
        data = (cents[r.integers(0, 12, 4000)]
                + r.normal(size=(4000, 16))).astype(np.float32)
        drv = ShardedUBISDriver(cfg, data[:500], mesh=mesh,
                                round_size=256, bg_ops_per_round=8,
                                gc_lag=4)
        res = drv.insert(data, np.arange(4000))
        assert res.accepted + res.cached == 4000, res
        drv.delete(np.arange(0, 600))
        drv.flush(max_ticks=40)
        # everything streamed minus deletes is live, exactly once
        st = drv.snapshot()       # asserts canonical free stack
        status = np.asarray(vm.unpack_status(st.rec_meta))
        vis = np.asarray(st.allocated) & (status != 3)
        ids = np.asarray(st.ids); sv = np.asarray(st.slot_valid)
        live = set()
        for p in np.flatnonzero(vis):
            for c in np.flatnonzero(sv[p]):
                i = int(ids[p, c])
                assert i not in live, f"dup id {i}"
                live.add(i)
        cv = np.asarray(st.cache_valid)
        live |= {int(i) for i in np.asarray(st.cache_ids)[cv]}
        assert live == set(range(600, 4000)), (
            len(live), min(live), max(live))
        # oversize postings all came down; GC reclaimed retirees
        lens = np.asarray(st.lengths)
        assert (lens[vis] <= cfg.l_max).all()
        assert drv.stats["bg_gc"] > 0, "in-round GC never reclaimed"
        # search quality vs exact truth over the live contents
        from repro.core import metrics
        q = (cents[r.integers(0, 12, 64)]
             + r.normal(size=(64, 16))).astype(np.float32)
        found = drv.search(q, 10).ids
        true = drv.exact(q, 10).ids
        rec = metrics.recall_at_k(np.asarray(found), np.asarray(true))
        assert rec > 0.95, rec
        print("OK", len(live), "live")
    """)
    assert "OK" in out




@pytest.mark.slow
@pytest.mark.parametrize("use_pq", [False, True])
def test_sharded_kernels_bit_identical_to_twins(use_pq):
    """The sharded programs with the Pallas kernels (interpret mode
    here) answer bit-identically to the jnp twins on a 4-shard mesh:
    insert, delete, background ticks, PQ retrains, search and exact,
    same state."""
    out = _run(f"""
        import numpy as np, jax
        from repro.api import ShardedUBISDriver
        from repro.core import UBISConfig
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        # small integers: every score is exact in f32, so the kernels'
        # summation order cannot show (as in test_e2e_d100)
        r = np.random.default_rng(5)
        cents = r.integers(-8, 9, (8, 16))
        data = (cents[r.integers(0, 8, 900)]
                + r.integers(-2, 3, (900, 16))).astype(np.float32)
        q = (cents[r.integers(0, 8, 16)]
             + r.integers(-2, 3, (16, 16))).astype(np.float32)

        def run(use_pallas):
            cfg = UBISConfig(dim=16, max_postings=64, capacity=32,
                             l_min=4, l_max=24, cache_capacity=64,
                             max_ids=1 << 10, nprobe=4,
                             use_pq={use_pq}, pq_m=4, pq_ksub=16,
                             rerank_k=24, use_pallas=use_pallas)
            drv = ShardedUBISDriver(cfg, data[:200], mesh=mesh,
                                    round_size=128, bg_ops_per_round=4,
                                    pq_retrain_every=2)
            drv.insert(data, np.arange(900))
            drv.delete(np.arange(0, 900, 9))
            drv.flush(max_ticks=8)
            s, e = drv.search(q, 10), drv.exact(q, 10)
            assert drv.obs.counter("kernel_fallback").value == 0.0
            assert drv.stats["pq_retrains"] >= {int(use_pq)}
            return (np.asarray(s.ids), np.asarray(s.scores),
                    np.asarray(e.ids), np.asarray(e.scores),
                    np.asarray(drv.state.id_loc))

        on, off = run("on"), run("off")
        for a, b in zip(on, off):
            np.testing.assert_array_equal(a, b)
        assert (on[0] >= 0).all()
        print("OK")
    """)
    assert "OK" in out
