"""Quant-plane properties: code/float sync through churn, versioned
codebook re-train safety, and use_pq=False float-path identity."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (UBISConfig, UBISDriver, brute_force, metrics,
                        version_manager as vm)
from repro.core.search import search as search_fn
from repro.kernels import ops, ref
from repro.kernels.posting_scan import BIG
from repro.quant import pq
from conftest import make_clustered


def _mk_cfg(mode="ubis", **kw):
    base = dict(dim=16, max_postings=256, capacity=64, l_min=6, l_max=48,
                cache_capacity=512, max_ids=1 << 14, use_pallas="off",
                mode=mode, use_pq=True, pq_m=4, pq_ksub=32, rerank_k=48)
    base.update(kw)
    return UBISConfig(**base)


def assert_codes_in_sync(state, cfg):
    """The tentpole invariant: for every valid slot of every live
    posting, the stored code equals encode(codebooks[posting's slot],
    stored float vector) — the planes never diverge."""
    status = np.asarray(vm.unpack_status(state.rec_meta))
    alive = np.flatnonzero(np.asarray(state.allocated) & (status != 3))
    cbs = np.asarray(state.pq_codebooks)
    slot = np.asarray(state.pq_posting_slot)
    codes = np.asarray(state.codes)
    vecs = np.asarray(state.vectors)
    sv = np.asarray(state.slot_valid)
    checked = 0
    for p in alive:
        if not sv[p].any():
            continue
        want = np.asarray(pq.encode(jnp.asarray(cbs[slot[p]]),
                                    jnp.asarray(vecs[p])))
        got = codes[p].T                       # (C, m)
        rows = np.flatnonzero(sv[p])
        assert (want[rows] == got[rows]).all(), f"codes diverged at {p}"
        checked += len(rows)
    assert checked > 0, "audit found nothing to check"
    return checked


def _churn(cfg, seed=0, n=2500, retrain_every=3):
    data = make_clustered(n, d=cfg.dim, k=6, seed=seed)
    drv = UBISDriver(cfg, data[:300], round_size=128, bg_ops_per_round=8,
                     pq_retrain_every=retrain_every)
    rng = np.random.default_rng(seed)
    drv.insert(data[: n // 2], np.arange(n // 2))
    drv.delete(rng.choice(n // 2, size=n // 5, replace=False))
    drv.insert(data[n // 2:], np.arange(n // 2, n))
    drv.flush(max_ticks=40)
    return drv, data


@pytest.mark.parametrize("mode", ["ubis", "spfresh"])
def test_codes_track_floats_through_churn(mode):
    """Insert/delete/split/merge/compact/reassign + scheduled re-trains:
    the code plane never diverges from the float plane."""
    drv, _ = _churn(_mk_cfg(mode), seed=1)
    assert drv.stats["bg_ops"] > 0, "churn produced no structural ops"
    if mode == "ubis":
        assert drv.stats["pq_retrains"] > 0, "no re-train was scheduled"
    assert_codes_in_sync(drv.state, drv.cfg)


def test_encode_in_row_blocks_matches_one_pass(monkeypatch):
    """``pq.encode`` over more than ``ENCODE_ROWS`` rows (blocked, the
    last block padded) equals one unblocked pass and a float64 numpy
    nearest-centroid encode."""
    r = np.random.default_rng(4)
    cb = jnp.asarray(r.normal(size=(4, 32, 4)), jnp.float32)
    x = jnp.asarray(r.normal(size=(300, 16)), jnp.float32)
    one = pq.encode(cb, x)
    monkeypatch.setattr(pq, "ENCODE_ROWS", 64)
    np.testing.assert_array_equal(pq.encode(cb, x), one)
    c = np.asarray(cb, np.float64)
    xs = np.asarray(x, np.float64).reshape(300, 4, 4)
    sc = (np.einsum("jkd,jkd->jk", c, c)[None]
          - 2 * np.einsum("njd,jkd->njk", xs, c))
    np.testing.assert_array_equal(np.asarray(one), sc.argmin(-1))


def test_decode_reencode_fixed_point():
    """Decode -> nearest-centroid re-encode is a fixed point (decoded
    vectors quantize back to their own code)."""
    drv, _ = _churn(_mk_cfg(), seed=2, n=1200)
    state = drv.state
    cbs = np.asarray(state.pq_codebooks)
    slot = np.asarray(state.pq_posting_slot)
    status = np.asarray(vm.unpack_status(state.rec_meta))
    alive = np.flatnonzero(np.asarray(state.allocated) & (status != 3))
    sv = np.asarray(state.slot_valid)
    codes = np.asarray(state.codes)
    hit = 0
    for p in alive[:16]:
        rows = np.flatnonzero(sv[p])
        if not len(rows):
            continue
        cb = jnp.asarray(cbs[slot[p]])
        got = jnp.asarray(codes[p].T[rows])        # (r, m)
        again = pq.encode(cb, pq.decode(cb, got))
        assert (np.asarray(again) == np.asarray(got)).all()
        hit += len(rows)
    assert hit > 0


def test_use_pq_false_is_bit_identical_to_float_path():
    """With use_pq=False the two-stage machinery must be fully inert:
    search equals the pre-quant float implementation bit for bit."""
    cfg = _mk_cfg(use_pq=False, pq_m=8)
    drv, data = _churn(cfg, seed=3, n=1500)
    state, k, nprobe = drv.state, 10, cfg.nprobe
    queries = jnp.asarray(make_clustered(32, d=cfg.dim, seed=7))
    found, scores, _ = search_fn(state, cfg, queries, k)

    # the seed float search, inlined verbatim as the identity oracle
    # (jitted like the production path so XLA fuses both identically)
    @jax.jit
    def oracle(state, queries):
        Q = queries.shape[0]
        q32 = queries.astype(jnp.float32)
        vis = vm.visible(state.rec_meta, state.allocated,
                         state.global_version)
        csc = ops.centroid_score(q32, state.centroids, vis, backend="off")
        _, probe = jax.lax.top_k(-csc, nprobe)
        pscores = ref.posting_scan_gather(
            q32, state.vectors, state.slot_valid, vis,
            probe.astype(jnp.int32))
        pids = state.ids[probe]
        cscores = ops.centroid_score(q32, state.cache_vecs,
                                     state.cache_valid, backend="off")
        cids = jnp.broadcast_to(state.cache_ids[None, :],
                                (Q, cfg.cache_capacity))
        all_scores = jnp.concatenate([pscores.reshape(Q, -1), cscores], 1)
        all_ids = jnp.concatenate([pids.reshape(Q, -1), cids], 1)
        neg, idx = jax.lax.top_k(-all_scores, k)
        want = jnp.where(-neg < BIG / 2,
                         jnp.take_along_axis(all_ids, idx, axis=1), -1)
        return want, -neg

    want_found, want_scores = oracle(state, queries)
    np.testing.assert_array_equal(np.asarray(found),
                                  np.asarray(want_found))
    np.testing.assert_array_equal(np.asarray(scores),
                                  np.asarray(want_scores))


def test_pq_search_recall_close_to_float():
    """Two-stage ADC + rerank stays within 5 recall points of the float
    scan on the same state (the ISSUE acceptance bar, shrunk to CI size)."""
    cfg = _mk_cfg(pq_m=8, pq_ksub=64, rerank_k=96)
    drv, data = _churn(cfg, seed=4, n=3000)
    queries = make_clustered(64, d=cfg.dim, seed=11)
    found = drv.search(queries, 10).ids
    true, _ = brute_force(drv.state, drv.cfg, jnp.asarray(queries), 10)
    rec_pq = metrics.recall_at_k(found, np.asarray(true))
    # same state searched through the float phase-2 (use_pq off)
    fcfg = _mk_cfg(pq_m=8, pq_ksub=64, use_pq=False)
    found_f, _, _ = search_fn(drv.state, fcfg, jnp.asarray(queries),
                                      10)
    rec_f = metrics.recall_at_k(np.asarray(found_f), np.asarray(true))
    assert rec_pq >= rec_f - 0.05, (rec_pq, rec_f)


def test_retrain_rotates_versions_and_keeps_old_codes_decodable():
    """A re-train installs a new generation in the evicted slot, re-encodes
    only postings pinned to it, and leaves every other posting's codes
    byte-identical (decodable under their original generation)."""
    cfg = _mk_cfg()
    drv, _ = _churn(cfg, seed=5, n=1500, retrain_every=0)  # no auto retrain
    state = drv.state
    active0 = int(state.pq_active)
    slot0 = np.asarray(state.pq_posting_slot)
    codes0 = np.asarray(state.codes)
    alloc = np.asarray(state.allocated)

    state2 = pq.retrain_round(state, cfg, jax.random.key(0))
    evict = (active0 + 1) % cfg.pq_versions
    assert int(state2.pq_active) == evict
    assert int(state2.pq_slot_gen[evict]) == int(state.pq_slot_gen[active0]) + 1
    # postings NOT pinned to the evicted slot keep their bytes and slot
    untouched = alloc & (slot0 != evict)
    assert (np.asarray(state2.pq_posting_slot)[untouched]
            == slot0[untouched]).all()
    assert (np.asarray(state2.codes)[untouched]
            == codes0[untouched]).all()
    # and the whole state is still in sync (pinned ones re-encoded)
    assert_codes_in_sync(state2, cfg)
    # float plane untouched: same vectors, ids, visibility
    np.testing.assert_array_equal(np.asarray(state2.vectors),
                                  np.asarray(state.vectors))
    np.testing.assert_array_equal(np.asarray(state2.id_loc),
                                  np.asarray(state.id_loc))
    np.testing.assert_array_equal(np.asarray(state2.rec_meta),
                                  np.asarray(state.rec_meta))
