"""Per-kernel correctness: Pallas (interpret=True on CPU) vs ref.py
oracles, swept over shapes and dtypes."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.kernels import ops, ref

SHAPES_QM = [(8, 16, 16), (17, 33, 40), (128, 512, 64), (130, 700, 96)]
DTYPES = [jnp.float32, jnp.bfloat16]


def _tol(dtype):
    return dict(rtol=3e-2, atol=3e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("Q,M,d", SHAPES_QM)
@pytest.mark.parametrize("dtype", DTYPES)
def test_centroid_score(Q, M, d, dtype, rng):
    q = jnp.asarray(rng.normal(size=(Q, d)), dtype)
    c = jnp.asarray(rng.normal(size=(M, d)), dtype)
    vis = jnp.asarray(rng.random(M) > 0.3)
    a = ops.centroid_score(q, c, vis, backend="off")
    b = ops.centroid_score(q, c, vis, backend="on")
    np.testing.assert_allclose(a, b, **_tol(dtype))


@pytest.mark.parametrize("shape,axis", [((7, 256), -1), ((40, 9), 0),
                                        ((300,), -1)])
def test_exact_argmin_matches_numpy_first_tie(shape, axis, rng):
    """``ref.exact_argmin``/``exact_argmax`` pick numpy's index, the
    lowest among ties (values drawn from a small integer set so rows
    carry many ties)."""
    x = rng.integers(-3, 4, size=shape).astype(np.float32)
    np.testing.assert_array_equal(ref.exact_argmin(jnp.asarray(x), axis),
                                  x.argmin(axis))
    np.testing.assert_array_equal(ref.exact_argmax(jnp.asarray(x), axis),
                                  x.argmax(axis))


@pytest.mark.parametrize("Q,G,C,d", [(5, 3, 24, 16), (64, 8, 96, 40),
                                     (16, 4, 128, 128)])
@pytest.mark.parametrize("dtype", DTYPES)
def test_posting_scan(Q, G, C, d, dtype, rng):
    q = jnp.asarray(rng.normal(size=(Q, d)), dtype)
    tiles = jnp.asarray(rng.normal(size=(G, C, d)), dtype)
    valid = jnp.asarray(rng.random((G, C)) > 0.4)
    a = ops.posting_scan(q, tiles, valid, backend="off")
    b = ops.posting_scan(q, tiles, valid, backend="on")
    np.testing.assert_allclose(a, b, **_tol(dtype))


def test_pq_scan_matches_decoded_float_scan(rng):
    """ADC scores equal the float scan over the *decoded* vectors —
    the semantic contract between the quant plane and the float plane."""
    from repro.quant import pq
    Q, m, dsub, ksub, M, C, P = 4, 4, 3, 16, 8, 24, 3
    d = m * dsub
    cb = jnp.asarray(rng.normal(size=(1, m, ksub, dsub)).astype(np.float32))
    vecs = jnp.asarray(rng.normal(size=(M * C, d)).astype(np.float32))
    codes = pq.encode(cb[0], vecs)
    decoded = pq.decode(cb[0], codes)
    q = jnp.asarray(rng.normal(size=(Q, d)).astype(np.float32))
    luts = pq.lookup_tables(cb, q)
    codes_t = codes.reshape(M, C, m).transpose(0, 2, 1)
    slot = jnp.zeros((M,), jnp.int32)
    probe = jnp.asarray(rng.integers(0, M, (Q, P)).astype(np.int32))
    adc = ref.pq_scan_gather(luts, codes_t, slot, probe)
    want = ref.posting_scan_gather(
        q, decoded.reshape(M, C, d), jnp.ones((M, C), bool),
        jnp.ones((M,), bool), probe)
    np.testing.assert_allclose(adc, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("N,K,d", [(10, 3, 8), (50, 7, 19), (256, 128, 64),
                                   (300, 130, 40)])
def test_kmeans_assign(N, K, d, rng):
    pts = jnp.asarray(rng.normal(size=(N, d)).astype(np.float32))
    cen = jnp.asarray(rng.normal(size=(K, d)).astype(np.float32))
    mask = jnp.asarray(rng.random(N) > 0.2)
    a1, b1 = ops.kmeans_assign(pts, cen, mask, backend="off")
    a2, b2 = ops.kmeans_assign(pts, cen, mask, backend="on")
    # argmin ties can differ; compare scores, and assignments where the
    # best score is unique
    np.testing.assert_allclose(b1, b2, rtol=1e-4, atol=1e-3)
    same = np.asarray(a1) == np.asarray(a2)
    assert same.mean() > 0.99


@pytest.mark.parametrize("Lq,Lk,D,Hq,Hkv", [(37, 53, 16, 4, 2),
                                            (64, 64, 32, 2, 2),
                                            (16, 128, 64, 8, 1)])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("window", [None, 9])
def test_flash_attention(Lq, Lk, D, Hq, Hkv, causal, window, rng):
    B = 2
    q = jnp.asarray(rng.normal(size=(B, Hq, Lq, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, Hkv, Lk, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, Hkv, Lk, D)).astype(np.float32))
    a = ops.flash_attention(q, k, v, causal=causal, window=window,
                            backend="off")
    b = ops.flash_attention(q, k, v, causal=causal, window=window,
                            backend="on")
    np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# Fused score+select kernels: the pallas path must be BIT-identical to
# the ref twin (scores and indices), including tie order — integer-
# valued float32 data makes every sum exact and ties frequent.
# ---------------------------------------------------------------------------


def _int_normal(rng, shape, lo=-3, hi=4):
    return jnp.asarray(rng.integers(lo, hi, shape).astype(np.float32))


@pytest.mark.parametrize("Q,M,d,k", [(1, 7, 5, 3), (9, 33, 24, 33),
                                     (128, 512, 64, 16), (5, 130, 16, 10)])
def test_centroid_topk_parity(Q, M, d, k, rng):
    q = _int_normal(rng, (Q, d))
    c = _int_normal(rng, (M, d))
    vis = jnp.asarray(rng.random(M) > 0.3)
    s1, i1 = ops.centroid_topk(q, c, vis, k=k, backend="off")
    s2, i2 = ops.centroid_topk(q, c, vis, k=k, backend="on")
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


def test_centroid_topk_all_masked(rng):
    """No visible centroid: every score is the BIG sentinel and both
    backends agree on the (degenerate) index order."""
    q = _int_normal(rng, (4, 8))
    c = _int_normal(rng, (12, 8))
    vis = jnp.zeros((12,), bool)
    s1, i1 = ops.centroid_topk(q, c, vis, k=5, backend="off")
    s2, i2 = ops.centroid_topk(q, c, vis, k=5, backend="on")
    assert np.all(np.asarray(s1) >= ref.BIG / 2)
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


def test_centroid_topk_ties(rng):
    """Duplicate centroids: ties must break lowest-index-first on both
    backends (the lax.top_k discipline)."""
    q = _int_normal(rng, (6, 16))
    base = _int_normal(rng, (8, 16))
    c = jnp.concatenate([base, base, base], axis=0)  # every score x3
    vis = jnp.ones((24,), bool)
    s1, i1 = ops.centroid_topk(q, c, vis, k=24, backend="off")
    s2, i2 = ops.centroid_topk(q, c, vis, k=24, backend="on")
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


@pytest.mark.parametrize("Q,M,C,P,d,k", [(1, 12, 128, 1, 128, 5),
                                         (6, 12, 128, 4, 128, 17),
                                         (3, 9, 128, 5, 128, 128)])
def test_posting_scan_topk_parity(Q, M, C, P, d, k, rng):
    q = _int_normal(rng, (Q, d))
    vectors = _int_normal(rng, (M, C, d), lo=-2, hi=3)
    slot_valid = jnp.asarray(rng.random((M, C)) > 0.3)
    vis = jnp.asarray(rng.random(M) > 0.2)
    probe = jnp.asarray(rng.integers(0, M, (Q, P)).astype(np.int32))
    s1, i1 = ops.posting_scan_topk(q, vectors, slot_valid, vis, probe,
                                   k=k, backend="off")
    s2, i2 = ops.posting_scan_topk(q, vectors, slot_valid, vis, probe,
                                   k=k, backend="on")
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


def test_posting_scan_topk_sparse_and_qp_ok(rng):
    """k beyond the live-candidate count: the tail is BIG on both
    backends; a per-(query, probe) ownership mask is honoured."""
    Q, M, C, P, d = 4, 6, 128, 3, 128
    q = _int_normal(rng, (Q, d))
    vectors = _int_normal(rng, (M, C, d), lo=-2, hi=3)
    slot_valid = jnp.asarray(rng.random((M, C)) > 0.95)  # ~6 live per tile
    vis = jnp.ones((M,), bool)
    probe = jnp.asarray(rng.integers(0, M, (Q, P)).astype(np.int32))
    qp_ok = jnp.asarray(rng.integers(0, 2, (Q, P)).astype(np.int32))
    k = P * C  # every candidate requested
    s1, i1 = ops.posting_scan_topk(q, vectors, slot_valid, vis, probe,
                                   k=k, qp_ok=qp_ok, backend="off")
    s2, i2 = ops.posting_scan_topk(q, vectors, slot_valid, vis, probe,
                                   k=k, qp_ok=qp_ok, backend="on")
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    assert np.any(np.asarray(s1) >= ref.BIG / 2)  # sparse -> BIG tail


@pytest.mark.parametrize("Q,V,m,ksub,M,C,P,k", [(1, 2, 8, 128, 12, 128, 1, 3),
                                                (6, 2, 8, 128, 12, 128, 4, 20),
                                                (3, 3, 4, 256, 9, 128, 5, 64),
                                                # the SIFT1M deployment's
                                                # m, ksub, C, nprobe, rerank
                                                (2, 2, 16, 256, 40, 96, 32,
                                                 64),
                                                # two query groups, the
                                                # last one short
                                                (11, 2, 8, 256, 12, 96, 4,
                                                 20)])
def test_pq_scan_topk_parity(Q, V, m, ksub, M, C, P, k, rng):
    luts = _int_normal(rng, (Q, V, m, ksub), lo=0, hi=8)
    codes = jnp.asarray(rng.integers(0, ksub, (M, m, C)).astype(np.uint8))
    slot = jnp.asarray(rng.integers(0, V, (M,)).astype(np.int32))
    slot_valid = jnp.asarray(rng.random((M, C)) > 0.3)
    vis = jnp.asarray(rng.random(M) > 0.2)
    probe = jnp.asarray(rng.integers(0, M, (Q, P)).astype(np.int32))
    s1, i1 = ops.pq_scan_topk(luts, codes, slot, slot_valid, vis, probe,
                              k=k, backend="off")
    s2, i2 = ops.pq_scan_topk(luts, codes, slot, slot_valid, vis, probe,
                              k=k, backend="on")
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


def test_pq_scan_topk_all_invalid(rng):
    """Every probed posting invisible: scores are all BIG and the
    degenerate candidate order still matches the ref twin."""
    Q, V, m, ksub, M, C, P, k = 3, 2, 4, 128, 8, 128, 3, 7
    luts = _int_normal(rng, (Q, V, m, ksub), lo=0, hi=8)
    codes = jnp.asarray(rng.integers(0, ksub, (M, m, C)).astype(np.uint8))
    slot = jnp.zeros((M,), jnp.int32)
    slot_valid = jnp.ones((M, C), bool)
    vis = jnp.zeros((M,), bool)
    probe = jnp.asarray(rng.integers(0, M, (Q, P)).astype(np.int32))
    s1, i1 = ops.pq_scan_topk(luts, codes, slot, slot_valid, vis, probe,
                              k=k, backend="off")
    s2, i2 = ops.pq_scan_topk(luts, codes, slot, slot_valid, vis, probe,
                              k=k, backend="on")
    assert np.all(np.asarray(s1) >= ref.BIG / 2)
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


@pytest.mark.parametrize("Q,M,C,P,k,live", [(4, 6, 128, 3, 3 * 128, 0.05),
                                            (3, 40, 96, 32, 64, 0.01)])
def test_pq_scan_topk_sparse_and_qp_ok(Q, M, C, P, k, live, rng):
    """Fewer live candidates than k: the tail is BIG on both backends,
    in the ref twin's tie order; a per-(query, probe) ownership mask is
    honoured."""
    V, m, ksub = 2, 16, 256
    luts = _int_normal(rng, (Q, V, m, ksub), lo=0, hi=8)
    codes = jnp.asarray(rng.integers(0, ksub, (M, m, C)).astype(np.uint8))
    slot = jnp.asarray(rng.integers(0, V, (M,)).astype(np.int32))
    slot_valid = jnp.asarray(rng.random((M, C)) < live)
    vis = jnp.ones((M,), bool)
    probe = jnp.asarray(rng.integers(0, M, (Q, P)).astype(np.int32))
    qp_ok = jnp.asarray(rng.integers(0, 2, (Q, P)).astype(np.int32))
    s1, i1 = ops.pq_scan_topk(luts, codes, slot, slot_valid, vis, probe,
                              k=k, qp_ok=qp_ok, backend="off")
    s2, i2 = ops.pq_scan_topk(luts, codes, slot, slot_valid, vis, probe,
                              k=k, qp_ok=qp_ok, backend="on")
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    assert np.any(np.asarray(s1) >= ref.BIG / 2)  # sparse -> BIG tail


@pytest.mark.parametrize("Q,V,m,ksub,M,C,P,k", [(3, 2, 16, 256, 40, 96, 32,
                                                 64),
                                                (3, 3, 8, 200, 7, 133, 3, 64)])
def test_pq_scan_topk_float_tables(Q, V, m, ksub, M, C, P, k, rng):
    """Non-integer tables: the kernel's scores are the float32 sum over
    subspaces in order, bit for bit, with the same ids."""
    luts = jnp.asarray(rng.normal(size=(Q, V, m, ksub)).astype(np.float32))
    codes = jnp.asarray(rng.integers(0, ksub, (M, m, C)).astype(np.uint8))
    slot = jnp.asarray(rng.integers(0, V, (M,)).astype(np.int32))
    slot_valid = jnp.asarray(rng.random((M, C)) > 0.3)
    vis = jnp.asarray(rng.random(M) > 0.2)
    probe = jnp.asarray(rng.integers(0, M, (Q, P)).astype(np.int32))
    s2, i2 = ops.pq_scan_topk(luts, codes, slot, slot_valid, vis, probe,
                              k=k, backend="on")
    s3, i3 = ref.np_pq_scan_topk(
        luts, codes, slot, np.asarray(slot_valid) & np.asarray(vis)[:, None],
        np.ones((Q, P), np.int32), probe, k)
    np.testing.assert_array_equal(np.asarray(s2), s3)
    np.testing.assert_array_equal(np.asarray(i2), i3)


# -- alignment-free sweeps: real-world dims (96/100/300), odd posting
# capacities and non-power-of-two ksub must serve the SAME fused pallas
# path bit-identically — no silent slow-path, no fallback (PR 10).


@pytest.mark.parametrize("Q,M,C,P,d,k", [(1, 7, 33, 3, 96, 5),
                                         (4, 9, 100, 4, 100, 40),
                                         (3, 6, 133, 5, 300, 17)])
def test_posting_scan_topk_misaligned_parity(Q, M, C, P, d, k, rng):
    q = _int_normal(rng, (Q, d))
    vectors = _int_normal(rng, (M, C, d), lo=-2, hi=3)
    slot_valid = jnp.asarray(rng.random((M, C)) > 0.3)
    vis = jnp.asarray(rng.random(M) > 0.2)
    probe = jnp.asarray(rng.integers(0, M, (Q, P)).astype(np.int32))
    s1, i1 = ops.posting_scan_topk(q, vectors, slot_valid, vis, probe,
                                   k=k, backend="off")
    s2, i2 = ops.posting_scan_topk(q, vectors, slot_valid, vis, probe,
                                   k=k, backend="on")
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    # candidate encoding uses the LOGICAL capacity C, not the padded one
    assert np.all((np.asarray(i2) >= 0) & (np.asarray(i2) < M * C))


@pytest.mark.parametrize("Q,V,m,ksub,M,C,P,k",
                         [(1, 2, 4, 16, 8, 33, 2, 3),
                          (5, 2, 4, 100, 10, 100, 4, 25),
                          (3, 3, 8, 200, 7, 133, 3, 64)])
def test_pq_scan_topk_misaligned_parity(Q, V, m, ksub, M, C, P, k, rng):
    luts = _int_normal(rng, (Q, V, m, ksub), lo=0, hi=8)
    codes = jnp.asarray(rng.integers(0, ksub, (M, m, C)).astype(np.uint8))
    slot = jnp.asarray(rng.integers(0, V, (M,)).astype(np.int32))
    slot_valid = jnp.asarray(rng.random((M, C)) > 0.3)
    vis = jnp.asarray(rng.random(M) > 0.2)
    probe = jnp.asarray(rng.integers(0, M, (Q, P)).astype(np.int32))
    s1, i1 = ops.pq_scan_topk(luts, codes, slot, slot_valid, vis, probe,
                              k=k, backend="off")
    s2, i2 = ops.pq_scan_topk(luts, codes, slot, slot_valid, vis, probe,
                              k=k, backend="on")
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    assert np.all((np.asarray(i2) >= 0) & (np.asarray(i2) < M * C))


@pytest.mark.parametrize("Q,M,C,d,R,k", [(1, 5, 100, 96, 7, 3),
                                         (4, 8, 33, 100, 24, 10),
                                         (3, 6, 128, 128, 64, 64)])
def test_rerank_topk_parity(Q, M, C, d, R, k, rng):
    """Fused exact rerank: candidate gather + ||v||^2 - 2 q.v +
    tier-spill ADC passthrough + top-k, bit-identical to the ref twin —
    including BIG carry for dead ADC slots and spilled-tile rows."""
    q = _int_normal(rng, (Q, d))
    vectors = _int_normal(rng, (M, C, d), lo=-2, hi=3)
    tier_spilled = jnp.asarray(rng.random(M) > 0.7)
    cand = jnp.asarray(rng.integers(0, M * C, (Q, R)).astype(np.int32))
    adc = np.array(_int_normal(rng, (Q, R), lo=0, hi=9))
    adc[rng.random((Q, R)) > 0.8] = ref.BIG  # dead candidate slots
    adc = jnp.asarray(adc)
    s1, i1 = ops.rerank_topk(q, vectors, tier_spilled, cand, adc, k=k,
                             backend="off")
    s2, i2 = ops.rerank_topk(q, vectors, tier_spilled, cand, adc, k=k,
                             backend="on")
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    # selected indices address the flattened (M*C) vector store
    assert np.all((np.asarray(i2) >= 0) & (np.asarray(i2) < M * C))


def test_rerank_topk_all_dead(rng):
    """Every ADC slot dead: the fused kernel carries BIG through and
    both backends agree on the degenerate order."""
    Q, M, C, d, R, k = 2, 4, 33, 100, 9, 5
    q = _int_normal(rng, (Q, d))
    vectors = _int_normal(rng, (M, C, d), lo=-2, hi=3)
    tier_spilled = jnp.zeros((M,), bool)
    cand = jnp.asarray(rng.integers(0, M * C, (Q, R)).astype(np.int32))
    adc = jnp.full((Q, R), ref.BIG, jnp.float32)
    s1, i1 = ops.rerank_topk(q, vectors, tier_spilled, cand, adc, k=k,
                             backend="off")
    s2, i2 = ops.rerank_topk(q, vectors, tier_spilled, cand, adc, k=k,
                             backend="on")
    assert np.all(np.asarray(s1) >= ref.BIG / 2)
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


def test_kmeans_assign_large_nonmultiple_k(rng):
    """K > 128 and not a multiple of the 128-lane tile, mask=None: the
    sentinel-row padding must never win an assignment."""
    N, K, d = 64, 200, 24
    pts = jnp.asarray(rng.normal(size=(N, d)).astype(np.float32))
    cen = jnp.asarray(rng.normal(size=(K, d)).astype(np.float32))
    a1, b1 = ops.kmeans_assign(pts, cen, backend="off")
    a2, b2 = ops.kmeans_assign(pts, cen, backend="on")
    np.testing.assert_allclose(b1, b2, rtol=1e-4, atol=1e-3)
    assert np.all(np.asarray(a2) < K)
    same = np.asarray(a1) == np.asarray(a2)
    assert same.mean() > 0.99


def test_no_fallback_on_misaligned_shapes(rng):
    """The kernels are alignment-free: a pallas-backend request with
    misaligned storage shapes serves the Pallas path and reports NO
    fallback (the PR-10 contract — this test pinned the opposite
    behaviour before the wrappers learned to pad)."""
    from repro.obs import Obs
    obs = Obs()
    ops.observe_fallbacks(obs)
    Q, V, m, ksub, M, C, P = 2, 1, 2, 16, 4, 24, 2  # C, ksub misaligned
    luts = jnp.asarray(rng.normal(size=(Q, V, m, ksub)).astype(np.float32))
    codes = jnp.asarray(rng.integers(0, ksub, (M, m, C)).astype(np.uint8))
    slot = jnp.zeros((M,), jnp.int32)
    slot_valid = jnp.ones((M, C), bool)
    vis = jnp.ones((M,), bool)
    probe = jnp.asarray(rng.integers(0, M, (Q, P)).astype(np.int32))
    sig = ("test-misaligned",)
    with ops.count_fallback_dispatches(obs, sig):
        ops.pq_scan_topk(luts, codes, slot, slot_valid, vis, probe, k=3,
                         backend="on")
        q = jnp.asarray(rng.normal(size=(Q, 24)).astype(np.float32))
        vecs = jnp.asarray(rng.normal(size=(M, C, 24)).astype(np.float32))
        ops.posting_scan_topk(q, vecs, slot_valid, vis, probe, k=3,
                              backend="on")
    assert obs.counter("kernel_fallback").value == 0.0
    assert obs.counter("kernel_fallback_traces").value == 0.0
    assert obs.events("kernel_fallback") == []


def test_fallback_dispatch_counting():
    """The two-clock fallback plane: ``kernel_fallback_traces`` bumps at
    note (trace) time, ``kernel_fallback`` bumps per wrapped dispatch by
    the signature's memoized fallback count — including cache-warm
    dispatches where the note itself never re-runs."""
    from repro.obs import Obs
    obs = Obs()
    ops.observe_fallbacks(obs)
    sig = ("plane", "on", 100)
    # first dispatch of this signature: the program "traces" and notes
    with ops.count_fallback_dispatches(obs, sig):
        ops._note_fallback("some_kernel", "no pallas lowering")
        ops._note_fallback("some_kernel", "no pallas lowering")  # same key
        ops._note_fallback("other_kernel", "int8 unsupported")
    assert obs.counter("kernel_fallback_traces").value == 3.0
    assert obs.counter("kernel_fallback").value == 2.0  # distinct keys
    evs = obs.events("kernel_fallback")
    assert {e["kernel"] for e in evs} == {"some_kernel", "other_kernel"}
    # cache-warm dispatch: no notes run, the memo still counts 2
    with ops.count_fallback_dispatches(obs, sig):
        pass
    assert obs.counter("kernel_fallback").value == 4.0
    assert obs.counter("kernel_fallback_traces").value == 3.0
    assert len(obs.events("kernel_fallback")) == 2  # one-shot per key
    # a different signature captures independently
    with ops.count_fallback_dispatches(obs, ("plane", "on", 128)):
        pass
    assert obs.counter("kernel_fallback").value == 4.0
    # reset clears the memo, the sinks and the one-shot dedup
    ops.reset_fallback_state()
    obs2 = Obs()
    ops.observe_fallbacks(obs2)
    with ops.count_fallback_dispatches(obs2, sig):
        ops._note_fallback("some_kernel", "no pallas lowering")
    assert obs2.counter("kernel_fallback").value == 1.0
    assert len(obs2.events("kernel_fallback")) == 1


def test_kernel_backend_names_are_validated():
    """``use_pallas`` and the ops ``backend`` share one spelling:
    "auto" | "on" ("on" means the kernels) | "off" (the jnp twins).
    Anything else raises instead of silently meaning the twins."""
    from repro.core import UBISConfig
    assert UBISConfig().use_pallas == "auto"
    q = jnp.zeros((2, 8), jnp.float32)
    np.testing.assert_array_equal(
        ops.centroid_score(q, q, backend="on"),
        ops.centroid_score(q, q, backend="off"))
    for bad in ("pallas", "ref", "ON", ""):
        with pytest.raises(ValueError, match="use_pallas"):
            UBISConfig(use_pallas=bad)
        with pytest.raises(ValueError, match="backend"):
            ops.centroid_score(q, q, backend=bad)


def test_driver_close_detaches_fallback_sink():
    """UBISDriver.close() unregisters its Obs bundle so later notes no
    longer reach it."""
    from repro.core import UBISConfig, UBISDriver
    cfg = UBISConfig(dim=16, max_postings=8, capacity=16, l_min=2,
                     l_max=12, cache_capacity=16, max_ids=1 << 8,
                     nprobe=2, use_pallas="off")
    rng = np.random.default_rng(0)
    drv = UBISDriver(cfg, rng.normal(size=(20, 16)).astype(np.float32))
    drv.close()
    ops._note_fallback("k", "r")
    assert drv.obs.counter("kernel_fallback_traces").value == 0.0


@pytest.mark.slow
@pytest.mark.parametrize("use_pq", [False, True])
def test_e2e_d100_pallas_bit_identical_zero_fallback(use_pq):
    """End-to-end PR-10 acceptance: a pallas-backend index at d=100
    (odd capacity, non-power-of-two ksub) answers bit-identically to
    the ref backend through inserts/deletes/splits, and the fallback
    counters stay at ZERO — the alignment slow-path hole is closed."""
    from repro.core import UBISConfig, UBISDriver

    def build(backend):
        cfg = UBISConfig(dim=100, max_postings=24, capacity=33, l_min=4,
                         l_max=28, cache_capacity=64, max_ids=1 << 11,
                         nprobe=6, use_pq=use_pq,
                         use_pallas=backend,
                         pq_m=4, pq_ksub=100, rerank_k=40)
        r = np.random.default_rng(7)
        seed = r.integers(-3, 4, (80, 100)).astype(np.float32)
        data = r.integers(-3, 4, (300, 100)).astype(np.float32)
        drv = UBISDriver(cfg, seed)
        drv.insert(data, np.arange(300))
        drv.delete(np.arange(0, 300, 7))
        drv.flush(max_ticks=6)
        q = r.integers(-3, 4, (5, 100)).astype(np.float32)
        return drv, drv.search(q, 10)

    drv_p, res_p = build("on")
    _, res_r = build("off")
    np.testing.assert_array_equal(np.asarray(res_p.ids),
                                  np.asarray(res_r.ids))
    np.testing.assert_array_equal(np.asarray(res_p.scores),
                                  np.asarray(res_r.scores))
    assert drv_p.obs.counter("kernel_fallback").value == 0.0
    assert drv_p.obs.counter("kernel_fallback_traces").value == 0.0


def test_flash_attention_matches_chunked(rng):
    """The pure-JAX chunked attention (model fast path) agrees with the
    kernel oracle."""
    from repro.models.attention import chunked_attention, local_attention
    B, Hq, Hkv, L, D = 2, 4, 2, 96, 16
    q = jnp.asarray(rng.normal(size=(B, Hq, L, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, Hkv, L, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, Hkv, L, D)).astype(np.float32))
    a = ref.flash_attention(q, k, v, causal=True)
    b = chunked_attention(q, k, v, causal=True, chunk_q=32, chunk_k=32,
                          backend="off")
    np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)
    # windowed: blocked-local path vs masked reference
    w = 32
    a = ref.flash_attention(q, k, v, causal=True, window=w)
    b = local_attention(q, k, v, window=w, backend="off")
    np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)
