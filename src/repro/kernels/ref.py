"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the semantic ground truth: tests assert the Pallas
kernels (run with ``interpret=True`` on CPU) match these to tolerance,
sweeping shapes and dtypes.  ``ops.py`` routes to these implementations
on non-TPU backends.

Distance convention: all ANN kernels return *scores*
``s(q, v) = ||v||^2 - 2 q.v`` which order identically to squared L2
(``||q||^2`` is constant per query).  True squared distance is
``s + ||q||^2``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

BIG = 1e30  # masked-score sentinel shared with the Pallas kernels
# f32 matmuls at full precision, as in the Pallas kernels (a TPU's
# default precision rounds f32 operands to bfloat16)
HIGHEST = jax.lax.Precision.HIGHEST


def exact_argmin(x: jax.Array, axis: int = -1) -> jax.Array:
    """``jnp.argmin`` (lowest index among ties) that stays exact on a
    TPU.  XLA's TPU backend keeps the unused value half of argmin's
    (value, index) reduction in bfloat16, so candidates within bf16
    resolution of the minimum (~0.4% relative) can win: on a v5e this
    made 57% of PQ codes differ from a float64 encode.  A f32 min
    followed by a first-match search of the equality mask does not
    narrow.  Returns int32."""
    best = jnp.min(x, axis=axis, keepdims=True)
    return jnp.argmax(x == best, axis=axis).astype(jnp.int32)


def exact_argmax(x: jax.Array, axis: int = -1) -> jax.Array:
    """``jnp.argmax`` counterpart of :func:`exact_argmin`."""
    best = jnp.max(x, axis=axis, keepdims=True)
    return jnp.argmax(x == best, axis=axis).astype(jnp.int32)


def centroid_score(queries: jax.Array, centroids: jax.Array) -> jax.Array:
    """Phase-1 scoring.  (Q, d), (M, d) -> (Q, M) float32 scores."""
    q = queries.astype(jnp.float32)
    c = centroids.astype(jnp.float32)
    cn = jnp.sum(c * c, axis=-1)
    return cn[None, :] - 2.0 * jnp.matmul(q, c.T, precision=HIGHEST)


def posting_scan(queries: jax.Array, tiles: jax.Array,
                 valid: jax.Array) -> jax.Array:
    """Phase-2 masked scan.

    queries: (Q, d); tiles: (G, C, d) gathered posting tiles;
    valid: (G, C) bool live-slot mask.
    Returns (Q, G*C) float32 scores with +inf at invalid slots.
    """
    q = queries.astype(jnp.float32)
    G, C, d = tiles.shape
    v = tiles.reshape(G * C, d).astype(jnp.float32)
    vn = jnp.sum(v * v, axis=-1)
    s = vn[None, :] - 2.0 * jnp.matmul(q, v.T, precision=HIGHEST)
    return jnp.where(valid.reshape(1, G * C), s, BIG)


def kmeans_assign(points: jax.Array, centroids: jax.Array,
                  mask: jax.Array | None = None):
    """Nearest-centroid assignment.

    points: (N, d); centroids: (K, d); mask: (N,) bool or None.
    Returns (assign (N,) int32, score (N,) f32); masked points get
    assignment -1 and score +inf.
    """
    s = centroid_score(points, centroids)  # (N, K)
    assign = exact_argmin(s)
    best = jnp.min(s, axis=-1)
    if mask is not None:
        assign = jnp.where(mask, assign, -1)
        best = jnp.where(mask, best, BIG)
    return assign, best


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    *, causal: bool = True, window: int | None = None,
                    scale: float | None = None) -> jax.Array:
    """Reference attention.  q: (B, Hq, Lq, D), k/v: (B, Hkv, Lk, D).

    GQA: Hq must be a multiple of Hkv.  ``window``: sliding-window size
    (keys attend within [i - window + 1, i]); None = full.
    """
    B, Hq, Lq, D = q.shape
    Hkv = k.shape[1]
    rep = Hq // Hkv
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    kf = jnp.repeat(k, rep, axis=1).astype(jnp.float32)
    vf = jnp.repeat(v, rep, axis=1).astype(jnp.float32)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), kf) * scale
    Lk = k.shape[2]
    qpos = jnp.arange(Lq)[:, None] + (Lk - Lq)  # align ends (decode-friendly)
    kpos = jnp.arange(Lk)[None, :]
    m = jnp.ones((Lq, Lk), bool)
    if causal:
        m &= kpos <= qpos
    if window is not None:
        m &= kpos > qpos - window
    logits = jnp.where(m[None, None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vf)
    return out.astype(q.dtype)


def pq_scan_gather(luts: jax.Array, codes: jax.Array, slot: jax.Array,
                   probe: jax.Array) -> jax.Array:
    """ADC scan over probed PQ-code tiles (quant plane phase 2).

    luts: (Q, V, m, ksub) per-query per-codebook-slot lookup tables;
    codes: (M, m, C) uint8 subspace-major codes; slot: (M,) int32
    codebook slot of each posting; probe: (Q, P) int32.
    Returns raw (Q, P, C) scores ``sum_j lut[slot[p], j, code[j, c]]``
    (validity masking is the caller's job — ``pq_scan_topk`` applies it).
    """
    Q, V, m, ksub = luts.shape
    codes_g = codes[probe].astype(jnp.int32)                # (Q, P, m, C)
    # one flat gather per (q, p, j, c): index = slot*m*ksub + j*ksub + code
    # (avoids materializing the (Q, P, m, ksub) per-probe table slice)
    base = (jnp.clip(slot[probe], 0)[:, :, None] * (m * ksub)
            + jnp.arange(m, dtype=jnp.int32)[None, None, :] * ksub)
    idx = base[..., None] + codes_g                         # (Q, P, m, C)
    flat = luts.reshape(Q, V * m * ksub)
    picked = jnp.take_along_axis(flat, idx.reshape(Q, -1), axis=1)
    return jnp.sum(picked.reshape(codes_g.shape), axis=2)   # (Q, P, C)


def centroid_topk(queries: jax.Array, centroids: jax.Array,
                  vis: jax.Array, k: int):
    """Fused phase-1 oracle: masked centroid scores + top-k.

    queries: (Q, d); centroids: (M, d); vis: (M,) bool.
    Returns (scores (Q, k) f32 ascending, idx (Q, k) int32); masked
    centroids carry BIG.  ``lax.top_k`` breaks ties lowest-index-first;
    the Pallas twin reproduces that order bit-identically.
    """
    s = centroid_score(queries, centroids)
    s = jnp.where(vis[None, :], s, BIG)
    neg, idx = jax.lax.top_k(-s, k)
    return -neg, idx.astype(jnp.int32)


def pq_scan_topk(luts: jax.Array, codes: jax.Array, slot: jax.Array,
                 valid: jax.Array, qp_ok: jax.Array, probe: jax.Array,
                 k: int):
    """Fused ADC-scan oracle: masked probe scores + top-k.

    luts: (Q, V, m, ksub); codes: (M, m, C) uint8; slot: (M,) int32;
    valid: (M, C) bool (slot validity & posting visibility combined);
    qp_ok: (Q, P) per-(query, probe) mask; probe: (Q, P) int32.
    Returns (scores (Q, k) ascending, cand (Q, k) int32 flat slot index
    ``probe*C + c``); masked candidates carry BIG.  Tie order is
    probe-position-major (the flattened (P, C) order), matching the
    running-merge order of the Pallas twin bit-identically.
    """
    raw = pq_scan_gather(luts, codes, slot, probe)          # (Q, P, C)
    Q, P, C = raw.shape
    ok = valid[probe] & (qp_ok != 0)[:, :, None]
    s = jnp.where(ok, raw, BIG)
    neg, pos = jax.lax.top_k(-s.reshape(Q, P * C), k)
    cand_all = (probe[:, :, None] * C
                + jnp.arange(C, dtype=jnp.int32)[None, None, :])
    cand = jnp.take_along_axis(cand_all.reshape(Q, P * C), pos, axis=1)
    return -neg, cand.astype(jnp.int32)


def posting_scan_topk(queries: jax.Array, vectors: jax.Array,
                      valid: jax.Array, qp_ok: jax.Array,
                      probe: jax.Array, k: int):
    """Fused float phase-2 oracle: masked probe scan + top-k.

    queries: (Q, d); vectors: (M, C, d); valid: (M, C) bool; qp_ok:
    (Q, P); probe: (Q, P) int32.  Returns (scores (Q, k) ascending,
    cand (Q, k) int32 flat slot index); same tie discipline as
    :func:`pq_scan_topk`.
    """
    q = queries.astype(jnp.float32)
    tiles = vectors[probe].astype(jnp.float32)              # (Q, P, C, d)
    Q, P, C, _ = tiles.shape
    vn = jnp.sum(tiles * tiles, axis=-1)
    dots = jnp.einsum("qd,qpcd->qpc", q, tiles, precision=HIGHEST)
    ok = valid[probe] & (qp_ok != 0)[:, :, None]
    s = jnp.where(ok, vn - 2.0 * dots, BIG)
    neg, pos = jax.lax.top_k(-s.reshape(Q, P * C), k)
    cand_all = (probe[:, :, None] * C
                + jnp.arange(C, dtype=jnp.int32)[None, None, :])
    cand = jnp.take_along_axis(cand_all.reshape(Q, P * C), pos, axis=1)
    return -neg, cand.astype(jnp.int32)


def rerank_topk(queries: jax.Array, vectors: jax.Array,
                tier_spilled: jax.Array, cand: jax.Array,
                adc: jax.Array, k: int):
    """Fused exact-rerank oracle (quant plane stage 2).

    queries: (Q, d); vectors: (M, C, d); tier_spilled: (M,) bool; cand:
    (Q, R) int32 flat slot candidates from ``pq_scan_topk``; adc: (Q, R)
    their ADC scores.  Exact-rescores each candidate's float row,
    keeps the ADC score for tier-spilled postings (codes-only serving),
    carries BIG through empty ADC slots, and returns the top-k
    (scores (Q, k) ascending, cand (Q, k) int32).  Ties break
    lowest-ADC-rank-first (``lax.top_k`` over the R row), matching the
    arrival order of the Pallas twin bit-identically.
    """
    M, C, d = vectors.shape
    q = queries.astype(jnp.float32)
    cv = vectors.reshape(M * C, d)[cand].astype(jnp.float32)  # (Q, R, d)
    exact = (jnp.sum(cv * cv, -1)
             - 2.0 * jnp.einsum("qd,qrd->qr", q, cv, precision=HIGHEST))
    exact = jnp.where(tier_spilled[cand // C], adc, exact)
    exact = jnp.where(adc < BIG / 2, exact, BIG)
    neg, pos = jax.lax.top_k(-exact, k)
    return -neg, jnp.take_along_axis(cand, pos, axis=1).astype(jnp.int32)


def posting_scan_gather(queries: jax.Array, vectors: jax.Array,
                        slot_valid: jax.Array, vis: jax.Array,
                        probe: jax.Array) -> jax.Array:
    """Per-query probe scan (search phase 2).

    queries: (Q, d); vectors: (M, C, d); slot_valid: (M, C) bool;
    vis: (M,) bool posting visibility; probe: (Q, P) int32.
    Returns (Q, P, C) scores; invalid slots / invisible postings -> BIG.
    """
    q = queries.astype(jnp.float32)
    tiles = vectors[probe].astype(jnp.float32)          # (Q, P, C, d)
    vn = jnp.sum(tiles * tiles, axis=-1)
    dots = jnp.einsum("qd,qpcd->qpc", q, tiles, precision=HIGHEST)
    s = vn - 2.0 * dots
    ok = slot_valid[probe] & vis[probe][..., None]
    return jnp.where(ok, s, BIG)
