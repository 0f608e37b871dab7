"""Pallas TPU kernel: fused phase-1 centroid scoring + running top-k.

The unfused phase 1 (``centroid_score`` + ``lax.top_k``) writes the full
(Q, M) score matrix to HBM only for top-k to immediately throw away all
but ``nprobe`` entries per query.  This kernel keeps a running
(score, index) top-k list per query block in the *output* refs instead —
the TPU grid is sequential over the centroid axis, so out-ref carry is
the same online-reduction idiom flash attention uses for its running
softmax (and ``kmeans_assign`` uses for its k=1 argmin): no (Q, M)
intermediate ever leaves VMEM.

    q   : (Q, d)        queries (VMEM-resident per block)
    c   : (M, d)        centroids, streamed in (bm, d) tiles
    vis : (1, M) bool   visibility mask (False -> BIG sentinel)
    ->  scores (Q, k) f32 ascending, idx (Q, k) int32

Tie discipline: candidates are visited in index order and the running
list orders equal scores by arrival, so ties break lowest-index-first —
exactly ``lax.top_k``'s rule.  The ref twin (``ref.centroid_topk``) is
therefore bit-identical, selection order included.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .posting_scan import BIG, dots_qt, sq_norms_row

DEFAULT_BQ = 128
DEFAULT_BM = 512


def merge_topk(run_s, run_i, tile_s, tile_i, k: int):
    """Merge a running top-k with a tile of fresh candidates.

    run_s/run_i: (rows, k) current best scores (ascending) and indices;
    tile_s/tile_i: (rows, n) this tile's candidate scores and indices.
    Returns the new (rows, k) pair, ascending by (score, arrival).
    Running entries (earlier candidates) sit before tile entries in the
    concatenation, so the global tie order is lowest-candidate-index-
    first, matching ``lax.top_k`` on the full score row.  Empty running
    slots hold +inf (> BIG), so masked-but-real candidates always win
    over them.
    """
    s = jnp.concatenate([run_s, tile_s], axis=1)        # (rows, k + n)
    idx = jnp.concatenate([run_i, tile_i], axis=1)
    return topk_rows(s, idx, k)


def topk_rows(s, idx, k: int):
    """The k smallest entries of each row of ``s`` (with their ``idx``),
    ascending; ties go to the lowest position.

    Selection is k rounds of (min, first-position-of-min, mask) — VPU
    and cross-lane reductions only, no sort/top_k lowering needed.  Each
    round's pick lands in lane ``t`` of the (rows, k) result, so no
    vector ever changes layout.
    """
    rows, n_all = s.shape
    pos = jax.lax.broadcasted_iota(jnp.int32, (rows, n_all), 1)
    out_lane = jax.lax.broadcasted_iota(jnp.int32, (rows, k), 1)
    out_s = jnp.zeros((rows, k), s.dtype)
    out_i = jnp.zeros((rows, k), idx.dtype)
    for t in range(k):                                  # k static, small
        best = jnp.min(s, axis=1, keepdims=True)        # (rows, 1)
        arg = jnp.min(jnp.where(s == best, pos, n_all), axis=1,
                      keepdims=True)
        hit = pos == arg
        pick = jnp.sum(jnp.where(hit, idx, 0), axis=1, keepdims=True)
        out_s = jnp.where(out_lane == t, best, out_s)
        out_i = jnp.where(out_lane == t, pick, out_i)
        s = jnp.where(hit, jnp.inf, s)                  # retire the pick
    return out_s, out_i


def _kernel(q_ref, c_ref, vis_ref, s_ref, i_ref, *, k):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        # +inf (not BIG): real-but-masked candidates carry BIG and must
        # outrank empty slots, or the sentinel indices would leak.
        s_ref[...] = jnp.full_like(s_ref, jnp.inf)
        i_ref[...] = jnp.zeros_like(i_ref)

    q = q_ref[...].astype(jnp.float32)                  # (bq, d)
    c = c_ref[...].astype(jnp.float32)                  # (bm, d)
    vis = vis_ref[...] != 0                             # (1, bm)
    # fused norm epilogue
    score = jnp.where(vis, sq_norms_row(c) - 2.0 * dots_qt(q, c), BIG)
    bq, bm = score.shape
    tile_i = (jax.lax.broadcasted_iota(jnp.int32, (bq, bm), 1)
              + j * bm)
    s, i = merge_topk(s_ref[...], i_ref[...], score, tile_i, k)
    s_ref[...] = s
    i_ref[...] = i


@functools.partial(jax.jit, static_argnames=("k", "bq", "bm", "interpret"))
def centroid_topk(q: jax.Array, c: jax.Array, vis: jax.Array, *, k: int,
                  bq: int = DEFAULT_BQ, bm: int = DEFAULT_BM,
                  interpret: bool = False):
    """Padded-shape Pallas entry.  Q % bq == 0, M % bm == 0, d % 128 == 0
    are guaranteed by the ops.py wrapper; padded centroid rows arrive
    with vis=False."""
    Q, d = q.shape
    M = c.shape[0]
    grid = (Q // bq, M // bm)
    return pl.pallas_call(
        functools.partial(_kernel, k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1, bm), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Q, k), jnp.float32),
            jax.ShapeDtypeStruct((Q, k), jnp.int32),
        ],
        interpret=interpret,
    )(q, c, vis)
