"""Pallas TPU kernel: k-means assignment step.

BalanceSplit (paper Alg. 1) runs 2-means on every split, and the initial
build runs full k-means; the assignment step (argmin over centroids) is
its compute hot-spot.  The kernel streams centroid tiles while a point
tile stays VMEM-resident, carrying a running (best score, best index)
pair across the centroid grid dimension in the *output* refs — the TPU
grid is executed sequentially over the last axis, so out-ref carry is
the idiomatic accumulator pattern.

    points    : (N, d)
    centroids : (K, d)
    ->  assign (N,) int32, best (N,) f32  (lane-dense (1, N) carries)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .posting_scan import BIG, dots_qt, lane_row, sq_norms_row

DEFAULT_BN = 256
DEFAULT_BK = 128


def _kernel(p_ref, c_ref, assign_ref, best_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        assign_ref[...] = jnp.full_like(assign_ref, -1)
        best_ref[...] = jnp.full_like(best_ref, BIG)

    p = p_ref[...].astype(jnp.float32)          # (BN, d)
    c = c_ref[...].astype(jnp.float32)          # (BK, d)
    score = sq_norms_row(c) - 2.0 * dots_qt(p, c)   # (BN, BK)
    bk = score.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, score.shape, 1)
    blk_best = jnp.min(score, axis=-1, keepdims=True)          # (BN, 1)
    blk_arg = jnp.min(jnp.where(score == blk_best, lane, bk), axis=-1,
                      keepdims=True) + j * bk
    # lane-dense carry: the per-point pair lives in (1, BN) rows
    blk_best, blk_arg = lane_row(blk_best), lane_row(blk_arg)
    prev_best = best_ref[...]
    take = blk_best < prev_best
    best_ref[...] = jnp.where(take, blk_best, prev_best)
    assign_ref[...] = jnp.where(take, blk_arg, assign_ref[...])


@functools.partial(jax.jit, static_argnames=("bn", "bk", "interpret"))
def kmeans_assign(points: jax.Array, centroids: jax.Array,
                  *, bn: int = DEFAULT_BN, bk: int = DEFAULT_BK,
                  interpret: bool = False):
    """Padded-shape Pallas entry: N % bn == 0 (bn a multiple of 128),
    K % bk == 0, d % 128 == 0, guaranteed by the ops.py wrapper.
    Returns (assign (N,) int32, best (N,) f32)."""
    N, d = points.shape
    K = centroids.shape[0]
    grid = (N // bn, K // bk)
    row = pl.BlockSpec((1, bn), lambda i, j: (0, i))
    assign, best = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bk, d), lambda i, j: (j, 0)),
        ],
        out_specs=[row, row],
        out_shape=[
            jax.ShapeDtypeStruct((1, N), jnp.int32),
            jax.ShapeDtypeStruct((1, N), jnp.float32),
        ],
        interpret=interpret,
    )(points, centroids)
    return assign[0], best[0]
