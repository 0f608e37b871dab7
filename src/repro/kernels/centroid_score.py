"""Pallas TPU kernel: phase-1 centroid scoring (coarse filter).

Every search *and* every insert locate step scores the full centroid
table: (Q, d) x (M, d) -> (Q, M).  This is a blocked GEMM with a fused
``+||c||^2`` epilogue and visibility masking — centroid norms are
computed in-kernel from the resident tile, saving one HBM stream.

The visibility mask encodes the Posting Recorder rule (allocated, not
DELETED, weight <= snapshot version), evaluated by the caller.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .posting_scan import BIG, dots_qt, sq_norms_row

DEFAULT_BQ = 128
DEFAULT_BM = 512


def _kernel(q_ref, c_ref, vis_ref, out_ref):
    q = q_ref[...].astype(jnp.float32)          # (BQ, d)
    c = c_ref[...].astype(jnp.float32)          # (BM, d)
    vis = vis_ref[...] != 0                     # (1, BM)
    # fused norm epilogue
    out_ref[...] = jnp.where(vis, sq_norms_row(c) - 2.0 * dots_qt(q, c),
                             BIG)


@functools.partial(jax.jit, static_argnames=("bq", "bm", "interpret"))
def centroid_score(q: jax.Array, c: jax.Array, vis: jax.Array,
                   *, bq: int = DEFAULT_BQ, bm: int = DEFAULT_BM,
                   interpret: bool = False) -> jax.Array:
    Q, d = q.shape
    M = c.shape[0]
    grid = (Q // bq, M // bm)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1, bm), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bq, bm), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Q, M), jnp.float32),
        interpret=interpret,
    )(q, c, vis)
