"""Pallas TPU kernels: phase-2 masked distance scans over posting tiles.

This is the search hot-spot of a cluster-based index (paper Section V:
search efficiency): for a batch of queries, compute masked L2 scores
``||v||^2 - 2 q.v`` against posting slots.  Two kernels:

  * ``posting_scan`` — dense scan of a flattened slot range (the exact
    oracle ``search.brute_force``): the query tile (BQ x d) stays
    resident in VMEM while slot tiles (BV x d) stream through; the
    ``-2 q.v`` term runs on the MXU, the norm epilogue and the
    tombstone masking on the VPU;
  * ``posting_scan_topk`` — the fused float phase 2 of search: probed
    posting tiles stream HBM->VMEM by scalar-prefetched probe id and a
    running top-k is kept on-chip (no (Q, P, C) score tensor in HBM).

Scores accumulate in fp32 regardless of storage dtype.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

BIG = 1e30  # stand-in for +inf that survives top-k arithmetic

DEFAULT_BQ = 128
DEFAULT_BV = 512

# f32 MXU products at full precision on the chip (no-op in interpret mode)
HIGHEST = jax.lax.Precision.HIGHEST


def lane_row(col: jax.Array) -> jax.Array:
    """(n, 1) column -> (1, n) row, exactly (no arithmetic).

    A reduction over lanes leaves its result down the sublanes, while
    score tiles are laid out along lanes.  Mosaic has no direct
    sublane->lane relayout for a vector, so broadcast across one lane
    tile and transpose.  ``n`` is a multiple of 8 (128 for the scans)."""
    n = col.shape[0]
    return jnp.transpose(jnp.broadcast_to(col, (n, 128)))[:1, :]


def sq_norms_row(x: jax.Array) -> jax.Array:
    """(n, d) f32 tile -> (1, n) squared row norms."""
    return lane_row(jnp.sum(x * x, axis=-1, keepdims=True))


def dots_qt(q: jax.Array, x: jax.Array) -> jax.Array:
    """(a, d) x (b, d) -> (a, b) f32 dot products on the MXU."""
    return jax.lax.dot_general(q, x, (((1,), (1,)), ((), ())),
                               precision=HIGHEST,
                               preferred_element_type=jnp.float32)


def _kernel(q_ref, v_ref, valid_ref, out_ref):
    q = q_ref[...].astype(jnp.float32)          # (BQ, d)
    v = v_ref[...].astype(jnp.float32)          # (BV, d)
    valid = valid_ref[...] != 0                 # (1, BV)
    score = sq_norms_row(v) - 2.0 * dots_qt(q, v)
    out_ref[...] = jnp.where(valid, score, BIG)


@functools.partial(jax.jit, static_argnames=("bq", "bv", "interpret"))
def posting_scan(q: jax.Array, v: jax.Array, valid: jax.Array,
                 *, bq: int = DEFAULT_BQ, bv: int = DEFAULT_BV,
                 interpret: bool = False) -> jax.Array:
    """Padded-shape Pallas entry: q (Q, d), v (V, d), valid (1, V) int32.
    Q % bq == 0, V % bv == 0, d % 128 == 0 are guaranteed by the ops.py
    wrapper.  Returns (Q, V) f32 scores, +BIG at invalid slots."""
    Q, d = q.shape
    V = v.shape[0]
    grid = (Q // bq, V // bv)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bv, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1, bv), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bq, bv), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Q, V), jnp.float32),
        interpret=interpret,
    )(q, v, valid)


# ---------------------------------------------------------------------------
# Fused gather scan + on-chip top-k (float phase-2 twin of
# ``pq_scan.pq_scan_topk``).  The probe table is scalar-prefetched and
# each grid step (i, j) DMAs exactly one posting tile HBM->VMEM (Pallas
# double-buffers consecutive steps); the (Q, P, C) score tensor never
# hits HBM — a running top-k (score, flat-slot) list per query is carried
# in the output refs (``merge_topk``, the flash-attention online-reduction
# idiom), with validity and per-(query, probe) ownership masks applied
# in-kernel before selection.  Per-query and per-posting rows carry a
# unit middle axis ((Q, 1, dp), (M, 1, Cp), (Q, 1, k)) so that every
# block's last two dims equal the array's, as Mosaic requires.
# ---------------------------------------------------------------------------


def _gather_topk_kernel(probe_ref, ok_ref, q_ref, v_ref, valid_ref,
                        s_ref, i_ref, *, k, c):
    from .centroid_topk import merge_topk
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        s_ref[...] = jnp.full_like(s_ref, jnp.inf)
        i_ref[...] = jnp.zeros_like(i_ref)

    q = q_ref[...].astype(jnp.float32)            # (1, dp)
    v = v_ref[...].astype(jnp.float32)            # (Cp, dp)
    Cp = v.shape[0]
    vn = jnp.sum(v * v, axis=-1, keepdims=True)   # (Cp, 1)
    raw = lane_row(vn - 2.0 * dots_qt(v, q))      # (1, Cp)
    # slots beyond the LOGICAL capacity ``c`` are wrapper padding: +inf
    # (never selectable — the wrapper guarantees k <= P*c real
    # candidates, all <= BIG < inf) keeps the BIG-tie order of real
    # masked slots intact, and the candidate index uses the logical
    # stride so flat ids match the ref twin bit-for-bit.
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, Cp), 1)
    in_lane = lane < c
    ok = (valid_ref[...] != 0) & (ok_ref[i, j] != 0) & in_lane
    score = jnp.where(ok, raw, jnp.where(in_lane, BIG, jnp.inf))
    cand = lane + probe_ref[i, j] * c
    s, ids = merge_topk(s_ref[...], i_ref[...], score, cand, k)
    s_ref[...] = s
    i_ref[...] = ids


@functools.partial(jax.jit, static_argnames=("k", "c", "interpret"))
def posting_scan_topk(q: jax.Array, vectors: jax.Array, valid: jax.Array,
                      qp_ok: jax.Array, probe: jax.Array,
                      *, k: int, c: int, interpret: bool = False):
    """Fused probe scan + running top-k.

    q: (Q, dp); vectors: (M, Cp, dp); valid: (M, Cp) int32 (slot
    validity & posting visibility, precombined; padding lanes 0);
    qp_ok: (Q, P) int32 per-(query, probe) mask; probe: (Q, P) int32.
    ``c`` is the LOGICAL posting capacity — lanes in [c, Cp) are wrapper
    padding, masked in-kernel via an iota-vs-extent mask.  Returns
    (scores (Q, k) f32 ascending, cand (Q, k) int32 flat slot index
    ``probe*c + lane``); masked candidates carry BIG.  Bit-identical to
    ``ref.posting_scan_topk`` including tie order.  Storage shapes
    arrive 128-aligned from the ops.py wrapper (the assertions below
    never fire).
    """
    Q, d = q.shape
    M, C, _ = vectors.shape
    P = probe.shape[1]
    assert d % 128 == 0 and C % 8 == 0, (d, C)
    assert 0 < c <= C, (c, C)
    row = lambda n: pl.BlockSpec((None, 1, n),
                                 lambda i, j, probe, ok: (i, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(Q, P),
        in_specs=[
            row(d),
            pl.BlockSpec((None, C, d),
                         lambda i, j, probe, ok: (probe[i, j], 0, 0)),
            pl.BlockSpec((None, 1, C),
                         lambda i, j, probe, ok: (probe[i, j], 0, 0)),
        ],
        out_specs=[row(k), row(k)],
    )
    s, ids = pl.pallas_call(
        functools.partial(_gather_topk_kernel, k=k, c=c),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((Q, 1, k), jnp.float32),
            jax.ShapeDtypeStruct((Q, 1, k), jnp.int32),
        ],
        interpret=interpret,
    )(probe, qp_ok, q.reshape(Q, 1, d), vectors, valid.reshape(M, 1, C))
    return s.reshape(Q, k), ids.reshape(Q, k)
