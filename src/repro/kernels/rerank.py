"""Pallas TPU kernel: fused exact rerank of the quant plane's ADC
survivors (the second stage of the two-stage PQ search).

After ``pq_scan_topk`` picks the top-R candidates by ADC score, the
float rerank used to be an XLA gather materialising (Q, R, d) candidate
rows in HBM, an einsum, two ``where`` fixups and a ``top_k``.  This
kernel fuses the whole tail: the candidate table is scalar-prefetched
and each grid step (i, r) DMAs exactly ONE candidate's float row
HBM->VMEM (Pallas double-buffers consecutive steps), computes
``||v||^2 - 2 q.v`` on the VPU, substitutes the ADC score for
tier-spilled candidates (cold-tier plane: their device float tile is
zeroed, so the ADC score IS their serving score), masks empty ADC slots
to BIG, and writes the score into lane r of a (1, R) VMEM row.  The
last step of each query selects the top-k of that row on-chip.  No
(Q, R, d) gather and no (Q, R) score row ever hit HBM: the kernel
writes 2*Q*k words.

    q       : (Q, dp) f32        queries (d zero-padded to 128)
    vflat   : (M*C, dp) f32      posting pool viewed as flat slot rows
    cand    : (Q, R) int32       flat slot candidates (prefetched, and
                                 read as a row for the final selection)
    adc     : (Q, R) f32         the candidates' ADC scores
    spilled : (Q, R) int32       1 where the candidate's posting is
                                 tier-spilled (serve the ADC score)
Output:
    scores  : (Q, k) f32 ascending;  cand_out : (Q, k) int32

Tie discipline: the final selection takes the lowest lane on equal
scores, so ties break lowest-r-first — exactly ``lax.top_k`` over the
(Q, R) exact row, which makes the ref twin (``ref.rerank_topk``)
bit-identical.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from .posting_scan import BIG


def _kernel(cand_ref, q_ref, v_ref, adc_ref, sp_ref, cand_row_ref,
            s_ref, i_ref, row_ref, *, k):
    from .centroid_topk import topk_rows
    r = pl.program_id(1)
    R = row_ref.shape[1]

    q = q_ref[...].astype(jnp.float32)            # (1, dp)
    v = v_ref[...].astype(jnp.float32)            # (1, dp)
    exact = (jnp.sum(v * v, axis=-1, keepdims=True)
             - 2.0 * jnp.sum(q * v, axis=-1, keepdims=True))   # (1, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, R), 1)
    at_r = lane == r
    # this candidate's ADC score and spill flag, read out of their rows
    # (exact: every other lane is the reduction's identity)
    adc = jnp.min(jnp.where(at_r, adc_ref[...], jnp.inf), axis=-1,
                  keepdims=True)
    spilled = jnp.max(jnp.where(at_r, sp_ref[...], 0), axis=-1,
                      keepdims=True)
    # cold-tier passthrough: spilled candidates keep their ADC score
    # (their float row is zeroed); empty ADC slots stay BIG so the
    # final merge's ``score < BIG/2`` id masking keeps working.
    score = jnp.where(spilled != 0, adc, exact)
    score = jnp.where(adc < BIG / 2, score, BIG)
    row_ref[...] = jnp.where(at_r, score, row_ref[...])

    @pl.when(r == R - 1)
    def _select():
        s, ids = topk_rows(row_ref[...], cand_row_ref[...], k)
        s_ref[...] = s
        i_ref[...] = ids


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def rerank_topk(q: jax.Array, vflat: jax.Array, cand: jax.Array,
                adc: jax.Array, spilled: jax.Array,
                *, k: int, interpret: bool = False):
    """Padded-shape Pallas entry.  q: (Q, dp); vflat: (M*C, dp); cand:
    (Q, R) int32 in [0, M*C); adc/spilled: (Q, R).  The ops.py wrapper
    zero-pads d up to a 128 multiple (fp-exact) — the assertion below
    never fires.  Returns (scores (Q, k) ascending, cand (Q, k)).
    Per-query and per-slot rows carry a unit middle axis so that every
    block's last two dims equal the array's, as Mosaic requires."""
    Q, d = q.shape
    N = vflat.shape[0]
    R = cand.shape[1]
    assert d % 128 == 0, d
    assert 0 < k <= R, (k, R)
    cand = cand.astype(jnp.int32)
    row = lambda n: pl.BlockSpec((None, 1, n), lambda i, r, cand: (i, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Q, R),
        in_specs=[
            row(d),
            pl.BlockSpec((None, 1, d), lambda i, r, cand: (cand[i, r], 0, 0)),
            row(R), row(R), row(R),
        ],
        out_specs=[row(k), row(k)],
        scratch_shapes=[pltpu.VMEM((1, R), jnp.float32)],
    )
    s, ids = pl.pallas_call(
        functools.partial(_kernel, k=k),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((Q, 1, k), jnp.float32),
            jax.ShapeDtypeStruct((Q, 1, k), jnp.int32),
        ],
        interpret=interpret,
    )(cand, q.reshape(Q, 1, d), vflat.reshape(N, 1, d),
      adc.reshape(Q, 1, R), spilled.astype(jnp.int32).reshape(Q, 1, R),
      cand.reshape(Q, 1, R))
    return s.reshape(Q, k), ids.reshape(Q, k)
