"""Pallas TPU kernel: fused ADC scan + top-k over packed PQ codes.

The quant-plane twin of ``posting_scan.posting_scan_topk``: for each
(query, probe) pair, stream one posting's uint8 code tile HBM->VMEM,
accumulate per-subspace lookup-table entries, and merge the tile into a
running per-query top-k held in the output refs (``merge_topk``, the
flash-attention online-reduction idiom).  The probe table AND the
per-posting codebook-slot table are scalar-prefetched, so the lookup
table block for grid step (i, j) is selected by the *probed posting's*
codebook version — versioned codebooks cost one extra scalar
indirection, not a second pass.  No (Q, P, C) score tensor ever hits
HBM: the kernel writes 2*k words per query.

The in-kernel gather is expressed as ``m`` small one-hot matmuls
(code row -> one-hot (ksub, C) on the VPU, lut row @ one-hot on the
MXU): TPU has no per-lane dynamic gather, but ksub <= 256 keeps each
one-hot block a single (256, C) tile.  Each product has exactly one
non-zero term, so the sum is exact.  Arithmetic intensity is higher
than the float scan by design — C*m bytes of codes per posting instead
of C*d*4 — which is the whole point of the quant plane.

The validity mask (slot_valid & vis, precombined by ops.py into one
(M, C) row table) and the per-(query, probe) mask (the sharded plane's
``mine``) are applied in-kernel *before* selection — post-hoc masking
is impossible once top-k is fused.

    luts  : (Q, V, m, ksub) f32   per-query per-slot ADC tables
    codes : (M, m, C) uint8       subspace-major code tiles
    slot  : (M,) int32            codebook slot per posting (prefetched)
    probe : (Q, P) int32          posting ids per query (prefetched)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from .posting_scan import BIG, HIGHEST


def _topk_kernel(probe_ref, slot_ref, ok_ref, lut_ref, codes_ref,
                 valid_ref, s_ref, i_ref, *, k, c):
    from .centroid_topk import merge_topk
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        s_ref[...] = jnp.full_like(s_ref, jnp.inf)
        i_ref[...] = jnp.zeros_like(i_ref)

    lut = lut_ref[...].astype(jnp.float32)        # (m, ksub)
    code = codes_ref[...].astype(jnp.int32)       # (m, Cp)
    m, Cp = code.shape
    ksub = lut.shape[1]
    k_iota = jax.lax.broadcasted_iota(jnp.int32, (ksub, Cp), 0)
    acc = jnp.zeros((1, Cp), jnp.float32)
    for jj in range(m):                           # static unroll, m small
        onehot = (code[jj:jj + 1, :] == k_iota).astype(jnp.float32)
        acc = acc + jax.lax.dot_general(
            lut[jj:jj + 1, :], onehot, (((1,), (0,)), ((), ())),
            precision=HIGHEST, preferred_element_type=jnp.float32)
    # lanes beyond the LOGICAL capacity ``c`` are wrapper padding: mask
    # them to +inf (never selectable: the wrapper guarantees k <= P*c
    # real candidates, all <= BIG < inf) so they cannot perturb the
    # BIG-tie order of masked-but-real candidates, and index candidates
    # with the logical stride so flat ids match the ref twin exactly.
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, Cp), 1)
    in_lane = lane < c
    ok = (valid_ref[...] != 0) & (ok_ref[i, j] != 0) & in_lane  # (1, Cp)
    score = jnp.where(ok, acc, jnp.where(in_lane, BIG, jnp.inf))
    cand = lane + probe_ref[i, j] * c
    s, ids = merge_topk(s_ref[...], i_ref[...], score, cand, k)
    s_ref[...] = s
    i_ref[...] = ids


@functools.partial(jax.jit, static_argnames=("k", "c", "interpret"))
def pq_scan_topk(luts: jax.Array, codes: jax.Array, slot: jax.Array,
                 valid: jax.Array, qp_ok: jax.Array, probe: jax.Array,
                 *, k: int, c: int, interpret: bool = False):
    """Fused ADC scan + running top-k.

    luts: (Q, V, m, ksub) f32; codes: (M, m, Cp) uint8; slot: (M,) int32;
    valid: (M, Cp) int32 (slot_valid & posting visibility, precombined,
    padding lanes 0); qp_ok: (Q, P) int32 per-(query, probe) mask;
    probe: (Q, P) int32.  ``c`` is the LOGICAL posting capacity; lanes
    in [c, Cp) are wrapper padding, masked in-kernel via an
    iota-vs-extent mask.  Returns (scores (Q, k) f32 ascending, cand
    (Q, k) int32 flat slot index ``probe*c + lane``); masked candidates
    carry BIG.  Bit-identical to ``ref.pq_scan_topk`` including tie
    order (probe-position-major).  Storage shapes arrive 128-aligned
    from the ops.py wrapper (assertions below never fire).  Per-posting
    and per-query rows carry a unit middle axis so that every block's
    last two dims equal the array's, as Mosaic requires.
    """
    Q, V, m, ksub = luts.shape
    M, _, C = codes.shape
    P = probe.shape[1]
    assert C % 8 == 0 and ksub % 128 == 0, (C, ksub)
    assert 0 < c <= C, (c, C)
    out_row = pl.BlockSpec((None, 1, k),
                           lambda i, j, probe, slot, ok: (i, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(Q, P),
        in_specs=[
            pl.BlockSpec((None, None, m, ksub),
                         lambda i, j, probe, slot, ok: (i,
                                                        slot[probe[i, j]],
                                                        0, 0)),
            pl.BlockSpec((None, m, C),
                         lambda i, j, probe, slot, ok: (probe[i, j], 0, 0)),
            pl.BlockSpec((None, 1, C),
                         lambda i, j, probe, slot, ok: (probe[i, j], 0, 0)),
        ],
        out_specs=[out_row, out_row],
    )
    s, ids = pl.pallas_call(
        functools.partial(_topk_kernel, k=k, c=c),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((Q, 1, k), jnp.float32),
            jax.ShapeDtypeStruct((Q, 1, k), jnp.int32),
        ],
        interpret=interpret,
    )(probe, slot, qp_ok, luts, codes, valid.reshape(M, 1, C))
    return s.reshape(Q, k), ids.reshape(Q, k)
