"""Pallas TPU kernel: fused ADC scan + top-k over packed PQ codes.

The quant-plane twin of ``posting_scan.posting_scan_topk``: for each
(query, probe) pair, stream one posting's uint8 code tile HBM->VMEM,
look up and sum its per-subspace table entries, and park the tile's
masked score row in a (P, GROUP, L) VMEM scratch, one sublane per query
of a group of GROUP consecutive queries; the group's last step selects
each query's top-k of its P rows, all GROUP queries side by side.  The
probe table AND the
per-posting codebook-slot table are scalar-prefetched, so the lookup
table block for grid step (i, j) is selected by the *probed posting's*
codebook version — versioned codebooks cost one extra scalar
indirection, not a second pass.  No (Q, P, C) score tensor ever hits
HBM: the kernel writes 2*k words per query.

Lookup is an in-register lane gather (``take_along_axis`` along lanes,
the TPU's dynamic lane permute): the code tile is widened to whole
128-lane tiles in registers (never in HBM), each 128-lane chunk of the
table row is gathered by ``code & 127``, and ``code >> 7`` picks the
chunk (one chunk for ksub <= 128, two for 256).  Every entry is read
exactly in f32 and the per-subspace sum runs j = 0..m-1 in f32, so a
score is the same sequential sum the ref twin's integer-table tests
and a numpy f32 loop compute.  Selection is k rounds of (min,
first-position-of-min, pick, retire) over a query's P*L candidates,
once per query instead of once per probe, and each round's cross-lane
reductions serve a whole group: ties go to the lowest flat position,
probe-position major, exactly ``lax.top_k``'s order over the ref twin's
(P*C) row.

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

from .posting_scan import BIG

LANES = 128
GROUP = 8        # queries whose top-k select together, one per sublane


def _widen(x, width: int):
    """Zero-extend the lanes of a (rows, n) tile to ``width``, in
    registers."""
    rows, n = x.shape
    if n == width:
        return x
    return jnp.concatenate([x, jnp.zeros((rows, width - n), x.dtype)],
                           axis=1)


def _adc_row(lut, code):
    """Sum over subspaces of ``lut[j, code[j, l]]``: (m, ksubp) f32 and
    (m, L) int32 codes (L a multiple of 128) -> (1, L) f32, summed
    j = 0..m-1 in order."""
    m, width = code.shape
    lo = code & (LANES - 1)
    hi = code >> 7
    chunks = [lut[:, t * LANES:(t + 1) * LANES]
              for t in range(lut.shape[1] // LANES)]
    parts = []
    for b in range(width // LANES):
        lo_b = lo[:, b * LANES:(b + 1) * LANES]
        hi_b = hi[:, b * LANES:(b + 1) * LANES]
        d = jnp.take_along_axis(chunks[0], lo_b, axis=1)
        for t in range(1, len(chunks)):
            d = jnp.where(hi_b == t,
                          jnp.take_along_axis(chunks[t], lo_b, axis=1), d)
        parts.append(d)
    d = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
    acc = jnp.zeros((1, width), jnp.float32)
    for jj in range(m):                           # static unroll, m small
        acc = acc + d[jj:jj + 1, :]
    return acc


def _select(s, ids, k: int):
    """Top-k of each query of a (P, G, L) score scratch (query g's
    candidates in sublane g) with their ids: ascending (G, k) rows,
    ties to the lowest flat position ``p * L + lane``.  The G queries
    select side by side, one per sublane, so each round's reductions
    serve all of them at once."""
    n, rows, width = s.shape
    shape = (n, rows, width)
    pos = (jax.lax.broadcasted_iota(jnp.int32, shape, 0) * width
           + jax.lax.broadcasted_iota(jnp.int32, shape, 2))
    out_lane = jax.lax.broadcasted_iota(jnp.int32, (rows, k), 1)

    def per_query(x, op):                         # -> (1, G, 1)
        return op(op(x, axis=0, keepdims=True), axis=2, keepdims=True)

    def round_(t, carry):
        s, out_s, out_i = carry
        best = per_query(s, jnp.min)
        arg = per_query(jnp.where(s == best, pos, n * width), jnp.min)
        hit = pos == arg
        pick = per_query(jnp.where(hit, ids, 0), jnp.sum)
        out_s = jnp.where(out_lane == t, best[0], out_s)
        out_i = jnp.where(out_lane == t, pick[0], out_i)
        return jnp.where(hit, jnp.inf, s), out_s, out_i   # retire the pick

    _, out_s, out_i = jax.lax.fori_loop(
        0, k, round_, (s, jnp.zeros((rows, k), jnp.float32),
                       jnp.zeros((rows, k), jnp.int32)))
    return out_s, out_i


def _topk_kernel(probe_ref, slot_ref, ok_ref, lut_ref, codes_ref,
                 valid_ref, s_ref, i_ref, s_scr, i_scr, *, k, c):
    i = pl.program_id(0)
    j = pl.program_id(1)
    width = s_scr.shape[2]
    code = _widen(codes_ref[...].astype(jnp.int32), width)   # (m, L)
    acc = _adc_row(lut_ref[...].astype(jnp.float32), code)   # (1, L)
    # lanes beyond the LOGICAL capacity ``c`` are padding (the wrapper's
    # 8-row pad, then the widening to 128 lanes): mask them to +inf
    # (never selectable: the wrapper guarantees k <= P*c real
    # candidates, all <= BIG < inf) so they cannot perturb the BIG-tie
    # order of masked-but-real candidates, and index candidates with the
    # logical stride so flat ids match the ref twin exactly.
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    in_lane = lane < c
    ok = ((_widen(valid_ref[...], width) != 0) & (ok_ref[i, j] != 0)
          & in_lane)
    g = i % GROUP
    s_scr[j, pl.ds(g, 1), :] = jnp.where(ok, acc,
                                         jnp.where(in_lane, BIG, jnp.inf))
    i_scr[j, pl.ds(g, 1), :] = lane + probe_ref[i, j] * c

    # a group's selection runs once its last query's last probe is in;
    # the rows of a short last group that no query wrote select garbage,
    # each in its own sublane, and are sliced off by the wrapper
    @pl.when((j == pl.num_programs(1) - 1)
             & ((g == GROUP - 1) | (i == pl.num_programs(0) - 1)))
    def _emit():
        s_ref[...], i_ref[...] = _select(s_scr[...], i_scr[...], k)


@functools.partial(jax.jit, static_argnames=("k", "c", "interpret"))
def pq_scan_topk(luts: jax.Array, codes: jax.Array, slot: jax.Array,
                 valid: jax.Array, qp_ok: jax.Array, probe: jax.Array,
                 *, k: int, c: int, interpret: bool = False):
    """Fused ADC scan + per-query top-k.

    luts: (Q, V, m, ksub) f32; codes: (M, m, Cp) uint8; slot: (M,) int32;
    valid: (M, Cp) int32 (slot_valid & posting visibility, precombined,
    padding lanes 0); qp_ok: (Q, P) int32 per-(query, probe) mask;
    probe: (Q, P) int32.  ``c`` is the LOGICAL posting capacity; lanes
    in [c, Cp) are wrapper padding, masked in-kernel via an
    iota-vs-extent mask.  Returns (scores (Q, k) f32 ascending, cand
    (Q, k) int32 flat slot index ``probe*c + lane``); masked candidates
    carry BIG.  Bit-identical to ``ref.pq_scan_topk`` including tie
    order (probe-position-major).  Storage shapes arrive aligned from
    the ops.py wrapper (ksub to 128 lanes, Cp to 8; the assertions below
    never fire).  The per-posting validity rows carry a unit middle axis
    so that every block's last two dims equal the array's, as Mosaic
    requires; the outputs come in blocks of GROUP query rows.
    """
    Q, V, m, ksub = luts.shape
    M, _, C = codes.shape
    P = probe.shape[1]
    assert C % 8 == 0 and ksub % 128 == 0, (C, ksub)
    assert 0 < c <= C, (c, C)
    width = -(-C // LANES) * LANES        # the code tile, widened in VMEM
    out_rows = pl.BlockSpec((GROUP, k),
                            lambda i, j, probe, slot, ok: (i // GROUP, 0))
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
        out_specs=[out_rows, out_rows],
        scratch_shapes=[pltpu.VMEM((P, GROUP, width), jnp.float32),
                        pltpu.VMEM((P, GROUP, width), jnp.int32)],
    )
    Qp = -(-Q // GROUP) * GROUP
    s, ids = pl.pallas_call(
        functools.partial(_topk_kernel, k=k, c=c),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((Qp, k), jnp.float32),
            jax.ShapeDtypeStruct((Qp, k), jnp.int32),
        ],
        interpret=interpret,
    )(probe, slot, qp_ok, luts, codes, valid.reshape(M, 1, C))
    return s[:Q], ids[:Q]
