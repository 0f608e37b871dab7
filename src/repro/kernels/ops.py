"""Public kernel entry points: padding, backend dispatch, jit wrappers.

Every op has three backends, spelled as ``UBISConfig.use_pallas``:
  * ``off``     — the pure-jnp twin (``ref.py``), always correct, XLA-fused;
  * ``on``      — the Pallas kernel (compiled on TPU, interpret=True on CPU);
  * ``auto``    — the kernel on TPU backends, the twin elsewhere.

Callers pass logical shapes; wrappers pad to hardware-aligned tiles
(lane dim 128, sublane 8) and slice results back.
"""
from __future__ import annotations

import contextlib
import warnings
import weakref
from typing import Optional

import jax
import jax.numpy as jnp

from . import ref
from .posting_scan import BIG, posting_scan as _ps_pallas
from .centroid_score import centroid_score as _cs_pallas
from .centroid_topk import centroid_topk as _ct_pallas
from .kmeans_assign import kmeans_assign as _ka_pallas
from .flash_attention import flash_attention as _fa_pallas

_PAD_CENTROID = 1e6  # padded rows score ~1e14 >> any real score


BACKENDS = ("auto", "on", "off")


def _use_pallas(backend: str) -> bool:
    if backend not in BACKENDS:
        raise ValueError(f"kernel backend {backend!r} is not one of "
                         f"{BACKENDS}")
    if backend == "auto":
        return jax.default_backend() == "tpu"
    return backend == "on"


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# Kernel-fallback observability.  All fused kernels are alignment-free
# (wrappers pad storage shapes to the TPU layout and mask in-kernel), so
# today NO pallas request ever falls back — but the plane stays wired so
# any future gate that re-opens the silent-slow-path hole is loud.
#
# Two signals with different clocks:
#   * ``kernel_fallback_traces`` — bumped by ``_note_fallback`` at TRACE
#     time (shape checks are static, so the note runs once per
#     compilation of the enclosing jitted program), plus a one-shot
#     ``kernel_fallback`` trace event per (kernel, reason);
#   * ``kernel_fallback``        — per-DISPATCH count.  Python inside a
#     jitted function does not re-run on cache-warm calls, so drivers
#     wrap each dispatch in ``count_fallback_dispatches``: the first
#     wrap of a signature captures the keys noted while the program
#     traces, and every wrap bumps the counter by the memoized count —
#     under steady-state serving the counter now moves every call
#     instead of freezing after the first compilation.
# ---------------------------------------------------------------------------

_FALLBACK_SINKS: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
_FALLBACK_WARNED: set = set()
_CAPTURE_STACK: list = []       # active trace-capture sets (LIFO)
_DISPATCH_MEMO: dict = {}       # signature -> frozenset[(kernel, reason)]


def observe_fallbacks(obs) -> None:
    """Register an ``Obs`` bundle to receive kernel-fallback signals
    (drivers call this at construction).  Weakly held."""
    if obs not in _FALLBACK_SINKS:
        _FALLBACK_SINKS[obs] = set()


def discard_fallback_sink(obs) -> None:
    """Unregister one ``Obs`` bundle (driver teardown)."""
    _FALLBACK_SINKS.pop(obs, None)


def reset_fallback_state() -> None:
    """Clear ALL process-global fallback bookkeeping: sinks, one-shot
    warn/event dedup sets, capture scopes and the dispatch memo.
    Back-to-back driver constructions in one process (a test suite, a
    notebook) call this between indexes so one index's one-shot state
    never suppresses the next one's signals."""
    _FALLBACK_SINKS.clear()
    _FALLBACK_WARNED.clear()
    _CAPTURE_STACK.clear()
    _DISPATCH_MEMO.clear()


def _note_fallback(kernel: str, reason: str) -> None:
    """Record one kernel-fallback decision.  Runs at TRACE time."""
    key = (kernel, reason)
    for cap in _CAPTURE_STACK:
        cap.add(key)
    for obs, emitted in _FALLBACK_SINKS.items():
        obs.counter("kernel_fallback_traces").inc()
        if key not in emitted:
            emitted.add(key)
            obs.emit("kernel_fallback", kernel=kernel, reason=reason)
    if not _FALLBACK_SINKS and key not in _FALLBACK_WARNED:
        _FALLBACK_WARNED.add(key)
        warnings.warn(f"kernel {kernel} fell back to the jnp reference "
                      f"({reason})", stacklevel=3)


@contextlib.contextmanager
def count_fallback_dispatches(obs, signature):
    """Wrap ONE dispatch of a jitted program and count its fallbacks.

    ``signature`` must cover everything that decides backend routing for
    the wrapped program (backend knob + the plane identity) — shapes
    that merely retrigger jit tracing (e.g. the query-batch size) may be
    omitted, since re-traces of the same signature make the same
    routing decisions.  The first wrap of a signature captures the
    (kernel, reason) keys ``_note_fallback`` records while the program
    traces; every wrap bumps ``obs.counter("kernel_fallback")`` by the
    memoized key count.  Caveat: if the program was first compiled
    OUTSIDE any wrap, the first wrap sees a warm cache and memoizes an
    empty set — drivers avoid this by wrapping every dispatch.
    """
    first = signature not in _DISPATCH_MEMO
    if first:
        cap: set = set()
        _CAPTURE_STACK.append(cap)
    try:
        yield
    finally:
        if first:
            _CAPTURE_STACK.remove(cap)
            _DISPATCH_MEMO[signature] = frozenset(cap)
    n = len(_DISPATCH_MEMO[signature])
    if n and obs is not None:
        obs.counter("kernel_fallback").inc(n)


def _ceil(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _pad2(x, rows: int, cols: int, value=0.0):
    """Pad columns (feature dim) with zeros, then extra rows with ``value``
    — so sentinel row-padding never corrupts real rows' norms."""
    r, c = x.shape
    x = jnp.pad(x, ((0, 0), (0, cols - c)))
    return jnp.pad(x, ((0, rows - r), (0, 0)), constant_values=value)


# ---------------------------------------------------------------------------


def centroid_score(q: jax.Array, c: jax.Array,
                   vis: Optional[jax.Array] = None,
                   *, backend: str = "auto") -> jax.Array:
    """(Q, d), (M, d)[, (M,) bool] -> (Q, M) scores; masked -> BIG."""
    Q, d = q.shape
    M = c.shape[0]
    if vis is None:
        vis = jnp.ones((M,), bool)
    if not _use_pallas(backend):
        s = ref.centroid_score(q, c)
        return jnp.where(vis[None, :], s, BIG)
    bq = 128 if Q >= 128 else _ceil(Q, 8)
    bm = 512 if M >= 512 else _ceil(M, 128)
    Qp, Mp, dp = _ceil(Q, bq), _ceil(M, bm), _ceil(d, 128)
    qp = _pad2(q, Qp, dp)
    cp = _pad2(c, Mp, dp, value=_PAD_CENTROID)
    vp = jnp.pad(vis, (0, Mp - M)).astype(jnp.int32)[None, :]
    out = _cs_pallas(qp, cp, vp, bq=bq, bm=bm, interpret=_interpret())
    return out[:Q, :M]


def centroid_topk(q: jax.Array, c: jax.Array,
                  vis: Optional[jax.Array] = None, *, k: int,
                  backend: str = "auto"):
    """Fused phase 1: (Q, d), (M, d)[, (M,) bool] -> (scores (Q, k)
    ascending, idx (Q, k) int32); masked centroids -> BIG.

    Replaces ``centroid_score`` + ``lax.top_k``: on the pallas path no
    (Q, M) score matrix is materialized.  Both backends break ties
    lowest-index-first, so the pair is bit-identical."""
    Q, d = q.shape
    M = c.shape[0]
    assert k <= M, (k, M)
    if vis is None:
        vis = jnp.ones((M,), bool)
    if not _use_pallas(backend):
        return ref.centroid_topk(q, c, vis, k)
    bq = 128 if Q >= 128 else _ceil(Q, 8)
    bm = 512 if M >= 512 else _ceil(M, 128)
    Qp, Mp, dp = _ceil(Q, bq), _ceil(M, bm), _ceil(d, 128)
    qp = _pad2(q, Qp, dp)
    cp = _pad2(c, Mp, dp, value=_PAD_CENTROID)
    vp = jnp.pad(vis, (0, Mp - M)).astype(jnp.int32)[None, :]
    # padded rows masked -> BIG; k <= M real candidates always
    # outrank the padded tail on ties
    s, i = _ct_pallas(qp, cp, vp, k=k, bq=bq, bm=bm,
                      interpret=_interpret())
    return s[:Q], i[:Q]


def posting_scan(q: jax.Array, tiles: jax.Array, valid: jax.Array,
                 *, backend: str = "auto") -> jax.Array:
    """(Q, d), (G, C, d), (G, C) -> (Q, G*C) scores; invalid -> BIG."""
    Q, d = q.shape
    G, C, _ = tiles.shape
    if not _use_pallas(backend):
        s = ref.posting_scan(q, tiles, valid)
        return jnp.where(jnp.isfinite(s), s, BIG)
    V = G * C
    bq = 128 if Q >= 128 else _ceil(Q, 8)
    bv = 512 if V >= 512 else _ceil(V, 128)
    Qp, Vp, dp = _ceil(Q, bq), _ceil(V, bv), _ceil(d, 128)
    qp = _pad2(q, Qp, dp)
    vp = _pad2(tiles.reshape(V, d), Vp, dp)
    mp = jnp.pad(valid.reshape(V), (0, Vp - V)).astype(jnp.int32)[None, :]
    out = _ps_pallas(qp, vp, mp, bq=bq, bv=bv, interpret=_interpret())
    return out[:Q, :V]


def kmeans_assign(points: jax.Array, centroids: jax.Array,
                  mask: Optional[jax.Array] = None,
                  *, backend: str = "auto"):
    """(N, d), (K, d)[, (N,) bool] -> (assign (N,) int32, best (N,) f32)."""
    N, d = points.shape
    K = centroids.shape[0]
    if not _use_pallas(backend):
        a, b = ref.kmeans_assign(points, centroids, mask)
        return a, jnp.where(jnp.isfinite(b), b, BIG)
    bn = 256 if N >= 256 else _ceil(N, 128)  # lane-dense output rows
    bk = 128  # lane-width tile; K pads up to a multiple (sentinel rows)
    Np, Kp, dp = _ceil(N, bn), _ceil(K, bk), _ceil(d, 128)
    pp = _pad2(points, Np, dp)
    cp = _pad2(centroids, Kp, dp, value=_PAD_CENTROID)
    a, b = _ka_pallas(pp, cp, bn=bn, bk=bk, interpret=_interpret())
    a, b = a[:N], b[:N]
    if mask is not None:
        a = jnp.where(mask, a, -1)
        b = jnp.where(mask, b, BIG)
    return a, b


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    scale: Optional[float] = None,
                    backend: str = "auto"):
    """(B,Hq,Lq,D), (B,Hkv,Lk,D) x2 -> (B,Hq,Lq,D)."""
    B, Hq, Lq, D = q.shape
    Lk = k.shape[2]
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    if not _use_pallas(backend):
        return ref.flash_attention(q, k, v, causal=causal, window=window,
                                   scale=scale)
    bq = 128 if Lq >= 128 else _ceil(Lq, 8)
    bk = 128 if Lk >= 128 else _ceil(Lk, 8)
    Lqp, Lkp, Dp = _ceil(Lq, bq), _ceil(Lk, bk), _ceil(D, 128)
    # q is padded at the FRONT so that the last real row keeps its
    # end-aligned position (kv_len - Lq + i); k/v pad at the back and are
    # masked by kv_len inside the kernel.
    qp = jnp.pad(q, ((0, 0), (0, 0), (Lqp - Lq, 0), (0, Dp - D)))
    pad_kv = lambda x: jnp.pad(
        x, ((0, 0), (0, 0), (0, Lkp - x.shape[2]), (0, Dp - x.shape[3])))
    out = _fa_pallas(qp, pad_kv(k), pad_kv(v),
                     causal=causal, window=window, scale=scale, kv_len=Lk,
                     bq=bq, bk=bk, interpret=_interpret())
    return out[:, :, Lqp - Lq:, :D]


def pq_scan_topk(luts: jax.Array, codes: jax.Array,
                 posting_slot: jax.Array, slot_valid: jax.Array,
                 vis: jax.Array, probe: jax.Array, *, k: int,
                 qp_ok: Optional[jax.Array] = None,
                 backend: str = "auto"):
    """Fused ADC scan + top-k (quant-plane phase 2).

    luts: (Q, V, m, ksub); codes: (M, m, C) uint8; posting_slot: (M,)
    int32; slot_valid: (M, C); vis: (M,); probe: (Q, P); ``k`` and an
    optional per-(query, probe) mask ``qp_ok`` (the sharded plane's
    ownership mask).  Returns (scores (Q, k) ascending, cand (Q, k)
    int32 flat slot index ``probe*C + c``) with BIG at masked
    candidates.  On the pallas path the (Q, P, C) score tensor is never
    materialized — selection runs on-chip against the streamed code
    tiles.  Alignment-free: ksub zero-pads to the 128-lane tile and C to
    the 8-row tile here — padded lut columns are unreachable (codes <
    the logical ksub) and padded code lanes are masked to +inf
    in-kernel, so the BIG-tie order stays bit-identical to the ref
    twin."""
    from .pq_scan import pq_scan_topk as _pqt_pallas
    Q, V, m, ksub = luts.shape
    C = codes.shape[2]
    P = probe.shape[1]
    assert k <= P * C, (k, P, C)
    slot = jnp.clip(posting_slot.astype(jnp.int32), 0, V - 1)
    valid = slot_valid & vis[:, None]
    if qp_ok is None:
        qp_ok = jnp.ones((Q, P), jnp.int32)
    qp_ok = qp_ok.astype(jnp.int32)
    if not _use_pallas(backend):
        return ref.pq_scan_topk(luts, codes, slot, valid, qp_ok, probe, k)
    Cp, ksubp = _ceil(C, 8), _ceil(ksub, 128)
    lp = jnp.pad(luts, ((0, 0), (0, 0), (0, 0), (0, ksubp - ksub)))
    cdp = jnp.pad(codes, ((0, 0), (0, 0), (0, Cp - C)))
    vp = jnp.pad(valid, ((0, 0), (0, Cp - C))).astype(jnp.int32)  # pad 0
    return _pqt_pallas(lp, cdp, slot, vp, qp_ok,
                       probe.astype(jnp.int32), k=k, c=C,
                       interpret=_interpret())


def posting_scan_topk(q: jax.Array, vectors: jax.Array,
                      slot_valid: jax.Array, vis: jax.Array,
                      probe: jax.Array, *, k: int,
                      qp_ok: Optional[jax.Array] = None,
                      backend: str = "auto"):
    """Fused float phase 2: probe scan + top-k in one kernel.

    q: (Q, d); vectors: (M, C, d); slot_valid: (M, C); vis: (M,);
    probe: (Q, P); ``k`` and an optional per-(query, probe) mask.
    Returns (scores (Q, k) ascending,
    cand (Q, k) int32 flat slot index) — no (Q, P, C) score tensor on
    the pallas path.  Alignment-free: d zero-pads to the 128-lane tile
    (fp-exact) and C to the 8-row tile here; padded lanes are masked to
    +inf in-kernel so the BIG-tie order stays bit-identical to the ref
    twin."""
    from .posting_scan import posting_scan_topk as _pst_pallas
    Q, d = q.shape
    M, C, _ = vectors.shape
    P = probe.shape[1]
    assert k <= P * C, (k, P, C)
    valid = slot_valid & vis[:, None]
    if qp_ok is None:
        qp_ok = jnp.ones((Q, P), jnp.int32)
    qp_ok = qp_ok.astype(jnp.int32)
    if not _use_pallas(backend):
        return ref.posting_scan_topk(q, vectors, valid, qp_ok, probe, k)
    Cp, dp = _ceil(C, 8), _ceil(d, 128)
    qp = jnp.pad(q, ((0, 0), (0, dp - d)))
    vecp = jnp.pad(vectors, ((0, 0), (0, Cp - C), (0, dp - d)))
    vp = jnp.pad(valid, ((0, 0), (0, Cp - C))).astype(jnp.int32)  # pad 0
    return _pst_pallas(qp, vecp, vp, qp_ok,
                       probe.astype(jnp.int32), k=k, c=C,
                       interpret=_interpret())


def rerank_topk(q: jax.Array, vectors: jax.Array, tier_spilled: jax.Array,
                cand: jax.Array, adc: jax.Array, *, k: int,
                backend: str = "auto"):
    """Fused exact rerank of the quant plane's ADC survivors.

    q: (Q, d); vectors: (M, C, d); tier_spilled: (M,) bool; cand:
    (Q, R) int32 flat slot candidates from :func:`pq_scan_topk`; adc:
    (Q, R) their ADC scores.  Exact-rescores each candidate
    (``||v||^2 - 2 q.v``), keeps the ADC score for tier-spilled
    postings (codes-only serving), carries BIG through empty ADC slots,
    and returns the top-k (scores (Q, k) ascending, cand (Q, k) int32).
    On the pallas path the candidate rows stream HBM->VMEM one at a
    time — no (Q, R, d) gather is ever materialized.  Alignment-free
    (d zero-pads, fp-exact); ties break lowest-ADC-rank-first on both
    backends, so the pair is bit-identical."""
    from .rerank import rerank_topk as _rr_pallas
    Q, d = q.shape
    M, C, _ = vectors.shape
    R = cand.shape[1]
    assert 0 < k <= R, (k, R)
    cand = cand.astype(jnp.int32)
    if not _use_pallas(backend):
        return ref.rerank_topk(q, vectors, tier_spilled, cand, adc, k)
    dp = _ceil(d, 128)
    qp = jnp.pad(q.astype(jnp.float32), ((0, 0), (0, dp - d)))
    vflat = jnp.pad(vectors.reshape(M * C, d).astype(jnp.float32),
                    ((0, 0), (0, dp - d)))
    spilled = tier_spilled[cand // C].astype(jnp.int32)
    return _rr_pallas(qp, vflat, cand, adc.astype(jnp.float32), spilled,
                      k=k, interpret=_interpret())
