"""Balance Detector + structural background operations (paper IV-C).

Key contribution of the paper: SPFresh's strict split/merge triggers
leave small postings stranded (Fig. 5); UBIS (a) *relaxes restrictions*
by keeping posting lengths in memory and scanning them periodically,
and (b) *identifies the root* — splits that produce an extremely small
side — via the balance factor ``f`` (Alg. 1 BalanceSplit).

Two layers of ops live here:
  * single-posting jitted transforms (``balance_split`` / ``merge_postings``
    / ``compact_posting`` / ``reassign_check``) — the reference semantics,
    kept as the sequential oracle the equivalence tests check against;
  * ``background_round`` — the production path: the WHOLE marked batch
    (kinds encoded as an int lane) executes as one SPMD program per tick.
The driver sequences rounds two-phase:
  round t   : mark SPLITTING/MERGING  (foreground traffic diverts to cache)
  round t+1 : execute; old posting -> DELETED with successor pointers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..kernels import ops, ref
from ..kernels.posting_scan import BIG
from . import version_manager as vm
from .types import (KIND_COMPACT, KIND_MERGE, KIND_NONE, KIND_SPLIT, NO_ID,
                    NO_SUCC, STATUS_DELETED, STATUS_MERGING, STATUS_NORMAL,
                    STATUS_SPLITTING, BackgroundRound, IndexState, UBISConfig)
from .update import (alloc_postings, batched_append, cache_append,
                     dataclasses_replace, free_postings, oob, _flat_set)


# ---------------------------------------------------------------------------
# detection (the in-memory length table scan)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("cfg",))
def detect(state: IndexState, cfg: UBISConfig):
    """Vectorized scan of the posting-length table.

    Returns (split_due, merge_due, compact_due) boolean masks over M.

    Spilled postings are never due: structural ops rewrite float tiles,
    which a spilled posting does not have on device — the tier planner
    force-promotes a structurally-due spilled posting first, and the
    detector picks it up the tick after (tests/test_tier.py).
    """
    status = vm.unpack_status(state.rec_meta)
    normal = (state.allocated & (status == STATUS_NORMAL)
              & ~state.tier_spilled)
    split_due = normal & (state.lengths > cfg.l_max)
    merge_due = normal & (state.lengths < cfg.l_min)
    compact_due = (normal & (state.used >= cfg.capacity)
                   & (state.lengths <= cfg.l_max))
    return split_due, merge_due, compact_due


# ---------------------------------------------------------------------------
# pool pressure (the saturation signal behind cross-shard rebalance)
# ---------------------------------------------------------------------------

def shard_pressure(state: IndexState, cfg: UBISConfig, base_pid=0):
    """Pressure stats for ONE posting pool: ``(live_postings, free_slots,
    cache_backlog, live_vectors)`` as a (4,) int32 vector.

    ``base_pid`` is the pool's global pid offset: cache targets are
    stored as global pids, so the backlog column counts parked jobs
    bound for THIS pool's postings.  Shared by the sharded background
    round (per shard, local state under ``shard_map``) and the
    single-device ``UBISDriver.shard_pressure`` (base 0, whole pool) so
    both planes report the same saturation signal in the same format.
    Pure local computation — contributes zero collectives to the round
    it rides in.
    """
    M_local = state.allocated.shape[0]
    status = vm.unpack_status(state.rec_meta)
    alive = state.allocated & (status != STATUS_DELETED)
    live = jnp.sum(alive)
    free = jnp.sum(~state.allocated)
    t = state.cache_target
    lo = jnp.asarray(base_pid, jnp.int32)
    backlog = jnp.sum(state.cache_valid & (t >= lo) & (t < lo + M_local))
    live_vecs = jnp.sum(jnp.where(alive, state.lengths, 0))
    return jnp.stack([live, free, backlog, live_vecs]).astype(jnp.int32)


# ---------------------------------------------------------------------------
# masked 2-means (the split clustering step)
# ---------------------------------------------------------------------------

def _median_bisect(tile, mask):
    """Deterministic balanced bisection: split the valid rows at the
    median of the maximum-variance axis (ties broken by rank, so the two
    sides differ by at most one point).  Used (a) to initialise 2-means
    and (b) as the termination guard when Lloyd collapses to an
    outlier-vs-rest split — a failure mode the paper's Alg. 1 does not
    handle (it would re-split the oversized survivor forever).
    """
    C = tile.shape[0]
    x = tile.astype(jnp.float32)
    n = jnp.maximum(jnp.sum(mask), 1)
    mean = jnp.sum(jnp.where(mask[:, None], x, 0), 0) / n
    var = jnp.sum(jnp.where(mask[:, None], (x - mean) ** 2, 0), 0)
    axis = ref.exact_argmax(var)
    vals = jnp.where(mask, x[:, axis], BIG)
    order = jnp.argsort(vals)            # valid rows first, ascending
    rank = jnp.zeros((C,), jnp.int32).at[order].set(
        jnp.arange(C, dtype=jnp.int32))
    assign = jnp.where(mask, (rank >= (n + 1) // 2).astype(jnp.int32), -1)
    return assign


def _two_means(tile, mask, iters: int, init: str = "median"):
    """2-means over the valid rows of one posting tile.

    init="median": deterministic median-split init (balanced starting
    point that avoids outlier-capture optima) — the UBIS path.
    init="farthest": classic farthest-point init — the SPFresh-faithful
    path, which DOES collapse to outlier-vs-rest splits on real data;
    that is precisely the small-posting generator behind the paper's
    Fig. 5, so the baseline must keep it.
    Returns (assign (C,) int32 in {0,1}, c0, c1)."""
    x = tile.astype(jnp.float32)
    if init == "median":
        ini = _median_bisect(tile, mask)
        c0 = _masked_mean(tile, (ini == 0) & mask, x[jnp.argmax(mask)])
        c1 = _masked_mean(tile, (ini == 1) & mask, x[jnp.argmax(mask)])
    else:
        first = jnp.argmax(mask)
        c0 = x[first]
        d0 = jnp.where(mask, jnp.sum((x - c0) ** 2, -1), -BIG)
        c1 = x[ref.exact_argmax(d0)]

    def body(_, carry):
        c0, c1 = carry
        d0 = jnp.sum((x - c0) ** 2, -1)
        d1 = jnp.sum((x - c1) ** 2, -1)
        a = (d1 < d0).astype(jnp.int32)        # 1 -> cluster 1
        w1 = (a == 1) & mask
        w0 = (a == 0) & mask
        n0 = jnp.maximum(jnp.sum(w0), 1)
        n1 = jnp.maximum(jnp.sum(w1), 1)
        m0 = jnp.sum(jnp.where(w0[:, None], x, 0), 0) / n0
        m1 = jnp.sum(jnp.where(w1[:, None], x, 0), 0) / n1
        c0 = jnp.where(jnp.any(w0), m0, c0)
        c1 = jnp.where(jnp.any(w1), m1, c1)
        return c0, c1

    c0, c1 = jax.lax.fori_loop(0, iters, body, (c0, c1))
    d0 = jnp.sum((x - c0) ** 2, -1)
    d1 = jnp.sum((x - c1) ** 2, -1)
    assign = jnp.where(mask, (d1 < d0).astype(jnp.int32), -1)
    return assign, c0, c1


def _masked_mean(tile, mask, fallback):
    n = jnp.maximum(jnp.sum(mask), 1)
    m = jnp.sum(jnp.where(mask[:, None], tile.astype(jnp.float32), 0), 0) / n
    return jnp.where(jnp.any(mask), m, fallback)


def _encode_written(state, cfg, rows):
    """Codes for freshly packed tile rows, under the ACTIVE codebook —
    every tile rewrite (split child, merge product, compact) is the lazy
    re-encode point of the versioned-codebook scheme."""
    from ..quant import pq
    cb = state.pq_codebooks[state.pq_active]
    stored = rows.astype(state.vectors.dtype).astype(jnp.float32)
    return pq.encode_tiles(cb, stored)


def _write_members(state, cfg, pid, tile, tids, member_mask):
    """Compact ``member_mask`` rows of a source tile into posting ``pid``
    (freshly allocated, empty).  Returns state with id_loc repointed.
    Row packing is shared with the batched round via ``_pack_rows`` so
    the sequential oracle and production path cannot drift."""
    C = cfg.capacity
    rows, rids, keep, n = _pack_rows(tile, tids, member_mask)
    vectors = state.vectors.at[pid].set(rows.astype(state.vectors.dtype))
    ids = state.ids.at[pid].set(rids)
    slot_valid = state.slot_valid.at[pid].set(keep)
    used = state.used.at[pid].set(n)
    lengths = state.lengths.at[pid].set(n)
    flat = pid * C + jnp.arange(C, dtype=jnp.int32)
    id_loc = state.id_loc.at[oob(rids, keep, cfg.max_ids)].set(flat,
                                                               mode="drop")
    state = dataclasses_replace(state, vectors=vectors, ids=ids,
                                slot_valid=slot_valid, used=used,
                                lengths=lengths, id_loc=id_loc)
    if cfg.use_pq:
        codes = state.codes.at[pid].set(
            _encode_written(state, cfg, rows[None])[0])
        state = dataclasses_replace(
            state, codes=codes,
            pq_posting_slot=state.pq_posting_slot.at[pid].set(
                state.pq_active))
    return state


# ---------------------------------------------------------------------------
# BalanceSplit — paper Algorithm 1
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("cfg",))
def balance_split(state: IndexState, cfg: UBISConfig, pid):
    """Split posting ``pid`` (status SPLITTING, marked a round earlier).

    Follows Alg. 1: filter deleted vectors; if the filtered posting no
    longer exceeds l_max, just compact it in place (lines 1-4).  Else run
    2-means; in UBIS mode, if the small side is under ``f * total``,
    reassign its points to nearer existing postings and fold the rest
    into the big side (lines 7-15) so no small posting is ever persisted.
    SPFresh mode keeps both sides unconditionally (the Fig. 5 failure).

    Two posting slots are consumed in the worst case; the driver checks
    ``free_top >= 2`` before scheduling.
    """
    C = cfg.capacity
    tile = state.vectors[pid]
    tids = state.ids[pid]
    mask = state.slot_valid[pid]
    n = state.lengths[pid]
    ver = state.global_version + jnp.uint32(1)

    assign, c0, c1 = _two_means(
        tile, mask, cfg.kmeans_iters,
        init="median" if cfg.is_ubis else "farthest")
    n0 = jnp.sum((assign == 0) & mask)
    n1 = jnp.sum((assign == 1) & mask)
    small_is_0 = n0 <= n1
    nmin = jnp.minimum(n0, n1)
    ntot = jnp.maximum(n0 + n1, 1)

    imbalanced = cfg.is_ubis & (
        nmin.astype(jnp.float32) < cfg.balance_factor *
        ntot.astype(jnp.float32))

    small_side = jnp.where(small_is_0, 0, 1)
    big_side = 1 - small_side
    small_mask = (assign == small_side) & mask
    big_mask = (assign == big_side) & mask
    c_big = jnp.where(small_is_0, c1, c0)
    c_small = jnp.where(small_is_0, c0, c1)

    # --- Alg.1 lines 10-13: nearer-posting search for the small side ----
    status = vm.unpack_status(state.rec_meta)
    other = (state.allocated & (status == STATUS_NORMAL)
             & ~state.tier_spilled)
    other = other.at[pid].set(False)
    sc = ops.centroid_score(tile.astype(jnp.float32), state.centroids, other,
                            backend=cfg.use_pallas)           # (C, M)
    best_other = ref.exact_argmin(sc, -1)
    best_d = jnp.min(sc, -1)
    d_big = (jnp.sum(tile.astype(jnp.float32) ** 2, -1)
             - 2 * jnp.matmul(tile.astype(jnp.float32), c_big,
                              precision=ref.HIGHEST)
             + jnp.sum(c_big ** 2))
    # score convention: sc already excludes ||p||^2, so compare apples:
    d_big_score = d_big - jnp.sum(tile.astype(jnp.float32) ** 2, -1)
    move_out = imbalanced & small_mask & (best_d < d_big_score)
    fold_in = imbalanced & small_mask & ~(best_d < d_big_score)

    # membership of the surviving side(s)
    members_a = jnp.where(imbalanced, big_mask | fold_in, big_mask)
    members_b = jnp.where(imbalanced, jnp.zeros_like(small_mask), small_mask)

    # --- termination guard (beyond-paper robustness, DESIGN.md §1) ------
    # If either surviving side still exceeds l_max (Lloyd collapsed to an
    # outlier-vs-rest split and the fold-in restored the oversize), the
    # paper's Alg. 1 would re-split that survivor forever.  Fall back to
    # the deterministic median bisection: both halves <= capacity/2 <=
    # l_max, so every split strictly reduces posting size.
    oversized = cfg.is_ubis & (
        (jnp.sum(members_a) > cfg.l_max)
        | (jnp.sum(members_b) > cfg.l_max))
    med = _median_bisect(tile, mask)
    med_a = (med == 0) & mask
    med_b = (med == 1) & mask
    members_a = jnp.where(oversized, med_a, members_a)
    members_b = jnp.where(oversized, med_b, members_b)
    move_out = move_out & ~oversized
    c_big = jnp.where(oversized, _masked_mean(tile, med_a, c_big), c_big)
    c_small = jnp.where(oversized, _masked_mean(tile, med_b, c_small),
                        c_small)

    cent_a = _masked_mean(tile, members_a, c_big)
    cent_b = _masked_mean(tile, members_b, c_small)

    # allocate both slots unconditionally (fixed shape); slot b is
    # returned to the free list when the imbalanced branch leaves it empty.
    state, pids_new = alloc_postings(
        state, cfg, 2, jnp.stack([cent_a, cent_b]), ver)
    pa, pb = pids_new[0], pids_new[1]
    state = _write_members(state, cfg, pa, tile, tids, members_a)
    state = _write_members(state, cfg, pb, tile, tids, members_b)

    b_empty = ~jnp.any(members_b)
    state = free_postings(state,
                          jnp.stack([pb, jnp.asarray(-1, jnp.int32)]),
                          jnp.array([True, False]) & b_empty)

    # move-out appends (may divert to cache when targets are full)
    state, ok, _ = batched_append(state, cfg, tile, tids,
                                  jnp.where(move_out, best_other, -1),
                                  move_out)
    spill = move_out & ~ok
    state, _ = cache_append(state, cfg, tile, tids,
                            jnp.where(spill, best_other, -1), spill)

    # retire the parent: DELETED with successor pointers
    succ_b = jnp.where(b_empty, -1, pb)
    rec_meta = vm.transition(state.rec_meta, pid[None], STATUS_DELETED,
                             ver[None])
    rec_succ = vm.set_successors(state.rec_succ, pid[None], pa[None],
                                 succ_b[None])
    # neighbourhood graph: children point at each other + parent's nbrs
    pn = state.nbrs[pid]
    nbrs = state.nbrs.at[pa].set(
        jnp.concatenate([jnp.where(b_empty, pa, pb)[None], pn[:-1]]))
    nbrs = nbrs.at[pb].set(jnp.concatenate([pa[None], pn[:-1]]))
    state = dataclasses_replace(state, rec_meta=rec_meta, rec_succ=rec_succ,
                                nbrs=nbrs, global_version=ver)
    return state, pids_new


@functools.partial(jax.jit, static_argnames=("cfg",))
def compact_posting(state: IndexState, cfg: UBISConfig, pid):
    """Alg. 1 lines 1-4: drop tombstones, rewrite in place."""
    tile = state.vectors[pid]
    tids = state.ids[pid]
    mask = state.slot_valid[pid]
    state = _write_members(state, cfg, pid, tile, tids, mask)
    return dataclasses_replace(
        state, global_version=state.global_version + jnp.uint32(1))


# ---------------------------------------------------------------------------
# merge (paper III-B2) — small posting folds into its nearest neighbour
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("cfg",))
def merge_postings(state: IndexState, cfg: UBISConfig, pid):
    """Merge posting ``pid`` with the nearest posting whose combined size
    stays under l_max.  Produces ONE new posting; both parents retire
    with successor pointers to it.  Consumes one slot."""
    C = cfg.capacity
    status = vm.unpack_status(state.rec_meta)
    n_me = state.lengths[pid]
    eligible = (state.allocated & (status == STATUS_NORMAL)
                & ~state.tier_spilled
                & (state.lengths + n_me < cfg.l_max))
    eligible = eligible.at[pid].set(False)
    sc = ops.centroid_score(state.centroids[pid][None], state.centroids,
                            eligible, backend=cfg.use_pallas)[0]
    partner = ref.exact_argmin(sc)
    has_partner = sc[partner] < BIG / 2
    ver = state.global_version + jnp.uint32(1)

    t1, i1, m1 = state.vectors[pid], state.ids[pid], state.slot_valid[pid]
    t2 = state.vectors[partner]
    i2 = state.ids[partner]
    m2 = state.slot_valid[partner] & has_partner
    n1 = jnp.sum(m1)
    n2 = jnp.sum(m2)
    cent = (_masked_mean(t1, m1, state.centroids[pid].astype(jnp.float32))
            * n1 + _masked_mean(t2, m2, 0.0) * n2) / jnp.maximum(n1 + n2, 1)

    state, pids_new = alloc_postings(state, cfg, 1, cent[None], ver)
    pnew = pids_new[0]
    # write both parents' members (total < l_max <= C by eligibility);
    # packing shared with the batched round via _merge_rows (no drift)
    rows, rids, keepm, n = _merge_rows(t1, i1, m1, t2, i2, m2)
    vectors = state.vectors.at[pnew].set(rows.astype(state.vectors.dtype))
    ids = state.ids.at[pnew].set(rids)
    slot_valid = state.slot_valid.at[pnew].set(keepm)
    used = state.used.at[pnew].set(n)
    lengths = state.lengths.at[pnew].set(n)
    flat = pnew * C + jnp.arange(C, dtype=jnp.int32)
    id_loc = state.id_loc.at[oob(rids, keepm, cfg.max_ids)].set(flat,
                                                                mode="drop")
    state = dataclasses_replace(state, vectors=vectors, ids=ids,
                                slot_valid=slot_valid, used=used,
                                lengths=lengths, id_loc=id_loc)
    if cfg.use_pq:
        state = dataclasses_replace(
            state,
            codes=state.codes.at[pnew].set(
                _encode_written(state, cfg, rows[None])[0]),
            pq_posting_slot=state.pq_posting_slot.at[pnew].set(
                state.pq_active))

    parents = jnp.stack([pid, jnp.where(has_partner, partner, -1)])
    rec_meta = vm.transition(state.rec_meta, parents, STATUS_DELETED,
                             jnp.stack([ver, ver]))
    rec_succ = vm.set_successors(state.rec_succ, parents,
                                 jnp.stack([pnew, pnew]),
                                 jnp.array([-1, -1]))
    nbrs = state.nbrs.at[pnew].set(state.nbrs[pid])
    state = dataclasses_replace(state, rec_meta=rec_meta, rec_succ=rec_succ,
                                nbrs=nbrs, global_version=ver)
    return state, pnew, has_partner


# ---------------------------------------------------------------------------
# LIRE reassign (paper III-B2) — post split/merge closure maintenance
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("cfg",))
def reassign_check(state: IndexState, cfg: UBISConfig, pid):
    """For each vector of ``pid``: if a strictly nearer NORMAL posting
    exists, move it there (append + tombstone here)."""
    C = cfg.capacity
    tile = state.vectors[pid].astype(jnp.float32)
    tids = state.ids[pid]
    mask = state.slot_valid[pid]
    status = vm.unpack_status(state.rec_meta)
    other = (state.allocated & (status == STATUS_NORMAL)
             & ~state.tier_spilled)
    other = other.at[pid].set(False)
    sc = ops.centroid_score(tile, state.centroids, other,
                            backend=cfg.use_pallas)
    best_other = ref.exact_argmin(sc, -1)
    best_d = jnp.min(sc, -1)
    own = state.centroids[pid].astype(jnp.float32)
    d_own = (jnp.sum(own * own)
             - 2 * jnp.matmul(tile, own, precision=ref.HIGHEST))
    move = mask & (best_d < d_own)

    state, ok, _ = batched_append(state, cfg, tile, tids,
                                  jnp.where(move, best_other, -1), move)
    moved = move & ok
    # tombstone moved rows here
    slot_valid = state.slot_valid.at[pid].set(
        state.slot_valid[pid] & ~moved)
    lengths = state.lengths.at[pid].add(
        -jnp.sum(moved).astype(jnp.int32))
    state = dataclasses_replace(
        state, slot_valid=slot_valid, lengths=lengths,
        global_version=state.global_version + jnp.uint32(1))
    return state, jnp.sum(moved)


# ---------------------------------------------------------------------------
# epoch GC — reclaim retired postings (TPU-native RCU analogue)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("cfg", "k"))
def gc_round(state: IndexState, cfg: UBISConfig, min_live_version, k: int):
    """Reclaim up to ``k`` DELETED postings whose retirement version is
    older than the oldest live snapshot; their ids return to the free
    list and successor words are cleared (chasers then re-locate)."""
    status = vm.unpack_status(state.rec_meta)
    weight = vm.unpack_weight(state.rec_meta)
    dead = (state.allocated & (status == STATUS_DELETED)
            & (weight < jnp.asarray(min_live_version, jnp.uint32)))
    # pick up to k by argsort (dead first)
    order = jnp.argsort(~dead, stable=True)[:k]
    valid = dead[order]
    state = free_postings(state, order.astype(jnp.int32), valid)
    return state, jnp.sum(valid)


# ---------------------------------------------------------------------------
# batched background round — the whole marked batch in ONE device program
# ---------------------------------------------------------------------------
# The driver used to sequence split/merge/compact one posting at a time,
# with a host status read, a free-list read, and a separate jit dispatch
# per op.  ``background_round`` replaces that loop: the batch of marked
# (kind, pid) ops executes as a single SPMD program — vmapped masked
# 2-means over a (B, C, d) gather, ranked free-list pops so concurrent
# allocations never collide, one scatter installing every successor
# pointer, and a fused post-op reassign pass.  Conflicts that the
# sequential order used to resolve implicitly are resolved explicitly:
#   * duplicate pids        -> first occurrence wins (recorder CAS rule);
#   * two merges, 1 partner -> first in batch order wins, loser defers;
#   * free-list exhaustion  -> a sequential grant scan admits ops in
#                              batch order while slots last, later ops
#                              defer (revert to NORMAL, re-marked later);
#   * postings retiring this round are excluded from every move-out /
#     reassign target set, so no vector can land in a dying tile.


def _pack_rows(tile, tids, member_mask):
    """Compact ``member_mask`` rows of one tile to the front (the
    vmappable core of ``_write_members``, minus the state scatter)."""
    C = tile.shape[0]
    order = jnp.argsort(~member_mask, stable=True)
    n = jnp.sum(member_mask)
    rows = tile[order]
    rids = tids[order]
    keep = jnp.arange(C) < n
    rows = jnp.where(keep[:, None], rows, 0)
    rids = jnp.where(keep, rids, NO_ID)
    return rows, rids, keep, n.astype(jnp.int32)


def _merge_rows(t1, i1, m1, t2, i2, m2):
    """Stable-compact the live members of two tiles into one (the
    vmappable core of ``merge_postings``' tile construction)."""
    C = t1.shape[0]
    o1 = jnp.argsort(~m1, stable=True)
    o2 = jnp.argsort(~m2, stable=True)
    rows = jnp.concatenate([t1[o1], t2[o2]])
    rids = jnp.concatenate([i1[o1], i2[o2]])
    keepm = jnp.concatenate([m1[o1], m2[o2]])
    order = jnp.argsort(~keepm, stable=True)[:C]
    rows, rids, keepm = rows[order], rids[order], keepm[order]
    rows = jnp.where(keepm[:, None], rows, 0)
    rids = jnp.where(keepm, rids, NO_ID)
    return rows, rids, keepm, jnp.sum(keepm).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("cfg", "reassign", "use_cache"))
def background_round(state: IndexState, cfg: UBISConfig, kinds, pids,
                     reassign: bool = True, use_cache: bool = True):
    """Execute a padded batch of marked background ops in one device call.

    kinds: (B,) int32 in {KIND_NONE, KIND_SPLIT, KIND_MERGE, KIND_COMPACT}
    pids:  (B,) int32 posting ids (-1 = padding)

    Ops must have been marked (SPLITTING for split/compact, MERGING for
    merge) in an earlier round — the two-phase window the vector cache
    depends on.  ``use_cache=False`` folds split-side spills back into
    child ``a`` instead of the cache (the sharded path, where the
    replicated cache cannot be written per-shard).  Returns
    (state, BackgroundRound).  Works on a sharded sub-pool too: all
    shapes derive from ``state`` and ``cfg.max_postings`` is only used
    as an out-of-bounds scatter sentinel (>= any local pool size).
    """
    B = kinds.shape[0]
    C = cfg.capacity
    M = state.lengths.shape[0]
    MS = cfg.max_postings           # OOB sentinel, >= M under shard_map
    d = cfg.dim
    ver = state.global_version + jnp.uint32(1)

    kinds = jnp.asarray(kinds, jnp.int32)
    pids = jnp.asarray(pids, jnp.int32)
    safe = jnp.clip(pids, 0, M - 1)
    status = vm.unpack_status(state.rec_meta)

    want = jnp.where(kinds == KIND_MERGE, STATUS_MERGING, STATUS_SPLITTING)
    # ~tier_spilled: a spilled posting has no device float tile to split/
    # merge/compact — the tier planner must promote it first (detect()
    # never marks one; this guards stale external batches)
    valid = ((pids >= 0) & (kinds != KIND_NONE)
             & vm.first_occurrence_mask(pids)
             & state.allocated[safe] & (status[safe] == want)
             & ~state.tier_spilled[safe])

    lengths0 = state.lengths[safe]
    # a split whose live length no longer exceeds l_max demotes to compact
    # (Alg. 1 lines 1-4) — decided on device, no host length read
    kind = jnp.where(valid & (kinds == KIND_SPLIT) & (lengths0 <= cfg.l_max),
                     KIND_COMPACT, jnp.where(valid, kinds, KIND_NONE))
    is_split = kind == KIND_SPLIT
    is_merge = kind == KIND_MERGE

    # append-target eligibility: spilled postings excluded (no device
    # float tile to append into) — all-False mask when tiering is off
    normal0 = (state.allocated & (status == STATUS_NORMAL)
               & ~state.tier_spilled)

    # ---- merge partner selection (conflicts: first in batch order wins)
    n_me = jnp.where(is_merge, lengths0, 0)
    psc = ops.centroid_score(state.centroids[safe].astype(jnp.float32),
                             state.centroids, normal0,
                             backend=cfg.use_pallas)            # (B, M)
    psc = jnp.where(state.lengths[None, :] + n_me[:, None] < cfg.l_max,
                    psc, BIG)
    partner = ref.exact_argmin(psc, -1)
    has_partner = (jnp.min(psc, -1) < BIG / 2) & is_merge
    pkey = jnp.where(has_partner, partner,
                     -2 - jnp.arange(B, dtype=jnp.int32))
    merge_ok = is_merge & (vm.first_occurrence_mask(pkey) | ~has_partner)
    kind = jnp.where(is_merge & ~merge_ok, KIND_NONE, kind)
    is_merge = kind == KIND_MERGE
    is_compact = kind == KIND_COMPACT

    # ---- free-slot budget: sequential grant scan over the batch -------
    demand = jnp.where(is_split, 2, jnp.where(is_merge, 1, 0))

    def grant_step(off, dem):
        g = off + dem <= state.free_top
        return off + jnp.where(g, dem, 0), (g, off)

    _, (granted, starts) = jax.lax.scan(grant_step, jnp.int32(0), demand)
    exec_ = (kind != KIND_NONE) & granted
    split_exec = is_split & exec_
    merge_exec = is_merge & exec_
    compact_exec = is_compact & exec_
    deferred = valid & ~exec_            # revert to NORMAL, re-mark later
    total = jnp.sum(jnp.where(exec_, demand, 0))

    # ---- ranked free-list pops: op i takes slots [start_i, start_i+dem)
    idx1 = state.free_top - 1 - starts
    pa = jnp.where(split_exec | merge_exec,
                   state.free_list[jnp.clip(idx1, 0, M - 1)], -1)
    pb = jnp.where(split_exec,
                   state.free_list[jnp.clip(idx1 - 1, 0, M - 1)], -1)

    partner = jnp.where(merge_exec & has_partner, partner, -1)
    has_partner = partner >= 0
    # postings retiring this round: split/merge parents + merge partners;
    # excluded from every append-target set below
    retiring = jnp.zeros((M,), bool)
    retiring = retiring.at[oob(pids, split_exec | merge_exec, MS)].set(
        True, mode="drop")
    retiring = retiring.at[oob(partner, has_partner, MS)].set(
        True, mode="drop")

    tiles = state.vectors[safe].astype(jnp.float32)      # (B, C, d)
    tids_all = state.ids[safe]                           # (B, C)
    masks = state.slot_valid[safe]                       # (B, C)

    # ---- split planning: vmapped masked 2-means + Alg. 1 balance ------
    # The 2-means sweep and the (B*C, M) nearer-posting matmul are the
    # round's dominant FLOPs but only splits consume them: an all-compact
    # / all-merge batch skips the whole block via lax.cond (ROADMAP
    # follow-up; the skip is observable as bg_ms_per_op in fig8).
    vmean = jax.vmap(_masked_mean)

    def split_plan(tile, mask):
        assign, c0, c1 = _two_means(
            tile, mask, cfg.kmeans_iters,
            init="median" if cfg.is_ubis else "farthest")
        n0 = jnp.sum((assign == 0) & mask)
        n1 = jnp.sum((assign == 1) & mask)
        small_is_0 = n0 <= n1
        imbalanced = cfg.is_ubis & (
            jnp.minimum(n0, n1).astype(jnp.float32)
            < cfg.balance_factor * jnp.maximum(n0 + n1, 1).astype(
                jnp.float32))
        small_side = jnp.where(small_is_0, 0, 1)
        small_mask = (assign == small_side) & mask
        big_mask = (assign == 1 - small_side) & mask
        c_big = jnp.where(small_is_0, c1, c0)
        c_small = jnp.where(small_is_0, c0, c1)
        return small_mask, big_mask, c_big, c_small, imbalanced

    def plan_splits(_):
        small_mask, big_mask, c_big, c_small, imbalanced = jax.vmap(
            split_plan)(tiles, masks)
        # nearer-posting search per small-side row, one flat score call
        sc = ops.centroid_score(tiles.reshape(B * C, d), state.centroids,
                                normal0 & ~retiring,
                                backend=cfg.use_pallas)
        best_other = ref.exact_argmin(sc, -1).reshape(B, C)
        best_d = jnp.min(sc, -1).reshape(B, C)
        d_big_score = (jnp.sum(c_big ** 2, -1)[:, None]
                       - 2 * jnp.einsum("bcd,bd->bc", tiles, c_big,
                                        precision=ref.HIGHEST))
        nearer = best_d < d_big_score
        move_out = (imbalanced[:, None] & small_mask & nearer
                    & split_exec[:, None])
        fold_in = imbalanced[:, None] & small_mask & ~nearer
        members_a = jnp.where(imbalanced[:, None], big_mask | fold_in,
                              big_mask)
        members_b = jnp.where(imbalanced[:, None],
                              jnp.zeros_like(small_mask), small_mask)
        # termination guard: median bisection when a survivor stays
        # oversize
        oversized = cfg.is_ubis & (
            (jnp.sum(members_a, -1) > cfg.l_max)
            | (jnp.sum(members_b, -1) > cfg.l_max))
        med = jax.vmap(_median_bisect)(tiles, masks)
        med_a = (med == 0) & masks
        med_b = (med == 1) & masks
        members_a = jnp.where(oversized[:, None], med_a, members_a)
        members_b = jnp.where(oversized[:, None], med_b, members_b)
        move_out = move_out & ~oversized[:, None]
        c_big = jnp.where(oversized[:, None], vmean(tiles, med_a, c_big),
                          c_big)
        c_small = jnp.where(oversized[:, None],
                            vmean(tiles, med_b, c_small), c_small)
        cent_a = vmean(tiles, members_a, c_big)
        cent_b = vmean(tiles, members_b, c_small)
        return members_a, members_b, move_out, best_other, cent_a, cent_b

    def plan_nothing(_):
        zc = jnp.zeros((B, C), bool)
        return (zc, zc, zc, jnp.zeros((B, C), jnp.int32),
                jnp.zeros((B, d), jnp.float32),
                jnp.zeros((B, d), jnp.float32))

    (members_a, members_b, move_out, best_other, cent_a,
     cent_b) = jax.lax.cond(jnp.any(split_exec), plan_splits, plan_nothing,
                            None)
    b_empty = ~jnp.any(members_b, -1) & split_exec

    # ---- merge tile construction --------------------------------------
    safe_partner = jnp.clip(partner, 0, M - 1)
    pt = state.vectors[safe_partner].astype(jnp.float32)
    pi = state.ids[safe_partner]
    pmask = state.slot_valid[safe_partner] & has_partner[:, None]
    m_rows, m_rids, m_keep, m_n = jax.vmap(_merge_rows)(
        tiles, tids_all, masks, pt, pi, pmask)
    n1 = jnp.sum(masks, -1)
    n2 = jnp.sum(pmask, -1)
    mean1 = vmean(tiles, masks, state.centroids[safe].astype(jnp.float32))
    mean2 = vmean(pt, pmask, jnp.zeros((B, d), jnp.float32))
    cent_m = ((mean1 * n1[:, None] + mean2 * n2[:, None])
              / jnp.maximum(n1 + n2, 1)[:, None])

    # ---- compact + split children tile packing ------------------------
    vpack = jax.vmap(_pack_rows)
    a_rows, a_rids, a_keep, a_n = vpack(tiles, tids_all, members_a)
    b_rows, b_rids, b_keep, b_n = vpack(tiles, tids_all, members_b)
    c_rows, c_rids, c_keep, c_n = vpack(tiles, tids_all, masks)

    # ---- one unified scatter writes every produced tile ---------------
    w_pid = jnp.concatenate([jnp.where(split_exec, pa, -1),
                             jnp.where(split_exec, pb, -1),
                             jnp.where(merge_exec, pa, -1),
                             jnp.where(compact_exec, pids, -1)])
    w_valid = jnp.concatenate([split_exec, split_exec, merge_exec,
                               compact_exec])
    w_rows = jnp.concatenate([a_rows, b_rows, m_rows, c_rows])
    w_rids = jnp.concatenate([a_rids, b_rids, m_rids, c_rids])
    w_keep = jnp.concatenate([a_keep, b_keep, m_keep, c_keep])
    w_keep = w_keep & w_valid[:, None]
    w_rids = jnp.where(w_keep, w_rids, NO_ID)
    w_n = jnp.concatenate([a_n, b_n, m_n, c_n])
    w_cent = jnp.concatenate([cent_a, cent_b, cent_m,
                              state.centroids[safe].astype(jnp.float32)])

    # claim the popped slots (recorder word + allocated + free_top)
    new_pids = jnp.concatenate([pa, pb])
    np_safe = oob(new_pids, new_pids >= 0, MS)
    rec_meta = state.rec_meta.at[np_safe].set(
        vm.pack_meta(jnp.uint32(STATUS_NORMAL), ver), mode="drop")
    rec_succ = state.rec_succ.at[np_safe].set(
        jnp.uint32((NO_SUCC << 16) | NO_SUCC), mode="drop")
    allocated = state.allocated.at[np_safe].set(True, mode="drop")
    # cold-tier plane: decay every touch counter (the per-round half-
    # life the tier planner's cold-age trigger reads — pure local math,
    # zero collectives under shard_map), children inherit the parent's
    # decayed heat, and every posting born this round is float-resident.
    heat = state.heat
    tier_spilled = state.tier_spilled
    if cfg.use_tier:
        heat = heat >> 1
        parents2 = jnp.clip(jnp.concatenate([pids, pids]), 0, M - 1)
        heat = heat.at[np_safe].set(heat[parents2], mode="drop")
        tier_spilled = tier_spilled.at[np_safe].set(False, mode="drop")

    wt = oob(w_pid, w_valid, MS)
    vectors = state.vectors.at[wt].set(
        w_rows.astype(state.vectors.dtype), mode="drop")
    ids_arr = state.ids.at[wt].set(w_rids, mode="drop")
    slot_valid = state.slot_valid.at[wt].set(w_keep, mode="drop")
    used = state.used.at[wt].set(w_n, mode="drop")
    lengths = state.lengths.at[wt].set(w_n, mode="drop")
    centroids = state.centroids.at[wt].set(
        w_cent.astype(state.centroids.dtype), mode="drop")
    flat = wt[:, None] * C + jnp.arange(C, dtype=jnp.int32)[None, :]
    id_loc = state.id_loc.at[
        oob(w_rids.reshape(-1), w_keep.reshape(-1), cfg.max_ids)].set(
        flat.reshape(-1), mode="drop")
    codes = state.codes
    pq_posting_slot = state.pq_posting_slot
    if cfg.use_pq:
        # every tile produced this round (split children, merge product,
        # compacted survivors) re-encodes under the ACTIVE codebook —
        # the lazy upgrade point of the versioned-codebook scheme
        codes = codes.at[wt].set(_encode_written(state, cfg, w_rows),
                                 mode="drop")
        pq_posting_slot = pq_posting_slot.at[wt].set(state.pq_active,
                                                     mode="drop")

    # ---- batched retirement: DELETED + successor installation ---------
    succ_b = jnp.where(b_empty, -1, pb)
    ret_pids = jnp.concatenate([jnp.where(split_exec, pids, -1),
                                jnp.where(merge_exec, pids, -1),
                                partner])
    ret_s1 = jnp.concatenate([jnp.where(split_exec, pa, -1),
                              jnp.where(merge_exec, pa, -1),
                              jnp.where(has_partner, pa, -1)])
    ret_s2 = jnp.concatenate([succ_b,
                              jnp.full((2 * B,), -1, jnp.int32)])
    rec_meta, rec_succ = vm.retire(rec_meta, rec_succ, ret_pids,
                                   ret_s1, ret_s2, ver)
    # Rescue rule: no mark may outlive a round it rode in.  A lane can be
    # invalid (stale kind, duplicate pid) while its posting still carries
    # SPLITTING/MERGING — e.g. a posting double-marked compact+merge: the
    # first lane fails the status check, the second dies to the dedup.
    # If no *other* lane handles that posting this round, revert it to
    # NORMAL so the detector can re-mark it (else it is wedged forever:
    # detect() only considers NORMAL postings).
    handled = jnp.zeros((M,), bool).at[
        oob(pids, exec_ | deferred, MS)].set(True, mode="drop")
    st0 = status[safe]
    stuck = ((pids >= 0) & ~exec_ & ~deferred & ~handled[safe]
             & state.allocated[safe]
             & ((st0 == STATUS_SPLITTING) | (st0 == STATUS_MERGING)))
    # deferred ops, rescued stragglers + finished compacts return to NORMAL
    rec_meta = vm.transition(
        rec_meta,
        jnp.concatenate([jnp.where(deferred | stuck, pids, -1),
                         jnp.where(compact_exec, pids, -1)]),
        STATUS_NORMAL)

    # ---- neighbourhood graph: children adopt the parent's edges -------
    pn = state.nbrs[safe]
    nb_pid = jnp.concatenate([jnp.where(split_exec, pa, -1),
                              jnp.where(split_exec, pb, -1),
                              jnp.where(merge_exec, pa, -1)])
    nb_rows = jnp.concatenate([
        jnp.concatenate([jnp.where(b_empty, pa, pb)[:, None], pn[:, :-1]], 1),
        jnp.concatenate([pa[:, None], pn[:, :-1]], 1),
        pn])
    nbrs = state.nbrs.at[oob(nb_pid, nb_pid >= 0, MS)].set(
        nb_rows, mode="drop")

    state = dataclasses_replace(
        state, vectors=vectors, ids=ids_arr, slot_valid=slot_valid,
        used=used, lengths=lengths, centroids=centroids, rec_meta=rec_meta,
        rec_succ=rec_succ, allocated=allocated, nbrs=nbrs, id_loc=id_loc,
        codes=codes, pq_posting_slot=pq_posting_slot,
        heat=heat, tier_spilled=tier_spilled,
        free_top=state.free_top - total, global_version=ver)

    # empty b-sides go straight back to the free list
    state = free_postings(state, pb, b_empty)

    # ---- small-side move-outs (one conflict-free append for the batch)
    mo_vecs = tiles.reshape(B * C, d)
    mo_ids = tids_all.reshape(B * C)
    mo = move_out.reshape(B * C)
    mo_tgt = jnp.where(mo, best_other.reshape(B * C), -1)
    state, mo_ok, _ = batched_append(state, cfg, mo_vecs, mo_ids, mo_tgt, mo)
    spill = mo & ~mo_ok
    if use_cache:
        state, cache_ok = cache_append(state, cfg, mo_vecs, mo_ids,
                                       jnp.where(spill, mo_tgt, -1), spill)
        lost = spill & ~cache_ok
        n_spill = jnp.sum(spill & cache_ok)
    else:  # no cache (sharded path): every spill folds back
        lost = spill
        n_spill = jnp.int32(0)
    # spills the cache could not hold (or cache-less mode) fold back into
    # child a — always fits (|members_a| + |move_out| <= parent length <=
    # capacity), so a full cache degrades to a lopsided split instead of
    # silently dropping the vector with id_loc dangling into the retired
    # parent (the sequential oracle's latent flaw, not replicated here)
    pa_row = jnp.broadcast_to(pa[:, None], (B, C)).reshape(B * C)
    state, _, _ = batched_append(state, cfg, mo_vecs, mo_ids,
                                 jnp.where(lost, pa_row, -1), lost)

    # ---- fused post-op reassign over every posting born this round ----
    # Gated by lax.cond: the (3B*C, M) score matmul only runs when the
    # batch actually produced a posting (all-compact batches skip it).
    if reassign:
        r_pid = jnp.concatenate([jnp.where(split_exec, pa, -1),
                                 jnp.where(split_exec & ~b_empty, pb, -1),
                                 jnp.where(merge_exec, pa, -1)])

        def do_reassign(state):
            rs = jnp.clip(r_pid, 0, M - 1)
            r_tiles = state.vectors[rs].astype(jnp.float32)
            r_ids = state.ids[rs]
            r_mask = state.slot_valid[rs] & (r_pid >= 0)[:, None]
            status2 = vm.unpack_status(state.rec_meta)
            sc2 = ops.centroid_score(
                r_tiles.reshape(3 * B * C, d), state.centroids,
                state.allocated & (status2 == STATUS_NORMAL)
                & ~state.tier_spilled,
                backend=cfg.use_pallas)
            own = jnp.broadcast_to(rs[:, None], (3 * B, C)).reshape(-1)
            sc2 = sc2.at[jnp.arange(3 * B * C), own].set(BIG)
            r_best = ref.exact_argmin(sc2, -1)
            r_bd = jnp.min(sc2, -1)
            own_c = state.centroids[rs].astype(jnp.float32)
            d_own = (jnp.sum(own_c ** 2, -1)[:, None]
                     - 2 * jnp.einsum("bcd,bd->bc", r_tiles, own_c,
                                      precision=ref.HIGHEST)).reshape(-1)
            mv = r_mask.reshape(-1) & (r_bd < d_own)
            state, mv_ok, _ = batched_append(
                state, cfg, r_tiles.reshape(-1, d), r_ids.reshape(-1),
                jnp.where(mv, r_best, -1), mv)
            moved = mv & mv_ok
            src_flat = (own * C
                        + jnp.tile(jnp.arange(C, dtype=jnp.int32), 3 * B))
            slot_valid2 = _flat_set(state.slot_valid,
                                    oob(src_flat, moved, MS * C),
                                    jnp.zeros_like(moved))
            lengths2 = state.lengths.at[oob(own, moved, MS)].add(
                -1, mode="drop")
            state = dataclasses_replace(state, slot_valid=slot_valid2,
                                        lengths=lengths2)
            return state, jnp.sum(moved).astype(jnp.int32)

        state, n_re = jax.lax.cond(
            jnp.any(r_pid >= 0), do_reassign,
            lambda state: (state, jnp.int32(0)), state)
    else:
        n_re = jnp.int32(0)

    i32 = lambda x: jnp.asarray(x, jnp.int32)
    rr = BackgroundRound(
        executed=i32(jnp.sum(exec_)), n_split=i32(jnp.sum(split_exec)),
        n_merge=i32(jnp.sum(merge_exec)),
        n_compact=i32(jnp.sum(compact_exec)),
        deferred=i32(jnp.sum(deferred) + jnp.sum(stuck)),
        moved_out=i32(jnp.sum(mo & mo_ok)),
        spilled=i32(n_spill), reassigned=i32(n_re),
        freed=i32(jnp.sum(b_empty)))
    return state, rr


def mark_selected(rec_meta, kinds, pids):
    """Transition the selected batch to its window status on device
    (SPLITTING for split/compact lanes, MERGING for merge lanes) — the
    mark half of the two-phase window, shared by the sharded round and
    the single-device ``fused_tick`` path."""
    split_like = (kinds == KIND_SPLIT) | (kinds == KIND_COMPACT)
    rec_meta = vm.transition(rec_meta, jnp.where(split_like, pids, -1),
                             STATUS_SPLITTING)
    return vm.transition(rec_meta, jnp.where(kinds == KIND_MERGE, pids, -1),
                         STATUS_MERGING)


@functools.partial(jax.jit, static_argnames=("cfg", "k"))
def mark_round(state: IndexState, cfg: UBISConfig, k: int):
    """Device-side candidate selection + mark in one program: the
    ``fused_tick`` replacement for the driver's ``detect()`` host
    round-trip.  Returns (state, kinds, pids, n_marked) — kinds/pids
    stay on device and feed the next tick's ``background_round``; only
    the scalar count crosses to the host (for scheduling/quiescence).
    """
    kinds, pids = select_candidates(state, cfg, k)
    rec_meta = mark_selected(state.rec_meta, kinds, pids)
    state = dataclasses_replace(
        state, rec_meta=rec_meta,
        global_version=state.global_version + jnp.uint32(1))
    return state, kinds, pids, jnp.sum(kinds != KIND_NONE)


@functools.partial(jax.jit, static_argnames=("cfg", "k"))
def select_candidates(state: IndexState, cfg: UBISConfig, k: int):
    """Device-side candidate pick: top-k due ops by the driver's priority
    (splits by length desc, then compacts, then merges by length asc).
    Returns (kinds (k,), pids (k,)) ready for ``background_round`` — used
    by the sharded path, where selection must not round-trip the host."""
    split_due, merge_due, compact_due = detect(state, cfg)
    L = jnp.int32(1) << 20
    key = jnp.where(split_due, -state.lengths,
                    jnp.where(compact_due, L,
                              jnp.where(merge_due, 2 * L + state.lengths,
                                        3 * L)))
    order = jnp.argsort(key, stable=True)[:k].astype(jnp.int32)
    due = key[order] < 3 * L
    kinds = jnp.where(split_due[order], KIND_SPLIT,
                      jnp.where(compact_due[order], KIND_COMPACT,
                                KIND_MERGE))
    kinds = jnp.where(due, kinds, KIND_NONE)
    return kinds, jnp.where(due, order, -1)
