"""Update data path: conflict-free batched appends, insert/delete rounds.

This implements the *high-concurrency controller* (paper IV-B2).  A
round is one jitted SPMD program processing a padded batch of jobs:

  1. resolve targets (hinted jobs chase DELETED successor pointers;
     fresh jobs locate the nearest insertable centroid);
  2. branch on Posting Recorder status — NORMAL -> direct append,
     SPLITTING/MERGING -> vector cache (UBIS) or reject (SPFresh's
     posting-lock model), DELETED dead-end -> relocate;
  3. resolve conflicts *ahead of* the scatter: jobs are ranked within
     their target-posting group (stable job order) and accepted while
     capacity lasts — the deterministic equivalent of the paper's CAS
     (exactly one winner per slot, no retries);
  4. one batched scatter applies all winners; losers divert to the
     cache or are rejected, never silently dropped.

All functions are pure ``state -> state`` transforms; ``cfg`` is static.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..kernels import ops, ref
from . import version_manager as vm
from .types import (NO_ID, NO_SUCC, STATUS_DELETED, STATUS_MERGING,
                    STATUS_NORMAL, STATUS_SPLITTING, IndexState, RoundResult,
                    UBISConfig)


# ---------------------------------------------------------------------------
# small combinators
# ---------------------------------------------------------------------------

def group_ranks(keys: jax.Array, valid: jax.Array) -> jax.Array:
    """Rank of each job within its equal-key group, stable job order.

    Invalid jobs get arbitrary (large) ranks.  O(J log J).
    """
    J = keys.shape[0]
    big = jnp.int32(jnp.iinfo(jnp.int32).max)
    key = jnp.where(valid, keys, big)
    order = jnp.argsort(key, stable=True)
    ks = key[order]
    idx = jnp.arange(J, dtype=jnp.int32)
    seg_start = jnp.concatenate([jnp.ones((1,), bool), ks[1:] != ks[:-1]])
    seg_first = jax.lax.associative_scan(
        jnp.maximum, jnp.where(seg_start, idx, 0))
    ranks_sorted = idx - seg_first
    return jnp.zeros((J,), jnp.int32).at[order].set(ranks_sorted)


def oob(idx, mask, size):
    """Scatter-index sentinel: ``mode="drop"`` only drops OUT-OF-BOUNDS
    indices, and -1 is *in bounds* (wraps).  Masked entries therefore map
    to ``size``, which is genuinely out of range."""
    return jnp.where(mask, idx, jnp.asarray(size, idx.dtype))


def _flat_set(arr2d, flat_idx, values):
    """Scatter rows into a (M, C, ...) array viewed as (M*C, ...)."""
    shp = arr2d.shape
    flat = arr2d.reshape((shp[0] * shp[1],) + shp[2:])
    flat = flat.at[flat_idx].set(values, mode="drop")
    return flat.reshape(shp)


# ---------------------------------------------------------------------------
# allocation
# ---------------------------------------------------------------------------

def alloc_postings(state: IndexState, cfg: UBISConfig, k: int,
                   centroids_new: jax.Array, weight) -> tuple:
    """Pop ``k`` posting slots from the free list and initialise them.

    Returns (state, pids (k,) int32).  Caller must ensure free_top >= k
    (the driver checks host-side before enqueuing structural ops).
    """
    idx = state.free_top - 1 - jnp.arange(k, dtype=jnp.int32)
    pids = state.free_list[idx]
    meta = vm.pack_meta(jnp.full((k,), STATUS_NORMAL, jnp.uint32),
                        jnp.broadcast_to(jnp.asarray(weight, jnp.uint32), (k,)))
    succ = jnp.full((k,), (NO_SUCC << 16) | NO_SUCC, jnp.uint32)
    state = dataclasses_replace(
        state,
        ids=state.ids.at[pids].set(NO_ID),
        slot_valid=state.slot_valid.at[pids].set(False),
        used=state.used.at[pids].set(0),
        lengths=state.lengths.at[pids].set(0),
        centroids=state.centroids.at[pids].set(
            centroids_new.astype(state.centroids.dtype)),
        rec_meta=state.rec_meta.at[pids].set(meta),
        rec_succ=state.rec_succ.at[pids].set(succ),
        allocated=state.allocated.at[pids].set(True),
        free_top=state.free_top - k,
        # fresh postings write codes under the active codebook generation
        pq_posting_slot=state.pq_posting_slot.at[pids].set(state.pq_active),
        # fresh postings are float-resident and born warm (cold-tier plane)
        heat=state.heat.at[pids].set(jnp.uint32(cfg.tier_promote_heat)),
        tier_spilled=state.tier_spilled.at[pids].set(False),
    )
    return state, pids


def free_postings(state: IndexState, pids: jax.Array,
                  valid: jax.Array) -> IndexState:
    """Push reclaimed posting ids back onto the free stack (GC).

    Also sweeps the Posting Recorder: any successor pointer referencing
    a reclaimed id is cleared, so a chaser can never follow a recycled
    slot into an unrelated posting — it dead-ends and re-locates.
    """
    k = pids.shape[0]
    M = state.rec_succ.shape[0]
    rank = group_ranks(jnp.zeros_like(pids), valid)
    slot = state.free_top + rank
    tgt = oob(slot, valid, M)
    free_list = state.free_list.at[tgt].set(pids, mode="drop")
    n = jnp.sum(valid.astype(jnp.int32))
    safe_pids = oob(pids, valid, M)
    allocated = state.allocated.at[safe_pids].set(False, mode="drop")
    succ = jnp.full((k,), (NO_SUCC << 16) | NO_SUCC, jnp.uint32)
    rec_succ = state.rec_succ.at[safe_pids].set(succ, mode="drop")
    # recycled slots re-enter the pool float-resident and cold
    heat = state.heat.at[safe_pids].set(jnp.uint32(0), mode="drop")
    tier_spilled = state.tier_spilled.at[safe_pids].set(False, mode="drop")
    # sweep dangling successor references to the reclaimed ids
    freed_mask = jnp.zeros((M,), bool).at[safe_pids].set(True, mode="drop")
    s1, s2 = vm.succ_ids(rec_succ)
    s1 = jnp.where((s1 >= 0) & freed_mask[jnp.clip(s1, 0)], -1, s1)
    s2 = jnp.where((s2 >= 0) & freed_mask[jnp.clip(s2, 0)], -1, s2)
    rec_succ = vm.pack_succ(jnp.where(s1 < 0, NO_SUCC, s1),
                            jnp.where(s2 < 0, NO_SUCC, s2))
    return dataclasses_replace(state, free_list=free_list,
                               free_top=state.free_top + n,
                               allocated=allocated, rec_succ=rec_succ,
                               heat=heat, tier_spilled=tier_spilled)


def dataclasses_replace(state: IndexState, **kw) -> IndexState:
    import dataclasses
    return dataclasses.replace(state, **kw)


def rebuild_free_stack(state: IndexState) -> IndexState:
    """Recompute a canonical free stack from ``allocated``.

    The sharded background round leaves ``free_list``/``free_top``
    fail-safe-empty (per-shard local views cannot form one global
    stack); call this after gathering such a state back to one device
    before handing it to any free-stack consumer (driver, alloc, GC).
    """
    order = jnp.argsort(state.allocated, stable=True)   # free pids first
    n_free = jnp.sum(~state.allocated).astype(jnp.int32)
    return dataclasses_replace(state, free_list=order.astype(jnp.int32),
                               free_top=n_free)


def ensure_free_stack(state: IndexState, check: bool = True) -> IndexState:
    """Snapshot-path guard: rebuild the free stack and *assert* it is
    canonical before any single-device reuse of a gathered state.

    The sharded background/GC round returns ``free_list``/``free_top``
    fail-safe EMPTY (per-shard local views cannot form one global
    stack).  This is the encoded form of that contract: every gather ->
    single-device hand-off (``ShardedUBISDriver.snapshot``) goes through
    here, so a state whose stack would alias live postings can never
    escape to the driver/alloc/GC free-stack consumers.
    """
    state = rebuild_free_stack(state)
    if check:
        import numpy as np
        allocated = np.asarray(state.allocated)
        top = int(state.free_top)
        free = np.asarray(state.free_list)[:top]
        assert top + int(allocated.sum()) == allocated.shape[0], \
            "free stack disagrees with the allocated bitmap"
        assert len(np.unique(free)) == top, "free stack holds duplicates"
        assert not allocated[free].any(), \
            "free stack aliases a live posting"
    return state


# ---------------------------------------------------------------------------
# the conflict-free batched append (shared by every write path)
# ---------------------------------------------------------------------------

def batched_append(state: IndexState, cfg: UBISConfig, vecs, ids, pids,
                   valid, update_id_loc: bool = True):
    """Append jobs to their target postings; winners determined by
    group rank vs. remaining tile capacity.  Returns (state, ok, flat)
    where ``flat`` is the written slot index pid*C+slot (OOB sentinel for
    losers).  ``update_id_loc=False`` lets the sharded path merge the
    (replicated) id map across shards itself."""
    C = cfg.capacity
    ranks = group_ranks(pids, valid)
    safe_pid = jnp.clip(pids, 0, cfg.max_postings - 1)
    slot = state.used[safe_pid] + ranks
    ok = valid & (pids >= 0) & (slot < C)
    MC = cfg.max_postings * C
    flat = oob(safe_pid * C + slot, ok, MC)
    vectors = _flat_set(state.vectors, flat, vecs.astype(state.vectors.dtype))
    ids_arr = _flat_set(state.ids, flat, ids.astype(jnp.int32))
    slot_valid = _flat_set(state.slot_valid, flat,
                           jnp.ones(ids.shape, jnp.bool_))
    add_pid = oob(pids, ok, cfg.max_postings)
    used = state.used.at[add_pid].add(1, mode="drop")
    lengths = state.lengths.at[add_pid].add(1, mode="drop")
    id_loc = state.id_loc
    if update_id_loc:
        id_loc = id_loc.at[oob(ids, ok, cfg.max_ids)].set(flat, mode="drop")
    codes = state.codes
    if cfg.use_pq:
        # quant-plane invariant: every float write carries its code,
        # encoded under the TARGET posting's codebook slot (postings pin
        # a codebook generation; appends must match it, not the active
        # one).  Encode under every slot (V small, static), select per
        # job.  Encode the post-storage-cast value so decode agrees with
        # the stored bytes under non-f32 dtypes.
        from ..quant import pq
        x = vecs.astype(state.vectors.dtype).astype(jnp.float32)
        codes_all = pq.encode_all_versions(state.pq_codebooks, x)
        tslot = jnp.clip(state.pq_posting_slot[safe_pid], 0,
                         cfg.pq_versions - 1)
        code_j = jnp.take_along_axis(
            codes_all.transpose(1, 0, 2), tslot[:, None, None], axis=1
        )[:, 0]                                             # (J, m)
        codes = codes.at[oob(pids, ok, cfg.max_postings), :, slot].set(
            code_j, mode="drop")
    state = dataclasses_replace(state, vectors=vectors, ids=ids_arr,
                                slot_valid=slot_valid, used=used,
                                lengths=lengths, id_loc=id_loc,
                                codes=codes)
    return state, ok, flat


def cache_append(state: IndexState, cfg: UBISConfig, vecs, ids, targets,
                 want):
    """Park jobs in the vector cache (paper IV-B2 branch 3).

    id_loc encoding for cached vectors: ``-2 - cache_slot``."""
    K = cfg.cache_capacity
    ranks = group_ranks(jnp.zeros_like(targets), want)
    slot_order = jnp.argsort(state.cache_valid, stable=True)  # free first
    nfree = jnp.sum(~state.cache_valid)
    ok = want & (ranks < nfree)
    slot = slot_order[jnp.clip(ranks, 0, K - 1)].astype(jnp.int32)
    tgt = oob(slot, ok, K)
    cache_vecs = state.cache_vecs.at[tgt].set(
        vecs.astype(state.cache_vecs.dtype), mode="drop")
    cache_ids = state.cache_ids.at[tgt].set(ids.astype(jnp.int32),
                                            mode="drop")
    cache_target = state.cache_target.at[tgt].set(targets.astype(jnp.int32),
                                                  mode="drop")
    cache_valid = state.cache_valid.at[tgt].set(True, mode="drop")
    id_loc = state.id_loc.at[oob(ids, ok, cfg.max_ids)].set(
        -2 - slot, mode="drop")
    state = dataclasses_replace(
        state, cache_vecs=cache_vecs, cache_ids=cache_ids,
        cache_target=cache_target, cache_valid=cache_valid, id_loc=id_loc)
    return state, ok


def cache_take(state: IndexState, cfg: UBISConfig, n: int):
    """Pop up to ``n`` cached vectors for re-insertion (background drain).

    Returns (state, vecs, ids, targets, taken)."""
    prio = jnp.argsort(~state.cache_valid, stable=True)  # valid entries first
    slots = prio[:n]
    taken = state.cache_valid[slots]
    vecs = state.cache_vecs[slots]
    ids = state.cache_ids[slots]
    targets = state.cache_target[slots]
    cache_valid = state.cache_valid.at[oob(slots, taken, cfg.cache_capacity)
                                        ].set(False, mode="drop")
    # in-flight: id_loc repointed by the follow-up insert round
    state = dataclasses_replace(state, cache_valid=cache_valid)
    return state, vecs, ids, targets, taken


# ---------------------------------------------------------------------------
# foreground rounds
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("cfg",))
def insert_round(state: IndexState, cfg: UBISConfig, vecs, ids, valid,
                 hints):
    """One foreground insert round over a padded job batch.

    hints: (J,) int32 precomputed target posting (-1 = locate fresh);
    used by cache drains and reassignments, and the path that exercises
    the paper's DELETED-branch pointer chasing.
    """
    status = vm.unpack_status(state.rec_meta)
    # spilled postings cannot take appends (their float tile is host-
    # resident): locate routes around them, so fresh vectors always land
    # in a float-resident posting.  All-False mask when tiering is off.
    insertable = (state.allocated & (status != STATUS_DELETED)
                  & ~state.tier_spilled)

    has_hint = hints >= 0
    chased, dead_end = vm.chase_successors(
        state.rec_meta, state.rec_succ, state.allocated, state.centroids,
        jnp.maximum(hints, 0), vecs, cfg.succ_chase_depth)
    chased_ok = (has_hint & ~dead_end & state.allocated[chased]
                 & ~state.tier_spilled[chased])

    scores = ops.centroid_score(vecs, state.centroids, insertable,
                                backend=cfg.use_pallas)
    located = ref.exact_argmin(scores, axis=-1)
    pid = jnp.where(chased_ok, chased, located)

    st = status[pid]
    # a resolved pid can still be spilled when NO insertable posting
    # exists (locate's argmin over an all-masked row is arbitrary): a
    # spilled posting must never take a direct float append, so such
    # jobs take the in-flux branch (cache / reject) instead
    sp_pid = state.tier_spilled[pid]
    normal = (st == STATUS_NORMAL) & ~sp_pid
    in_flux = ((st == STATUS_SPLITTING) | (st == STATUS_MERGING)
               | ((st == STATUS_NORMAL) & sp_pid))

    direct = valid & normal
    state, ok, _ = batched_append(state, cfg, vecs, ids,
                                  jnp.where(direct, pid, -1), direct)
    overflow = direct & ~ok

    if cfg.is_ubis:
        to_cache = valid & (in_flux | overflow)
        state, cached = cache_append(state, cfg, vecs, ids, pid, to_cache)
    else:  # SPFresh lock model: blocked jobs fail this round
        cached = jnp.zeros_like(valid)

    accepted = direct & ok
    rejected = valid & ~accepted & ~cached
    state = dataclasses_replace(
        state, global_version=state.global_version + jnp.uint32(1))
    touched = jnp.zeros((cfg.max_postings,), bool).at[
        oob(pid, accepted, cfg.max_postings)].set(True, mode="drop")
    result = RoundResult(accepted=accepted, cached=cached, rejected=rejected,
                         target=jnp.where(valid, pid, -1))
    return state, result, touched


def apply_tombstones(state: IndexState, cfg: UBISConfig, safe_ids, loc,
                     in_post, in_cache, *, base=0):
    """The shared delete kernel (UBIS semantics), parameterized by the
    caller's owner span.

    ``loc`` carries GLOBAL flat tile locations; only locations inside
    ``[base, base + span)`` (``span`` = this state's local pool in flat
    slots) are written to the tile arrays — the owner-span masking the
    sharded round needs, a no-op for the single-device caller
    (``base=0``, span = the whole pool).  The cache and ``id_loc``
    updates are computed from the (replicated) inputs unconditionally,
    which is what keeps the sharded replicas in sync with zero
    collectives.  Used by both ``delete_round`` and
    ``sharded.make_sharded_delete`` so the two cannot drift.
    """
    C = cfg.capacity
    M_local = state.lengths.shape[0]
    span = M_local * C
    lloc = loc - base
    mine = in_post & (lloc >= 0) & (lloc < span)
    flat = oob(lloc, mine, span)
    slot_valid = _flat_set(state.slot_valid, flat,
                           jnp.zeros(loc.shape, jnp.bool_))
    pid = oob(lloc // C, mine, M_local)
    lengths = state.lengths.at[pid].add(-1, mode="drop")
    cslot = oob(-2 - loc, in_cache, cfg.cache_capacity)
    cache_valid = state.cache_valid.at[cslot].set(False, mode="drop")
    done = in_post | in_cache
    id_loc = state.id_loc.at[oob(safe_ids, done, cfg.max_ids)].set(
        -1, mode="drop")
    state = dataclasses_replace(
        state, slot_valid=slot_valid, lengths=lengths,
        cache_valid=cache_valid, id_loc=id_loc,
        global_version=state.global_version + jnp.uint32(1))
    return state, done


@functools.partial(jax.jit, static_argnames=("cfg",))
def delete_round(state: IndexState, cfg: UBISConfig, del_ids, valid):
    """Mark a padded batch of external ids as deleted (tombstones)."""
    C = cfg.capacity
    safe = jnp.clip(del_ids, 0, cfg.max_ids - 1)
    loc = state.id_loc[safe]
    first = vm.first_occurrence_mask(safe) & valid
    in_post = first & (loc >= 0)
    in_cache = first & (loc <= -2)

    if not cfg.is_ubis:
        # SPFresh lock model: deletes on non-NORMAL postings are blocked.
        pid_all = jnp.clip(loc, 0) // C
        st = vm.unpack_status(state.rec_meta[pid_all])
        blocked = in_post & (st != STATUS_NORMAL)
        in_post = in_post & ~blocked
    else:
        blocked = jnp.zeros_like(valid)

    state, done = apply_tombstones(state, cfg, safe, loc, in_post, in_cache)
    return state, done, blocked


# ---------------------------------------------------------------------------
# recorder transitions used by the background scheduler
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("status",))
def mark_status(state: IndexState, pids, status: int):
    """Transition a batch of postings to SPLITTING/MERGING/NORMAL (the
    'window' phase that makes the vector cache functionally necessary)."""
    rec_meta = vm.transition(state.rec_meta, pids, status)
    return dataclasses_replace(
        state, rec_meta=rec_meta,
        global_version=state.global_version + jnp.uint32(1))
