"""Distributed UBIS: the index sharded over a TPU pod (beyond-paper).

The paper's conclusion lists distributed update as future work; here it
is a first-class feature.  Layout: the posting pool (M postings) shards
over the ``model`` mesh axis; query/job batches shard over ``data``
(× ``pod``).  One shard owns each posting, so *structural* updates
(split/merge/compact/GC) stay shard-local and embarrassingly parallel —
the Posting Recorder's one-winner-per-word rule needs no cross-shard
coordination.  Only two operations communicate:

  * search  — per-shard phase-1 top-nprobe, all-gather the (score, id)
              candidates, global re-rank, per-shard phase-2 scan of the
              postings it owns, all-gather per-shard top-k, final merge;
  * insert  — per-shard locate (scores vs. local centroids), global
              argmin over the gathered per-shard bests routes each job
              to its owner shard, which applies the conflict-free append.

Collective cost per search batch: 2 all-gathers of O(Q·(nprobe + k))
scalars over the model axis — independent of M and dim, which is what
makes the index scale to thousands of chips (§Roofline has the terms).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..kernels import ops, ref
from ..kernels.posting_scan import BIG
from . import balance, version_manager as vm
from .types import (NO_SUCC, STATUS_DELETED, STATUS_NORMAL, IndexState,
                    UBISConfig)
from .update import (apply_tombstones, dataclasses_replace, oob,
                     rebuild_free_stack)


def index_specs(cfg: UBISConfig):
    """PartitionSpecs for every IndexState field (postings over 'model').

    The id->location map and the vector cache are replicated: the cache
    is small and hot (every search scans it); id_loc updates are
    broadcast with the round's results.
    """
    return IndexState(
        vectors=P("model"), ids=P("model"), slot_valid=P("model"),
        used=P("model"), lengths=P("model"), centroids=P("model"),
        rec_meta=P("model"), rec_succ=P("model"), allocated=P("model"),
        nbrs=P("model"),
        cache_vecs=P(), cache_ids=P(), cache_target=P(), cache_valid=P(),
        free_list=P("model"), free_top=P(), global_version=P(),
        id_loc=P(),
        # quant plane: codes follow their posting's shard; the (small)
        # versioned codebooks are replicated so any shard can encode
        codes=P("model"), pq_codebooks=P(), pq_slot_gen=P(),
        pq_active=P(), pq_posting_slot=P("model"),
        # cold-tier plane: heat + residency flags follow their posting
        heat=P("model"), tier_spilled=P("model"),
    )


def _local_topk(scores, ids, k):
    neg, idx = jax.lax.top_k(-scores, k)
    return -neg, jnp.take_along_axis(ids, idx, axis=-1)


def _owned_cache_slice(state: IndexState, my, n_shard):
    """This shard's 1/S slice of the replicated vector cache:
    (vecs, valid, ids) with the clamp-overlap rows masked OUT of
    ``valid``.  Ceil-div slices of a non-divisible capacity overlap at
    the end of the pool (the ``start`` clamp); the ownership mask keeps
    every cache slot scanned by exactly one shard, so the merge
    all-gather can never double-count an entry.  Shared by the sharded
    search and the ``make_sharded_exact`` oracle — the two scans must
    agree on this discipline or recall metrics lie."""
    K_all = state.cache_vecs.shape[0]
    Ks = -(-K_all // n_shard)
    start = jnp.minimum(my * Ks, K_all - Ks)
    cvs = jax.lax.dynamic_slice_in_dim(state.cache_vecs, start, Ks, 0)
    cval = jax.lax.dynamic_slice_in_dim(state.cache_valid, start, Ks, 0)
    cid = jax.lax.dynamic_slice_in_dim(state.cache_ids, start, Ks, 0)
    own = (jnp.arange(Ks) + start) >= my * Ks
    return cvs, cval & own, cid


def _rebase_succ(rec_succ, offset, limit):
    """Shift stored successor pids by ``offset``; anything landing
    outside [0, limit) becomes no-successor."""
    s1, s2 = vm.succ_ids(rec_succ)

    def shift(s):
        t = jnp.where(s >= 0, s + offset, -1)
        return jnp.where((t >= 0) & (t < limit), t, -1)

    t1, t2 = shift(s1), shift(s2)
    return vm.pack_succ(jnp.where(t1 < 0, NO_SUCC, t1),
                        jnp.where(t2 < 0, NO_SUCC, t2))


def _pq_phase2(state: IndexState, cfg: UBISConfig, queries, probe, mine,
               vis, k: int):
    """Sharded search phase 2 served from PQ codes (``cfg.use_pq``).

    Per shard: ADC-scan the owned probed tiles' codes (``C * m`` bytes
    per posting instead of ``C * d * 4``), then fused-rerank the local
    top ``cfg.rerank_k`` candidates against their float rows — the
    shard-local form of ``search._pq_stage``.  The (small) versioned
    codebooks are replicated, so every shard builds the same per-query
    lookup tables.  Returns this shard's (scores, ids) candidate lists,
    ready for the existing merge all-gather.
    """
    from ..quant import pq
    M_local, C, d = state.vectors.shape
    R = min(cfg.rerank_k, probe.shape[1] * C)
    luts = pq.lookup_tables(state.pq_codebooks, queries)  # (Q, V, m, ksub)
    # fused ADC scan + on-chip top-R with the ownership mask applied
    # in-kernel — no (Q, P, C) score tensor on the pallas path
    adc_top, cand = ops.pq_scan_topk(
        luts, state.codes, state.pq_posting_slot, state.slot_valid, vis,
        probe, k=R, qp_ok=mine, backend=cfg.use_pallas)    # (Q, R)
    # fused rerank: gather + exact rescore + cold-tier ADC passthrough
    # (spilled postings have no device float tile — codes-only serving;
    # the driver's optional host rerank refines them from the pinned
    # pool) + local top-k, one kernel — no (Q, R, d) gather in HBM
    exact, cand_sel = ops.rerank_topk(
        queries, state.vectors, state.tier_spilled, cand, adc_top,
        k=min(k, R), backend=cfg.use_pallas)
    cand_ids = jnp.where(exact < BIG / 2,
                         state.ids.reshape(-1)[cand_sel], -1)
    return exact, cand_ids


def make_sharded_search(cfg: UBISConfig, mesh: Mesh, k: int,
                        nprobe: int | None = None,
                        shard_cache_scan: bool = True):
    """Builds a jitted sharded search: (state, queries) -> (ids, scores).

    queries shard over the data axes; the index shards over 'model'.
    ``shard_cache_scan``: each model shard scans only its 1/S slice of
    the (replicated) vector cache and the merge all-gather already in
    flight combines the partial top-ks — S-fold less cache compute for
    zero extra collective traffic (EXPERIMENTS.md §Perf, ubis-index).
    """
    if nprobe is None:
        nprobe = cfg.nprobe
    axes = mesh.axis_names
    qspec = P(("pod", "data") if "pod" in axes else "data")
    st_specs = index_specs(cfg)
    probe_cap = getattr(cfg, "shard_probe_cap", 0)

    def local(state: IndexState, queries):
        n_shard = jax.lax.axis_size("model")
        my = jax.lax.axis_index("model")
        M_local = state.centroids.shape[0]
        Q = queries.shape[0]
        queries = queries.astype(jnp.float32)

        vis = vm.visible(state.rec_meta, state.allocated,
                         state.global_version)
        # phase 1 local: fused centroid score + per-shard top-nprobe
        # (no (Q, M_local) score matrix on the pallas path)
        p_local = min(nprobe, M_local)
        s1, local_pid = ops.centroid_topk(queries, state.centroids, vis,
                                          k=p_local,
                                          backend=cfg.use_pallas)
        # global re-rank of gathered candidates
        s1_all = jax.lax.all_gather(s1, "model", axis=1, tiled=True)
        pid_all = jax.lax.all_gather(
            local_pid + my * 0, "model", axis=1, tiled=True)
        owner = jnp.repeat(jnp.arange(n_shard), p_local)[None, :]
        owner = jnp.broadcast_to(owner, s1_all.shape)
        s_sel, sel_idx = jax.lax.top_k(-s1_all, nprobe)
        probe_owner = jnp.take_along_axis(owner, sel_idx, axis=1)
        probe_pid = jnp.take_along_axis(pid_all, sel_idx, axis=1)
        # phase 2: scan the selected postings THIS shard owns.  A query's
        # nprobe probes spread ~uniformly over S shards (~nprobe/S each),
        # so the scan is COMPACTED to the first `probe_cap` owned probes
        # (phase-1 order = best-first): the gather and distance scan
        # shrink by nprobe/probe_cap with negligible recall impact
        # (only hurts when > probe_cap probes land on one shard).
        mine = probe_owner == my
        cap = probe_cap if probe_cap else nprobe
        if cap < nprobe:
            order = jnp.argsort(~mine, axis=1, stable=True)[:, :cap]
            pid_cap = jnp.take_along_axis(probe_pid, order, axis=1)
            mine_cap = jnp.take_along_axis(mine, order, axis=1)
        else:
            pid_cap, mine_cap = probe_pid, mine
        safe_pid = jnp.where(mine_cap, pid_cap, 0)
        if cfg.use_pq:
            # quant plane: serve phase 2 from the owned probes' CODES
            # (ADC scan + per-shard exact rerank) instead of the float
            # tiles — the sharded form of ``search._pq_stage``
            s2, i2 = _pq_phase2(state, cfg, queries, safe_pid, mine_cap,
                                vis, k)
        else:
            C_ = state.vectors.shape[1]
            k_local = min(k, safe_pid.shape[1] * C_)
            # fused gather scan + top-k with the ownership mask applied
            # in-kernel (no (Q, P, C) score tensor on the pallas path)
            s2, cand2 = ops.posting_scan_topk(
                queries, state.vectors, state.slot_valid, vis, safe_pid,
                k=k_local, qp_ok=mine_cap, backend=cfg.use_pallas)
            i2 = state.ids.reshape(-1)[cand2]
        # cache scan: each shard takes a 1/S slice of the replicated
        # cache (or shard 0 scans everything when disabled)
        if shard_cache_scan:
            cvs, cval_own, cid = _owned_cache_slice(state, my, n_shard)
            ck = min(k, cvs.shape[0])
            s3, cpos = ops.centroid_topk(queries, cvs, cval_own, k=ck,
                                         backend=cfg.use_pallas)
            i3 = cid[cpos]
        else:
            cval = state.cache_valid & (my == 0)
            ck = min(k, state.cache_vecs.shape[0])
            s3, cpos = ops.centroid_topk(queries, state.cache_vecs, cval,
                                         k=ck, backend=cfg.use_pallas)
            i3 = state.cache_ids[cpos]
        s2 = jnp.concatenate([s2, s3], axis=1)
        i2 = jnp.concatenate([i2, i3], axis=1)
        # global merge
        s2_all = jax.lax.all_gather(s2, "model", axis=1, tiled=True)
        i2_all = jax.lax.all_gather(i2, "model", axis=1, tiled=True)
        sf, idf = _local_topk(s2_all, i2_all, k)
        found = jnp.where(sf < BIG / 2, idf, -1)
        return found, sf

    in_specs = (st_specs, qspec)
    fn = jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                       out_specs=(qspec, qspec), check_vma=False)
    return jax.jit(fn)


def make_sharded_insert(cfg: UBISConfig, mesh: Mesh,
                        route_alpha: float = 0.0):
    """Builds a jitted sharded insert round:
    (state, vecs, ids, valid) -> (state, accepted (J,) bool,
    routed (J,) int32).

    Each shard locates jobs against its local centroids; a global argmin
    routes each job to its owner shard, which runs the conflict-free
    batched append on its local state.  Blocked jobs (non-NORMAL status)
    are *rejected* here — the vector cache is host-mediated in
    ``ShardedUBISDriver`` (replicated cache writes would race), which is
    why the per-job accepted mask (not a count) comes back: the driver
    owns the retry/park decision for every rejected lane.  ``routed``
    is the GLOBAL pid the round located for each job (-1 when nothing
    was insertable): parked jobs carry it as their cache target, which
    is what lets the background plane's pressure stats attribute the
    parked backlog to the saturated shard.

    ``route_alpha`` enables **pressure-aware routing** (prefer colder
    shards at locate time, the ROADMAP follow-up that cuts migration
    volume on skewed streams): each job's per-shard best score is
    penalized by ``route_alpha * saturation * range`` where saturation
    is the shard's live-sub-pool fraction and ``range`` is that job's
    finite score spread — so a nearly-full shard only wins a job it is
    decisively closest to, and ties break toward shards with free
    capacity.  Costs one (S,)-scalar all-gather in a round that already
    gathers per-job rows; ``route_alpha=0`` (default) is bit-identical
    to the unpenalized round.
    """
    jspec = P()     # jobs replicated: every shard sees all jobs
    st_specs = index_specs(cfg)

    def local(state: IndexState, vecs, ids, valid):
        import dataclasses as _dc
        from .update import batched_append
        my = jax.lax.axis_index("model")
        M_local = state.centroids.shape[0]
        status = vm.unpack_status(state.rec_meta)
        # spilled postings cannot take appends (float tile host-resident)
        insertable = (state.allocated & (status == 0)
                      & ~state.tier_spilled)
        sc = ops.centroid_score(vecs.astype(jnp.float32), state.centroids,
                                insertable, backend=cfg.use_pallas)
        best_local = jnp.min(sc, axis=1)
        best_pid = ref.exact_argmin(sc, axis=1)
        # global owner = argmin over shards
        all_best = jax.lax.all_gather(best_local, "model", axis=0)  # (S, J)
        if route_alpha:
            # saturation = live vector mass over the shard's pool
            # capacity (smoother than the posting count: it climbs with
            # every accepted append, not only on splits)
            alive = state.allocated & (status != STATUS_DELETED)
            sat = (jnp.sum(jnp.where(alive, state.lengths, 0))
                   .astype(jnp.float32) / (M_local * cfg.l_max))
            sat_all = jax.lax.all_gather(sat, "model")          # (S,)
            finite = all_best < BIG / 2
            vmin = jnp.min(jnp.where(finite, all_best, BIG), axis=0)
            vmax = jnp.max(jnp.where(finite, all_best, -BIG), axis=0)
            rng_j = jnp.maximum(vmax - vmin, 0.0)
            all_best = jnp.where(
                finite,
                all_best + route_alpha * sat_all[:, None] * rng_j[None, :],
                all_best)
        owner = ref.exact_argmin(all_best, axis=0)
        mine = valid & (owner == my) & (best_local < BIG / 2)
        # routed GLOBAL pid per job (one-hot psum: exactly one shard is
        # the argmin owner) — the cache-target hint for parked jobs
        claim = (owner == my) & (best_local < BIG / 2)
        routed = jax.lax.psum(
            jnp.where(claim, best_pid + my.astype(jnp.int32) * M_local, 0),
            "model")
        routable = jax.lax.psum(claim.astype(jnp.int32), "model") > 0
        routed = jnp.where(valid & routable, routed, -1)
        state, ok, flat_local = batched_append(
            state, cfg, vecs, ids, jnp.where(mine, best_pid, -1), mine,
            update_id_loc=False)
        # id_loc is REPLICATED across model shards: merge the per-job
        # global flat locations (exactly one shard wins each job, so a
        # psum of one-hot contributions keeps the replicas identical).
        won = mine & ok
        flat_global = jnp.where(won, my * (M_local * cfg.capacity)
                                + flat_local, 0)
        flat_global = jax.lax.psum(flat_global, "model")
        any_won = jax.lax.psum(won.astype(jnp.int32), "model") > 0
        safe_ids = jnp.where(valid & any_won, ids, cfg.max_ids)
        id_loc = state.id_loc.at[safe_ids].set(
            flat_global.astype(jnp.int32), mode="drop")
        state = _dc.replace(
            state, id_loc=id_loc,
            global_version=state.global_version + jnp.uint32(1))
        return state, valid & any_won, routed

    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(st_specs, jspec, jspec, jspec),
                       out_specs=(st_specs, P(), P()), check_vma=False)
    return jax.jit(fn, donate_argnums=(0,))


def make_sharded_delete(cfg: UBISConfig, mesh: Mesh):
    """Builds a jitted sharded delete round:
    (state, del_ids, valid) -> (state, done (J,) bool).

    Locations come from the replicated ``id_loc`` map, so routing is
    free: the owner shard (flat location // local pool span) tombstones
    its tiles and decrements its lengths; the cache and ``id_loc``
    updates are computed identically on every shard from replicated
    inputs, so the replicas stay in sync with zero collectives.  The
    tombstone writes themselves are ``update.apply_tombstones`` — ONE
    kernel parameterized by the owner span, shared with the single-device
    ``delete_round`` (base 0) so the two paths cannot drift.  UBIS
    semantics only — the SPFresh lock model lives in ``delete_round``.
    """
    jspec = P()
    st_specs = index_specs(cfg)
    C = cfg.capacity

    def local(state: IndexState, del_ids, valid):
        my = jax.lax.axis_index("model")
        M_local = state.lengths.shape[0]
        base = my.astype(jnp.int32) * (M_local * C)
        safe = jnp.clip(del_ids, 0, cfg.max_ids - 1)
        loc = state.id_loc[safe]
        first = vm.first_occurrence_mask(safe) & valid
        in_post = first & (loc >= 0)
        in_cache = first & (loc <= -2)
        state, done = apply_tombstones(state, cfg, safe, loc, in_post,
                                       in_cache, base=base)
        return state, done

    fn = jax.shard_map(local, mesh=mesh, in_specs=(st_specs, jspec, jspec),
                       out_specs=(st_specs, P()), check_vma=False)
    return jax.jit(fn, donate_argnums=(0,))


def make_sharded_background(cfg: UBISConfig, mesh: Mesh,
                            bg_ops: int = 8, reassign: bool = True,
                            gc_k: int = 64):
    """Builds a jitted sharded background tick:
    (state, gc_min_version) -> (state, executed, reclaimed, pressure).

    ``pressure`` is the (S, 4) int32 per-shard saturation report —
    ``balance.shard_pressure`` rows ``(live_postings, free_slots,
    cache_backlog, live_vectors)`` computed AFTER the structural batch
    and GC.  Each shard writes its own row through the ``P("model")``
    output layout, so the stats ride out of the same program with zero
    added collectives; the host-side ``RebalancePlanner`` reads them to
    pick donor->receiver migrations for ``make_sharded_migrate``.

    The SAME ``balance.background_round`` program runs on every model
    shard over the postings it owns — structural work is shard-local, so
    the whole pod's split/merge/compact batch is one collective-free
    device call.  Per shard: detect -> pick top ``bg_ops`` candidates ->
    mark -> execute, all on device.  Two shard-specific adaptations:

      * the global free stack is meaningless per shard (its slices hold
        arbitrary global ids), so each shard derives a local free view
        from ``allocated`` on entry and the state returns with an EMPTY
        (fail-safe) stack — gather + ``update.rebuild_free_stack`` before
        single-device use;
      * ``id_loc`` is replicated, so each shard's (local-flat) rewrites
        are rebased by its pool offset and merged with one psum — every
        id is owned by exactly one shard, so contributions never collide;
      * successor pointers (``rec_succ``) are stored global, used local:
        localized on entry (cross-shard successors dead-end, the safe
        fallback) and rebased back to global pids on exit.

    The vector cache is replicated and therefore unwritable per shard:
    the round runs with ``use_cache=False`` (small-side spills fold back
    into child ``a`` instead — nothing is dropped).

    Epoch GC rides in the same program: after the structural batch, each
    shard reclaims up to ``gc_k`` of its own retired postings older than
    ``gc_min_version`` (pass 0 to skip).  Structural ownership makes
    this collective-free too; the per-shard successor sweep covers every
    reference the sharded rounds themselves can create (they only link
    same-shard successors).
    """
    st_specs = index_specs(cfg)
    C = cfg.capacity

    def local(state: IndexState, gc_min_version):
        my = jax.lax.axis_index("model")
        M_local = state.allocated.shape[0]
        base_pid = my.astype(jnp.int32) * M_local
        # local free view: unallocated local pids, stack top at the end
        state = rebuild_free_stack(state)
        # successor pointers are stored as GLOBAL pids; the local round
        # reads/writes local ones.  Localize on entry (cross-shard
        # successors become -1: the round treats them as absent, the
        # designed-safe dead-end) and on exit rebase only the words the
        # round actually rewrote — untouched postings keep their
        # original global words, cross-shard pointers included.
        old_succ_global = state.rec_succ
        succ_local0 = _rebase_succ(old_succ_global, -base_pid, M_local)
        state = dataclasses_replace(state, rec_succ=succ_local0)
        old_id_loc = state.id_loc

        kinds, pids = balance.select_candidates(state, cfg, bg_ops)
        # mark + execute in one program: atomic within this device call,
        # so the two-phase window collapses without a race window
        state = dataclasses_replace(
            state, rec_meta=balance.mark_selected(state.rec_meta, kinds,
                                                  pids))
        state, rr = balance.background_round(
            state, cfg, kinds, pids, reassign=reassign, use_cache=False)
        # epoch GC on the shard's own retired postings, same device call
        state, n_gc = balance.gc_round(state, cfg, gc_min_version, gc_k)

        # merge the replicated id map: rebase local tile flats to global
        base = my.astype(jnp.int32) * (M_local * C)
        changed = state.id_loc != old_id_loc
        rebased = jnp.where(changed & (state.id_loc >= 0),
                            state.id_loc + base, state.id_loc)
        delta = jnp.where(changed, rebased - old_id_loc, 0)
        id_loc = old_id_loc + jax.lax.psum(delta, "model")
        # free stack on exit: per-shard local views cannot form one
        # canonical global stack, so return it fail-safe EMPTY — any
        # consumer that pops from it gets nothing instead of an aliased
        # live posting.  Each bg call re-derives its local view from
        # ``allocated``; a gathered single-device state must pass
        # through update.ensure_free_stack (the ShardedUBISDriver
        # snapshot path enforces this) before driver/alloc/GC use.
        succ_changed = state.rec_succ != succ_local0
        rec_succ = jnp.where(
            succ_changed,
            _rebase_succ(state.rec_succ, base_pid, cfg.max_postings),
            old_succ_global)
        state = dataclasses_replace(
            state, id_loc=id_loc, free_top=jnp.int32(0), rec_succ=rec_succ,
            global_version=jax.lax.pmax(state.global_version, "model"))
        executed = jax.lax.psum(rr.executed, "model")
        reclaimed = jax.lax.psum(jnp.asarray(n_gc, jnp.int32), "model")
        # per-shard pressure row (pure local math; the P("model") output
        # layout stacks the rows — no collective)
        pressure = balance.shard_pressure(state, cfg, base_pid=base_pid)
        return state, executed, reclaimed, pressure[None]

    fn = jax.shard_map(local, mesh=mesh, in_specs=(st_specs, P()),
                       out_specs=(st_specs, P(), P(), P("model")),
                       check_vma=False)
    return jax.jit(fn)


def make_sharded_migrate(cfg: UBISConfig, mesh: Mesh, jobs: int = 8):
    """Builds a jitted cross-shard posting migration round:
    (state, src_pids (B,), dst_shards (B,), valid (B,)) ->
    (state, migrated (B,) bool, new_pids (B,) int32).

    ``new_pids`` is the landing GLOBAL pid per job (-1 when the job did
    not move) — the cold-tier driver uses it to remap its host-pool
    entries: a **spilled** posting migrates WITHOUT being promoted (its
    zeroed device tile, codes, heat and ``tier_spilled`` flag all travel
    verbatim; only the host-side pool key changes).

    The rebalance data plane (the paper's "imbalanced distribution"
    countermeasure lifted to the pod level): a saturated shard's hot
    sub-pool hands whole postings to shards with free capacity, picked
    host-side by ``api.rebalance.RebalancePlanner`` from the pressure
    stats the background round reports.  One round, three phases:

      * **extraction** — the owner shard gathers each migrating tile
        (vectors, ids, slot validity, lengths, centroid, PQ codes +
        pinned codebook slot) and replicates it with a one-hot psum
        (exactly one shard contributes per job, the same discipline as
        the insert round's id-map merge).  Only postings that are
        allocated + NORMAL move — a posting the background round marked
        or retired in the meantime is silently skipped.  The neighbour
        row is NOT carried: its pids are shard-local (the sharded
        background rounds write local ids), so on the receiver they
        would alias unrelated postings — the landed posting starts with
        an empty row, like the NO_SUCC treatment of its recorder word;
      * **installation** — the receiver shard admits jobs through the
        same sequential free-stack grant scan the background round uses
        (jobs granted in batch order while local slots last), writes the
        tile verbatim into the popped slot (no repacking: PQ codes stay
        byte-identical to their pinned-slot encode), and claims the
        recorder word at the round's version;
      * **hand-off** — the donor retires its copy (DELETED at this
        version, NO successors: ``id_loc`` is repointed in this same
        program, and cross-shard successor pointers would break the
        per-shard GC sweep's locality contract), and every shard
        computes the identical ``id_loc`` rewrite from the replicated
        payload — the ``make_sharded_delete`` replica discipline, so the
        id map needs no extra merge.

    Tiles move through psums sized (B, C, d) etc. with B = ``jobs`` —
    a few postings per tick, independent of pool size.  The free stack
    returns fail-safe EMPTY per the sharded-state contract.
    """
    st_specs = index_specs(cfg)
    C = cfg.capacity

    def local(state: IndexState, src_pids, dst_shards, valid):
        my = jax.lax.axis_index("model").astype(jnp.int32)
        n_shard = jax.lax.axis_size("model")
        M_local = state.lengths.shape[0]
        base_pid = my * M_local
        ver = state.global_version + jnp.uint32(1)
        B = src_pids.shape[0]
        src_pids = jnp.asarray(src_pids, jnp.int32)
        dst_shards = jnp.asarray(dst_shards, jnp.int32)

        # local free view (same entry discipline as the background round)
        state = rebuild_free_stack(state)

        # replicated job sanity: in-range, deduped, actually cross-shard
        src_shard = src_pids // M_local
        job_ok = (valid & (src_pids >= 0)
                  & (src_pids < n_shard * M_local)
                  & vm.first_occurrence_mask(src_pids)
                  & (dst_shards >= 0) & (dst_shards < n_shard)
                  & (dst_shards != src_shard))

        # ---- donor extraction: one-hot psum replicates each payload ---
        src_local = src_pids - base_pid
        sl = jnp.clip(src_local, 0, M_local - 1)
        status = vm.unpack_status(state.rec_meta)
        donate = (job_ok & (src_local >= 0) & (src_local < M_local)
                  & state.allocated[sl] & (status[sl] == STATUS_NORMAL))

        def rep(x, mask):
            contrib = jnp.where(mask.reshape((B,) + (1,) * (x.ndim - 1)),
                                x, jnp.zeros((), x.dtype))
            return jax.lax.psum(contrib, "model")

        vec_b = rep(state.vectors[sl], donate)
        ids_b = rep(state.ids[sl], donate)
        sv_b = rep(state.slot_valid[sl].astype(jnp.int32), donate) > 0
        used_b = rep(state.used[sl], donate)
        len_b = rep(state.lengths[sl], donate)
        cent_b = rep(state.centroids[sl], donate)
        codes_b = rep(state.codes[sl].astype(jnp.int32),
                      donate).astype(jnp.uint8)
        pslot_b = rep(state.pq_posting_slot[sl], donate)
        heat_b = rep(state.heat[sl].astype(jnp.int32),
                     donate).astype(jnp.uint32)
        sp_b = rep(state.tier_spilled[sl].astype(jnp.int32), donate) > 0
        movable = jax.lax.psum(donate.astype(jnp.int32), "model") > 0

        # ---- receiver admission: sequential free-stack grant scan -----
        want = movable & (dst_shards == my)

        def grant_step(off, w):
            g = w & (off < state.free_top)
            return off + g.astype(jnp.int32), (g, off)

        _, (grant_l, starts) = jax.lax.scan(grant_step, jnp.int32(0), want)
        idx = state.free_top - 1 - starts
        new_local = jnp.where(
            grant_l, state.free_list[jnp.clip(idx, 0, M_local - 1)], -1)
        # replicate the landing pid (one-hot psum from the receiver)
        new_global = jax.lax.psum(
            jnp.where(grant_l, new_local + base_pid, 0), "model")
        migrated = jax.lax.psum(grant_l.astype(jnp.int32), "model") > 0
        new_global = jnp.where(migrated, new_global, -1)

        # ---- install on the receiver ----------------------------------
        tgt = oob(new_local, grant_l, M_local)
        vectors = state.vectors.at[tgt].set(vec_b, mode="drop")
        ids_arr = state.ids.at[tgt].set(ids_b, mode="drop")
        slot_valid = state.slot_valid.at[tgt].set(sv_b, mode="drop")
        used = state.used.at[tgt].set(used_b, mode="drop")
        lengths = state.lengths.at[tgt].set(len_b, mode="drop")
        centroids = state.centroids.at[tgt].set(cent_b, mode="drop")
        # fresh empty neighbour row: the donor's row holds shard-LOCAL
        # pids, meaningless (aliasing) in the receiver's pool
        nbrs = state.nbrs.at[tgt].set(
            jnp.full((B, state.nbrs.shape[1]), -1, jnp.int32),
            mode="drop")
        codes = state.codes.at[tgt].set(codes_b, mode="drop")
        pq_posting_slot = state.pq_posting_slot.at[tgt].set(pslot_b,
                                                            mode="drop")
        # tier residency travels with the posting (no promotion: a
        # spilled posting lands spilled, its pool entry is remapped
        # host-side by the driver via ``new_pids``)
        heat = state.heat.at[tgt].set(heat_b, mode="drop")
        tier_spilled = state.tier_spilled.at[tgt].set(sp_b, mode="drop")
        rec_meta = state.rec_meta.at[tgt].set(
            vm.pack_meta(jnp.uint32(STATUS_NORMAL), ver), mode="drop")
        rec_succ = state.rec_succ.at[tgt].set(
            jnp.uint32((NO_SUCC << 16) | NO_SUCC), mode="drop")
        allocated = state.allocated.at[tgt].set(True, mode="drop")

        # ---- donor retirement (no successors: id_loc is already new) --
        retire = donate & migrated
        rec_meta = vm.transition(rec_meta, jnp.where(retire, sl, -1),
                                 STATUS_DELETED,
                                 jnp.broadcast_to(ver, (B,)))
        rec_succ = vm.set_successors(rec_succ, jnp.where(retire, sl, -1),
                                     jnp.full((B,), -1, jnp.int32),
                                     jnp.full((B,), -1, jnp.int32))
        # the retired donor copy is no longer host-resident anywhere
        tier_spilled = tier_spilled.at[oob(sl, retire, M_local)].set(
            False, mode="drop")

        # ---- replicated id map: identical rewrite on every shard ------
        ids_flat = ids_b.reshape(B * C)
        live_flat = ((sv_b & migrated[:, None]).reshape(B * C)
                     & (ids_flat >= 0))
        new_flat = (new_global[:, None] * C
                    + jnp.arange(C, dtype=jnp.int32)[None, :]).reshape(-1)
        id_loc = state.id_loc.at[
            oob(jnp.clip(ids_flat, 0, cfg.max_ids - 1), live_flat,
                cfg.max_ids)].set(new_flat, mode="drop")

        state = dataclasses_replace(
            state, vectors=vectors, ids=ids_arr, slot_valid=slot_valid,
            used=used, lengths=lengths, centroids=centroids, nbrs=nbrs,
            codes=codes, pq_posting_slot=pq_posting_slot,
            heat=heat, tier_spilled=tier_spilled,
            rec_meta=rec_meta, rec_succ=rec_succ, allocated=allocated,
            id_loc=id_loc, free_top=jnp.int32(0),  # fail-safe EMPTY
            global_version=ver)
        return state, migrated, new_global

    fn = jax.shard_map(local, mesh=mesh, in_specs=(st_specs, P(), P(), P()),
                       out_specs=(st_specs, P(), P()), check_vma=False)
    jfn = jax.jit(fn, donate_argnums=(0,))

    def checked(state, src_pids, dst_shards, valid):
        # the batch width is baked into the compiled program; a caller
        # passing a different width would silently recompile per shape
        if src_pids.shape[0] != jobs:
            raise ValueError(f"migrate round built for jobs={jobs}, "
                             f"got batch of {src_pids.shape[0]}")
        return jfn(state, src_pids, dst_shards, valid)

    return checked


def make_sharded_retrain(cfg: UBISConfig, mesh: Mesh):
    """(state, key) -> state: ``pq.retrain_round`` for a model-sharded
    state.  Drawing the sample and installing the codebooks (gathers,
    XLA encode) are plain jit programs that GSPMD partitions over the
    state's shardings; the codebook training runs replicated under
    ``shard_map``, since a Pallas kernel (``kmeans_assign``) cannot be
    partitioned automatically.  Same steps, same result as the
    single-device round."""
    from ..quant import pq

    def train(sample, smask, codebooks, active):
        return pq.train_codebooks(sample, smask, codebooks[active],
                                  cfg.kmeans_iters, backend=cfg.use_pallas)

    sample_fn = jax.jit(pq.retrain_sample, static_argnums=1)
    train_fn = jax.jit(jax.shard_map(
        train, mesh=mesh, in_specs=(P(), P(), P(), P()), out_specs=P(),
        check_vma=False))
    install_fn = jax.jit(pq.install_codebooks, static_argnums=1)

    def run(state, key):
        sample, smask = sample_fn(state, cfg, key)
        new_cb = train_fn(sample, smask, state.pq_codebooks,
                          state.pq_active)
        return install_fn(state, cfg, new_cb)

    return run


def make_sharded_exact(cfg: UBISConfig, mesh: Mesh, k: int):
    """Builds a jitted exact top-k oracle over the sharded live contents:
    (state, queries) -> (ids, scores) — the ``shard_map``'d form of
    ``search.brute_force``.

    Each shard brute-force scans the posting slots it owns (full
    slot-validity + visibility masking) plus its 1/S slice of the
    replicated vector cache, takes a local top-k FROM ITS OWN id rows
    (no take-along-axis on a replicated row under GSPMD — the
    partial-sum id-scaling trap this replaces), and one all-gather +
    merge produces the global result.  Queries are replicated: the
    oracle is eval-only, so data-axis padding buys nothing.
    """
    st_specs = index_specs(cfg)

    def local(state: IndexState, queries):
        n_shard = jax.lax.axis_size("model")
        my = jax.lax.axis_index("model")
        queries = queries.astype(jnp.float32)
        vis = vm.visible(state.rec_meta, state.allocated,
                         state.global_version)
        # spilled postings excluded (device tiles zeroed) — the tiered
        # driver merges a host-pool scan on top, same as single-device
        valid = state.slot_valid & (vis & ~state.tier_spilled)[:, None]
        s = ops.posting_scan(queries, state.vectors, valid,
                             backend=cfg.use_pallas)          # (Q, M_local*C)
        ids_row = state.ids.reshape(-1)
        # cache slice: the same ownership split as the sharded search
        cvs, cval_own, cid = _owned_cache_slice(state, my, n_shard)
        cs = ops.centroid_score(queries, cvs, cval_own,
                                backend=cfg.use_pallas)
        scores = jnp.concatenate([s, cs], axis=1)
        flat = jnp.concatenate([ids_row, cid])
        ids2d = jnp.broadcast_to(flat[None, :],
                                 (queries.shape[0], flat.shape[0]))
        kl = min(k, scores.shape[1])
        s_loc, i_loc = _local_topk(scores, ids2d, kl)
        s_all = jax.lax.all_gather(s_loc, "model", axis=1, tiled=True)
        i_all = jax.lax.all_gather(i_loc, "model", axis=1, tiled=True)
        sf, idf = _local_topk(s_all, i_all, k)
        return jnp.where(sf < BIG / 2, idf, -1), sf

    fn = jax.shard_map(local, mesh=mesh, in_specs=(st_specs, P()),
                       out_specs=(P(), P()), check_vma=False)
    return jax.jit(fn)
