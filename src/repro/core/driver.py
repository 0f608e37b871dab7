"""Host-side orchestration: job queues + the background scheduler.

The paper's runtime is a foreground thread feeding a job queue and
background threads executing split/merge/reassign.  Here the *data
plane* is entirely jitted device code (update.py / balance.py /
search.py); this module is the *control plane*: it sequences rounds,
implements the two-phase SPLITTING/MERGING window, drains the vector
cache, garbage-collects retired postings, and carries the accounting
(TPS/QPS/recall inputs) the benchmarks read.

Mode differences (cfg.mode):
  * ``ubis``     — periodic balance-detector scan (relaxed restrictions),
                   vector cache for blocked jobs, balanced splits.
  * ``spfresh``  — strict triggers only (split on insert overflow, merge
                   on search touching a small posting), posting-lock
                   rejection of blocked jobs, unconditional 2-means splits.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..api.types import SearchResult, TickReport, UpdateResult
from ..kernels import ops
from ..obs import Obs
from . import balance, search as search_mod, tier as tier_mod, update
from .build import initial_state
from .types import (KIND_COMPACT, KIND_MERGE, KIND_SPLIT, IndexState,
                    UBISConfig)

KIND_CODES = {"split": KIND_SPLIT, "merge": KIND_MERGE,
              "compact": KIND_COMPACT}


@dataclasses.dataclass
class SearchDispatch:
    """An in-flight search: the jitted program has been LAUNCHED but not
    awaited.  ``collect_search`` blocks on the device values, runs the
    host tier rerank against the state *captured at dispatch* (the
    result answers for the index as of dispatch time, even if a tick ran
    in between), and returns the ``SearchResult``.

    This is the overlap seam the serving engine uses: dispatch a search
    batch, run an insert round or a background tick while the device
    works, then collect.
    """

    state: Any                       # IndexState captured at dispatch
    queries: np.ndarray              # host copy, for the tier rerank
    k: int
    found: Any                       # device (Q, k_eff) int32
    scores: Any                      # device (Q, k_eff) f32
    probe: Any                       # device probed pids
    t0: float


class UBISDriver:
    """Streaming driver for one index instance (a ``StreamingIndex``).

    ``fused_tick=True`` (UBIS mode only) moves background candidate
    selection on device: ``balance.mark_round`` replaces the
    ``detect()`` host round-trip — the kinds/pids batch stays on device
    and feeds the next tick's ``background_round`` directly, exactly as
    the sharded round already selects.  SPFresh's strict triggers are
    host-noted by construction, so the flag is ignored in that mode.
    """

    def __init__(self, cfg: UBISConfig, seed_vectors=None, *,
                 seed: int = 0, round_size: int = 1024,
                 bg_ops_per_round: int = 4, drain_per_tick: int = 256,
                 insert_retries: int = 2, gc_lag: int = 16,
                 reassign_after_split: bool = True,
                 pq_retrain_every: int = 32,
                 fused_tick: bool = False,
                 tier_moves_per_tick: int = 32,
                 tier_rerank_host: bool = True,
                 tier_async: bool = False,
                 obs: Optional[Obs] = None):
        self.cfg = cfg
        self.round_size = int(round_size)
        self.bg_ops = int(bg_ops_per_round)
        self.drain_n = int(drain_per_tick)
        self.retries = int(insert_retries)
        self.gc_lag = int(gc_lag)
        self.reassign_after_split = reassign_after_split
        # observability plane: metrics registry + structured tracer; the
        # stats mapping below is a schema-seeded facade registered with
        # it, so every engine exposes the same key set
        self.obs = obs if obs is not None else Obs()
        ops.observe_fallbacks(self.obs)
        # quant plane: codebook re-train cadence in ticks (0 = never);
        # only meaningful with cfg.use_pq
        self.pq_retrain_every = int(pq_retrain_every)
        self.fused_tick = bool(fused_tick) and cfg.is_ubis
        # cold-tier plane (cfg.use_tier): pinned host pool + planner
        self.tier = (tier_mod.TierManager(
            cfg, max_moves=int(tier_moves_per_tick),
            rerank_host=tier_rerank_host, obs=self.obs)
            if cfg.use_tier else None)
        # tier_async: dispatch the tick's spill/promote DMA at tick
        # START (overlapping the background round) and reconcile at tick
        # end, instead of the synchronous plan+move at tick end
        self.tier_async = bool(tier_async)
        self._bg_ran = False
        self._ticks = 0
        self._pq_key = jax.random.key(seed + 0x517C0DE)

        if seed_vectors is None:
            raise ValueError("seed_vectors required (used for k-means seeds)")
        self.state: IndexState = initial_state(
            cfg, jnp.asarray(seed_vectors), key=jax.random.key(seed))
        # ops marked SPLITTING/MERGING last tick, executed this tick
        self._marked: list[tuple[str, int]] = []
        self._marked_set: set[int] = set()
        # fused_tick: device-resident (kinds, pids) marked last tick
        self._marked_dev = None
        # SPFresh strict-trigger candidate sets
        self._sp_split: set[int] = set()
        self._sp_merge: set[int] = set()
        self.stats = self.obs.driver_stats()

    # ------------------------------------------------------------------
    # foreground
    # ------------------------------------------------------------------

    def insert(self, vecs, ids, *, tick_between: bool = True) -> UpdateResult:
        """Stream (vecs, ids) through padded insert rounds.

        Rejected jobs (SPFresh lock model / full cache) are retried up to
        ``insert_retries`` times with a background tick in between —
        mirroring the paper's blocked-then-retried updates; every retry
        costs wall time, which is how contention degrades TPS.
        """
        vecs = np.asarray(vecs, np.float32)
        ids = np.asarray(ids, np.int64).astype(np.int32)
        if len(vecs) != len(ids):
            raise ValueError(f"vecs/ids length mismatch: {len(vecs)} vs "
                             f"{len(ids)}")
        if ids.size and (ids.min() < 0 or ids.max() >= self.cfg.max_ids):
            raise ValueError("ids out of range for cfg.max_ids")
        n_acc = n_cache = n_rej = 0
        J = self.round_size
        # the round's target ids are read only where something notes them
        want_target = self.tier is not None or not self.cfg.is_ubis
        with self.obs.span("insert") as sp:
            pending = (vecs, ids, np.full(ids.shape, -1, np.int32))
            for attempt in range(self.retries + 1):
                pv, pi, ph = pending
                rej_v, rej_i, rej_h = [], [], []
                for off in range(0, len(pi), J):
                    cv, ci, ch = (pv[off:off + J], pi[off:off + J],
                                  ph[off:off + J])
                    pad = J - len(ci)
                    valid = np.concatenate([np.ones(len(ci), bool),
                                            np.zeros(pad, bool)])
                    cv = np.concatenate([cv, np.zeros((pad, self.cfg.dim),
                                                      np.float32)])
                    ci = np.concatenate([ci, np.zeros(pad, np.int32)])
                    ch = np.concatenate([ch, np.full(pad, -1, np.int32)])
                    self.state, res, _touched = update.insert_round(
                        self.state, self.cfg, jnp.asarray(cv),
                        jnp.asarray(ci), jnp.asarray(valid), jnp.asarray(ch))
                    acc, cac, rej, *tgt = self.obs.fetch(
                        "insert", res.accepted, res.cached, res.rejected,
                        *([res.target] if want_target else []))
                    n_acc += int(acc.sum())
                    n_cache += int(cac.sum())
                    if self.tier is not None:   # appends heat their target
                        self.tier.note_targets(tgt[0][acc])
                    if rej.any():
                        rej_v.append(cv[rej])
                        rej_i.append(ci[rej])
                        rej_h.append(np.full(int(rej.sum()), -1, np.int32))
                    if not self.cfg.is_ubis:
                        self._note_spfresh_overflow(tgt[0][acc])
                if not rej_v:
                    pending = None
                    break
                pending = (np.concatenate(rej_v), np.concatenate(rej_i),
                           np.concatenate(rej_h))
                if tick_between:
                    self.tick()
            if pending is not None:
                n_rej = len(pending[1])
            with self.obs.span("sync.insert"):
                jax.block_until_ready(self.state.lengths)
        dt = sp.seconds
        self.stats["insert_time"] += dt
        self.stats["inserted"] += n_acc + n_cache
        self.stats["rejected"] += n_rej
        self.obs.emit("insert", accepted=n_acc, cached=n_cache,
                      rejected=n_rej, seconds=round(dt, 6))
        return UpdateResult(accepted=n_acc, cached=n_cache, rejected=n_rej,
                            seconds=dt)

    def delete(self, ids) -> UpdateResult:
        ids = np.asarray(ids, np.int64).astype(np.int32)
        J = self.round_size
        n_done = n_blocked = 0
        with self.obs.span("delete") as sp:
            for off in range(0, len(ids), J):
                ci = ids[off:off + J]
                pad = J - len(ci)
                valid = np.concatenate([np.ones(len(ci), bool),
                                        np.zeros(pad, bool)])
                ci = np.concatenate([ci, np.zeros(pad, np.int32)])
                self.state, done, blocked = update.delete_round(
                    self.state, self.cfg, jnp.asarray(ci), jnp.asarray(valid))
                done, blocked = self.obs.fetch("delete", done, blocked)
                n_done += int(done.sum())
                n_blocked += int(blocked.sum())
            with self.obs.span("sync.delete"):
                jax.block_until_ready(self.state.lengths)
        dt = sp.seconds
        self.stats["delete_time"] += dt
        self.stats["deleted"] += n_done
        self.stats["blocked"] += n_blocked
        self.obs.emit("delete", deleted=n_done, blocked=n_blocked,
                      seconds=round(dt, 6))
        return UpdateResult(deleted=n_done, blocked=n_blocked, seconds=dt)

    def search(self, queries, k: int,
               nprobe: Optional[int] = None) -> SearchResult:
        return self.collect_search(self.dispatch_search(queries, k, nprobe))

    def dispatch_search(self, queries, k: int,
                        nprobe: Optional[int] = None) -> SearchDispatch:
        """Launch the jitted search WITHOUT waiting for it (JAX async
        dispatch: the call returns as soon as the program is enqueued).
        The serving engine overlaps inserts/ticks here; pair with
        ``collect_search``."""
        with self.obs.span("search.dispatch"):
            queries = np.asarray(queries, np.float32)
            t0 = time.perf_counter()
            # host rerank widens the final candidate set to rerank_k
            # (the device top-k orders spilled candidates by ADC score,
            # so the exact host pass must see the full rerank budget to
            # matter — cutting this below rerank_k measurably costs
            # recall on a mostly-cold index)
            k_eff = (max(k, self.cfg.rerank_k)
                     if self.tier is not None and self.tier.rerank_host
                     else k)
            # per-dispatch fallback accounting: the signature carries
            # every routing decision (backend knob + plane shape); the
            # query batch size is deliberately omitted — re-traces of the
            # same signature route identically (see
            # ops.count_fallback_dispatches)
            sig = ("ubis-search", self.cfg.use_pallas, self.cfg.dim,
                   self.cfg.capacity, self.cfg.use_pq, self.cfg.pq_ksub)
            with ops.count_fallback_dispatches(self.obs, sig):
                found, scores, probe = search_mod.search(
                    self.state, self.cfg, jnp.asarray(queries), k_eff,
                    nprobe)
            return SearchDispatch(state=self.state, queries=queries, k=k,
                                  found=found, scores=scores, probe=probe,
                                  t0=t0)

    def collect_search(self, disp: SearchDispatch) -> SearchResult:
        """Await a dispatched search and finish the host-side tail
        (heat notes, tier rerank, stats) against the dispatch-time
        state."""
        with self.obs.span("search.collect"):
            found, scores, probe = self.obs.fetch("search", disp.found,
                                                  disp.scores, disp.probe)
            if self.tier is not None:
                # probes are the search-heat signal (promote trigger),
                # and spilled candidates in the final candidate set get
                # their true distance from the pinned pool (optional host
                # rerank)
                self.tier.note_probes(probe)
                found, scores = self.tier.rerank(disp.state, disp.queries,
                                                 found, scores)
                found, scores = found[:, :disp.k], scores[:, :disp.k]
            dt = time.perf_counter() - disp.t0
            self.stats["search_time"] += dt
            self.stats["queries"] += disp.queries.shape[0]
            # search introspection, piggybacked on arrays the result path
            # already transferred (no added device syncs)
            self.stats["search_probed"] += int((probe >= 0).sum())
            self.stats["search_results"] += int((found >= 0).sum())
            if self.cfg.use_pq:
                self.stats["search_adc_batches"] += 1
            else:
                self.stats["search_exact_batches"] += 1
            if not self.cfg.is_ubis:
                self._note_spfresh_small(probe)
            return SearchResult(ids=found, scores=scores, seconds=dt)

    # ------------------------------------------------------------------
    # background
    # ------------------------------------------------------------------

    def tick(self) -> TickReport:
        """One background round: execute marked ops, drain the cache,
        detect + mark new candidates, GC, (quant plane) re-train the PQ
        codebooks on cadence, and (cold tier) run the spill/promote
        planner.  Each phase runs in its own ``tick.*`` span."""
        span = self.obs.span
        drained = retrained = spilled = promoted = 0
        with span("tick") as sp:
            with span("tick.execute") as sp_exec:
                plan = None
                if self.tier is not None and self.tier_async:
                    # tick-start dispatch: the spill tiles' D2H copy and
                    # the promote tiles' H2D staging run while the
                    # background round executes below; reconcile
                    # validates + commits at tick end.  Whether the round
                    # will carry the decay is known now — the marked
                    # batch was chosen LAST tick.
                    will_decay = (self._marked_dev is not None
                                  if self.fused_tick else bool(self._marked))
                    self.state, plan = self.tier.dispatch(
                        self.state, decayed=will_decay)
                executed = self._execute_marked()
            self.stats["bg_exec_time"] += sp_exec.seconds
            if self.cfg.is_ubis:
                with span("tick.drain"):
                    drained = self._drain_cache()
            with span("tick.mark"):
                marked = self._mark_candidates()
            with span("tick.gc"):
                reclaimed = self._gc()
            if self._pq_retrain_due():
                with span("tick.retrain"):
                    retrained = self._pq_retrain()
            if self.tier is not None:
                with span("tick.tier"):
                    spilled, promoted = self._tier_step(plan)
        dt = sp.seconds
        self.stats["bg_time"] += dt
        self.stats["bg_ops"] += executed
        self.stats["bg_gc"] += reclaimed
        self.stats["drained"] += drained
        self.obs.emit("tick", executed=executed, drained=drained,
                      marked=marked, gc=reclaimed, pq=retrained,
                      spilled=spilled, promoted=promoted,
                      seconds=round(dt, 6))
        return TickReport(executed=executed, drained=drained,
                          marked=marked, gc=reclaimed,
                          pq_retrained=retrained, spilled=spilled,
                          promoted=promoted, seconds=dt)

    def flush(self, max_ticks: int = 200) -> int:
        """Tick until quiescent (no marked ops, no due candidates, cache
        empty, no tier moves in flight — a forced promotion must get its
        follow-up structural op before flush returns).  Returns number
        of ticks."""
        for i in range(max_ticks):
            r = self.tick()
            cache_n = int(jnp.sum(self.state.cache_valid))
            if (r.executed == 0 and r.marked == 0
                    and r.spilled == 0 and r.promoted == 0
                    and (cache_n == 0 or not self.cfg.is_ubis)):
                return i + 1
        return max_ticks

    # ------------------------------------------------------------------

    def _execute_marked(self) -> int:
        """Execute the whole marked batch as ONE jitted background round.

        No per-op host reads: status/length/free-slot checks, slot
        budgeting and conflict resolution all happen on device; the only
        transfer is the small ``BackgroundRound`` counter struct.
        """
        self._bg_ran = False
        if self.fused_tick:
            md, self._marked_dev = self._marked_dev, None
            if md is None:
                return 0
            kinds, pids = md
        else:
            marked, self._marked = self._marked, []
            self._marked_set.clear()
            if not marked:
                return 0
            # every marked op MUST ride in this batch: truncating would
            # leave its SPLITTING/MERGING mark set with nothing queued to
            # clear it (the detector only re-marks NORMAL postings ->
            # wedged forever)
            B = max(self.bg_ops, len(marked), 1)
            kinds_np = np.zeros(B, np.int32)
            pids_np = np.full(B, -1, np.int32)
            for i, (kind, pid) in enumerate(marked):
                kinds_np[i] = KIND_CODES[kind]
                pids_np[i] = pid
            kinds, pids = jnp.asarray(kinds_np), jnp.asarray(pids_np)
        self.state, rr = balance.background_round(
            self.state, self.cfg, kinds, pids,
            reassign=self.reassign_after_split)
        self._bg_ran = True        # the round carried the heat decay
        rr = self.obs.fetch("round", rr)
        self.stats["bg_split"] += int(rr.n_split)
        self.stats["bg_merge"] += int(rr.n_merge)
        self.stats["bg_compact"] += int(rr.n_compact)
        self.stats["bg_deferred"] += int(rr.deferred)
        self.stats["bg_reassigned"] += int(rr.reassigned)
        self.obs.emit("bg_exec", split=int(rr.n_split),
                      merge=int(rr.n_merge), compact=int(rr.n_compact),
                      deferred=int(rr.deferred),
                      reassigned=int(rr.reassigned),
                      executed=int(rr.executed))
        return int(rr.executed)

    def _drain_cache(self) -> int:
        cache_n = int(self.obs.fetch("drain", jnp.sum(self.state.cache_valid)))
        if cache_n == 0:
            return 0
        n = min(self.drain_n, self.round_size)
        self.state, vecs, ids, targets, taken = update.cache_take(
            self.state, self.cfg, n)
        pad = self.round_size - n
        vecs = jnp.pad(vecs, ((0, pad), (0, 0)))
        ids = jnp.pad(ids, (0, pad))
        targets = jnp.pad(targets, (0, pad), constant_values=-1)
        taken = jnp.pad(taken, (0, pad))
        self.state, res, _ = update.insert_round(
            self.state, self.cfg, vecs, ids, taken, targets)
        return int(self.obs.fetch("drain", jnp.sum(res.accepted)))

    def _mark_candidates(self) -> int:
        from .types import STATUS_MERGING, STATUS_SPLITTING
        if self.fused_tick:
            # device-side selection + mark (one program, no detect()
            # host round-trip); the kinds/pids batch never leaves the
            # device — only the scalar count does, for flush quiescence
            self.state, kinds, pids, n = balance.mark_round(
                self.state, self.cfg, self.bg_ops)
            n = int(self.obs.fetch("detect", n))
            self._marked_dev = (kinds, pids) if n else None
            if n:
                # pids stay on device by design — only the count leaves
                self.obs.emit("bg_mark", reason="fused-device-round",
                              marked=n)
            return n
        if self.cfg.is_ubis:
            split_due, merge_due, compact_due, lengths = self.obs.fetch(
                "detect", *balance.detect(self.state, self.cfg),
                self.state.lengths)
            split_pids = np.flatnonzero(split_due)
            split_pids = split_pids[np.argsort(-lengths[split_pids])]
            merge_pids = np.flatnonzero(merge_due)
            merge_pids = merge_pids[np.argsort(lengths[merge_pids])]
            compact_pids = np.flatnonzero(compact_due)
        else:
            from . import version_manager as vm_
            # candidates were noted at search/insert time; a posting may
            # have been retired since — marking a DELETED posting would
            # RESURRECT its stale tile (duplicate vectors), so require
            # NORMAL status now (found by the invariant property test)
            lengths, alloc, status = self.obs.fetch(
                "detect", self.state.lengths, self.state.allocated,
                vm_.unpack_status(self.state.rec_meta))
            normal = alloc & (status == 0)
            split_pids = np.array(
                [p for p in self._sp_split
                 if normal[p] and lengths[p] > self.cfg.l_max], int)
            merge_pids = np.array(
                [p for p in self._sp_merge
                 if normal[p] and lengths[p] < self.cfg.l_min], int)
            compact_pids = np.array(
                [p for p in self._sp_split
                 if normal[p] and lengths[p] <= self.cfg.l_max], int)
            self._sp_split.clear()
            self._sp_merge.clear()

        jobs = ([("split", int(p)) for p in split_pids]
                + [("compact", int(p)) for p in compact_pids]
                + [("merge", int(p)) for p in merge_pids])
        # one job per posting: a hollowed-out full tile is both
        # compact_due and merge_due — double-marking would leave the
        # second kind's mark with a dead first lane in the batch
        seen = set(self._marked_set)
        deduped = []
        for j in jobs:
            if j[1] not in seen:
                seen.add(j[1])
                deduped.append(j)
        jobs = deduped[:self.bg_ops]
        if not jobs:
            return 0
        split_like = [p for k_, p in jobs if k_ in ("split", "compact")]
        merge_like = [p for k_, p in jobs if k_ == "merge"]
        # pad to the batch width (-1 lanes are dropped): one compiled
        # program per status, not one per batch length
        padded = lambda p: jnp.asarray(p + [-1] * (self.bg_ops - len(p)),
                                       jnp.int32)
        if split_like:
            self.state = update.mark_status(
                self.state, padded(split_like), STATUS_SPLITTING)
        if merge_like:
            self.state = update.mark_status(
                self.state, padded(merge_like), STATUS_MERGING)
        self._marked.extend(jobs)
        self._marked_set.update(p for _, p in jobs)
        self.obs.emit(
            "bg_mark",
            reason=("balance-detector" if self.cfg.is_ubis
                    else "strict-trigger"),
            split=[p for kk, p in jobs if kk == "split"],
            merge=[p for kk, p in jobs if kk == "merge"],
            compact=[p for kk, p in jobs if kk == "compact"])
        return len(jobs)

    def _gc(self) -> int:
        ver = int(self.obs.fetch("gc", self.state.global_version))
        if ver <= self.gc_lag:
            return 0
        self.state, n = balance.gc_round(
            self.state, self.cfg, jnp.uint32(ver - self.gc_lag), 64)
        return int(self.obs.fetch("gc", n))

    def _pq_retrain_due(self) -> bool:
        """Count one tick of the codebook re-train cadence; True when
        this tick re-trains (quant plane only)."""
        if not self.cfg.use_pq or self.pq_retrain_every <= 0:
            return False
        self._ticks += 1
        return self._ticks % self.pq_retrain_every == 0

    def _pq_retrain(self) -> int:
        """Versioned codebook re-train (quant plane)."""
        from ..quant import pq
        self._promote_retrain_pinned()
        evict = (int(self.obs.fetch("retrain", self.state.pq_active)) + 1
                 ) % self.cfg.pq_versions
        self._pq_key, k = jax.random.split(self._pq_key)
        self.state = pq.retrain_round(self.state, self.cfg, k)
        self.stats["pq_retrains"] += 1
        # live codebook generation, for monitors (throughput() readers)
        self.stats["pq_generation"] = int(self.obs.fetch(
            "retrain", self.state.pq_slot_gen[self.state.pq_active]))
        self.obs.emit("pq_retrain", reason="cadence",
                      evicted_slot=evict,
                      generation=int(self.stats["pq_generation"]))
        return 1

    def _promote_retrain_pinned(self) -> None:
        """Cold-tier x quant interplay: promote spilled postings pinned
        to the slot the retrain is about to evict (see
        ``tier.TierManager.promote_retrain_pinned``)."""
        if self.tier is None:
            return
        self.state, n = self.tier.promote_retrain_pinned(self.state)
        self.stats["tier_promoted"] += n

    def _tier_step(self, plan) -> tuple:
        """Cold-tier plane: reconcile the moves dispatched at tick start
        (``tier_async``), or apply accumulated touches, run the
        spill/promote planner and execute the moves."""
        if self.tier_async:
            self.state, n_s, n_p = self.tier.reconcile(self.state, plan)
        else:
            self.state, n_s, n_p = self.tier.tick(self.state,
                                                  decayed=self._bg_ran)
        self.stats["tier_spilled"] += n_s
        self.stats["tier_promoted"] += n_p
        self.stats["tier_resident"] = len(self.tier.pool)
        return n_s, n_p

    def force_spill(self, n: int) -> int:
        """Spill the ``n`` coldest hot postings now (test/benchmark
        hook — the planner's watermark path uses the same machinery)."""
        if self.tier is None:
            return 0
        self.state, moved = self.tier.force_spill(self.state, n)
        self.stats["tier_spilled"] += moved
        self.stats["tier_resident"] = len(self.tier.pool)
        return moved

    def force_promote(self, n=None) -> int:
        """Promote up to ``n`` spilled postings (all when None)."""
        if self.tier is None:
            return 0
        self.state, moved = self.tier.force_promote(self.state, n)
        self.stats["tier_promoted"] += moved
        self.stats["tier_resident"] = len(self.tier.pool)
        return moved

    # ---- SPFresh strict-trigger bookkeeping ---------------------------

    def _note_spfresh_overflow(self, pids: np.ndarray):
        lengths = self.obs.fetch("insert", self.state.lengths)
        for p in np.unique(pids):
            if p >= 0 and lengths[p] > self.cfg.l_max:
                self._sp_split.add(int(p))

    def _note_spfresh_small(self, probe: np.ndarray):
        lengths = self.obs.fetch("search", self.state.lengths)
        small = np.unique(probe[lengths[probe] < self.cfg.l_min])
        for p in small:
            if p >= 0:
                self._sp_merge.add(int(p))

    # ---- StreamingIndex protocol surface ------------------------------

    def snapshot(self) -> IndexState:
        """A single-device-usable state.  With the cold tier on, the
        spilled float tiles are written back into a COPY (flags stay
        set), so the snapshot is self-contained and checkpoint-safe;
        ``load_snapshot`` re-derives residency from the flags."""
        if self.tier is not None:
            return self.tier.snapshot_fill(self.state)
        return self.state

    def load_snapshot(self, state: IndexState) -> "UBISDriver":
        """Adopt a ``snapshot()`` state (possibly restored from a
        checkpoint): with the cold tier on, spilled tiles move back to
        the host pool and their device copies are re-zeroed, so the
        restored index answers search identically to the one that
        snapshotted.  Returns self (chaining convenience)."""
        if self.tier is not None:
            state = self.tier.adopt(state)
        self.state = state
        self._marked, self._marked_dev = [], None
        self._marked_set.clear()
        return self

    def memory_bytes(self) -> int:
        """Total bytes held by the index across BOTH tiers (the untiered
        figure; see ``memory_tiers`` for the device/host split)."""
        from .types import state_memory_bytes
        return state_memory_bytes(self.state)

    def memory_tiers(self) -> dict:
        """Device/host byte split; sums to ``memory_bytes()``."""
        if self.tier is not None:
            return self.tier.memory_tiers(self.state)
        return {"device": self.memory_bytes(), "host": 0}

    def exact(self, queries, k: int) -> SearchResult:
        """Exact top-k over the index's live contents (recall oracle).
        Spilled postings are scanned host-side from the pinned pool and
        merged with the device scan, so the oracle stays exact under
        tiering."""
        queries = np.asarray(queries, np.float32)
        found, scores = search_mod.brute_force(
            self.state, self.cfg, jnp.asarray(queries), k)
        if self.tier is not None:
            found, scores = self.tier.exact_merge(self.state, queries,
                                                  found, scores, k)
        return SearchResult(ids=np.asarray(found),
                            scores=np.asarray(scores))

    def posting_lengths(self) -> np.ndarray:
        from .metrics import live_posting_lengths
        return live_posting_lengths(self.state)

    def shard_pressure(self) -> np.ndarray:
        """The (1, 4) single-pool pressure row — the same
        ``balance.shard_pressure`` signal the sharded background round
        reports per shard, so monitors read one format either way."""
        return np.asarray(balance.shard_pressure(self.state,
                                                 self.cfg))[None]

    def live_count(self) -> int:
        """Vectors in visible postings + the cache (protocol surface)."""
        return int(self.state.live_vector_count()) + int(
            jnp.sum(self.state.cache_valid))

    # ------------------------------------------------------------------

    def throughput(self) -> dict:
        from .metrics import throughput_from_stats
        return throughput_from_stats(self.stats)

    def close(self) -> None:
        """Detach this driver's ``Obs`` bundle from the process-global
        kernel-fallback plane (the sinks are weakly held, so this only
        matters when the caller keeps the bundle alive past the driver —
        test suites and notebooks building many indexes call it, or
        ``ops.reset_fallback_state()`` between builds)."""
        ops.discard_fallback_sink(self.obs)
