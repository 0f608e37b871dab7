"""Host orchestration for the sharded index — the distributed driver.

``ShardedUBISDriver`` presents the *identical* ``StreamingIndex`` API as
the single-device ``UBISDriver``, with every data-plane call dispatched
to the jitted sharded programs (``core/sharded.py``) over a TPU-pod
mesh:

  * **insert** — padded replicated job rounds through
    ``make_sharded_insert``; the per-job accepted mask drives the
    retry-with-a-tick-between loop, and jobs still rejected after the
    retries park in the **host-mediated vector cache** (below);
  * **delete** — ``make_sharded_delete`` rounds (owner-shard tombstones,
    replicated id-map/cache updates, zero collectives);
  * **search** — ``make_sharded_search`` per (k, nprobe), queries padded
    to the data-axis multiple;
  * **tick**  — ONE ``make_sharded_background`` call (per-shard select →
    mark → execute → epoch GC, collective-free, reporting per-shard
    pressure rows), then the **cross-shard rebalance** stage (below),
    then the host cache drain, then the PQ codebook re-train on cadence.

**Cross-shard rebalance.**  Structural ownership makes every background
op shard-local — which is exactly why a skewed stream can saturate one
shard's sub-pool (splits defer until epoch GC frees a local slot,
inserts park in the cache) while cold shards sit on free capacity; with
contiguous pid seeding, a fresh index even starts with EVERY posting on
shard 0.  The tick's pressure rows feed a host-side
``rebalance.RebalancePlanner``; when a shard crosses the saturation
watermark (or the live-vector spread exceeds ``rebalance_ratio``), the
planner picks donor→receiver posting moves and ONE
``make_sharded_migrate`` round executes them (owner extraction,
free-stack-granted installation, replicated id-map rewrite).  The
background program itself stays collective-free — pressure rides out
through the sharded output layout, and migration is its own round.

**Host-mediated vector cache.**  The cache arrays are *replicated*
across model shards, so no shard may write them inside the SPMD
background/insert programs (replica divergence).  The host still OWNS
admission — it decides which jobs park — but executes it as one plain
jitted ``update.cache_append`` round: the program is deterministic over
the replicated arrays, so every replica computes identical bytes and
nothing round-trips through the host (the PR 3 follow-up; admission
used to pull all five cache arrays to numpy and re-replicate them).
Cached entries stay *searchable* — the sharded search's cache scan sees
them — and deletable; each tick drains up to ``drain_per_tick`` of them
back through the sharded insert round.

**Snapshot contract.**  The sharded rounds return the free stack
fail-safe EMPTY; ``snapshot()`` gathers the state and passes it through
``update.ensure_free_stack``, which rebuilds the canonical stack and
*asserts* it (the encoded form of the old sharded.py comment) — a
gathered state that would alias live postings cannot escape.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from ..core import tier as tier_mod, update
from ..core import version_manager as vm
from ..core.build import initial_state
from ..core.driver import SearchDispatch
from ..core.sharded import (index_specs, make_sharded_background,
                            make_sharded_delete, make_sharded_exact,
                            make_sharded_insert, make_sharded_migrate,
                            make_sharded_retrain, make_sharded_search)
from ..core.types import STATUS_NORMAL, IndexState, UBISConfig
from ..kernels import ops
from ..obs import Obs
from .rebalance import RebalancePlanner
from .types import SearchResult, TickReport, UpdateResult


def default_mesh(cfg: UBISConfig) -> Mesh:
    """All local devices on the ``model`` axis (posting-pool sharding),
    falling back toward fewer shards until ``max_postings`` divides."""
    n = len(jax.devices())
    m = n
    while m > 1 and (cfg.max_postings % m or n % m):
        m -= 1
    return jax.make_mesh((n // m, m), ("data", "model"))


class ShardedUBISDriver:
    """Streaming driver over a sharded index (a ``StreamingIndex``)."""

    def __init__(self, cfg: UBISConfig, seed_vectors=None, *,
                 mesh: Optional[Mesh] = None, seed: int = 0,
                 round_size: int = 1024, bg_ops_per_round: int = 8,
                 drain_per_tick: int = 256, insert_retries: int = 2,
                 gc_lag: int = 16, reassign_after_split: bool = True,
                 pq_retrain_every: int = 32,
                 shard_cache_scan: bool = True,
                 rebalance: bool = True,
                 rebalance_watermark: float = 0.85,
                 rebalance_ratio: float = 1.2,
                 migrate_per_tick: int = 8,
                 route_alpha: float = 0.0,
                 tier_moves_per_tick: int = 32,
                 tier_rerank_host: bool = True,
                 tier_async: bool = False,
                 obs: Optional[Obs] = None,
                 obs_profile_dir: Optional[str] = None):
        if not cfg.is_ubis:
            raise ValueError("ShardedUBISDriver is UBIS-mode only "
                             "(SPFresh's lock model is single-device)")
        if seed_vectors is None:
            raise ValueError("seed_vectors required (used for k-means seeds)")
        self.cfg = cfg
        mesh = mesh if mesh is not None else default_mesh(cfg)
        # the sharded programs rely on the compiler to place plain-jit
        # gathers and scatters, i.e. on Auto axes; ``jax.make_mesh``
        # builds Explicit axes by default, so take the mesh as Auto
        self.mesh = mesh.update(
            axis_types=(AxisType.Auto,) * len(mesh.axis_names))
        if cfg.max_postings % self.mesh.shape["model"]:
            raise ValueError("max_postings must divide the model axis")
        self.round_size = int(round_size)
        self.bg_ops = int(bg_ops_per_round)
        self.drain_n = int(drain_per_tick)
        self.retries = int(insert_retries)
        self.gc_lag = int(gc_lag)
        self.pq_retrain_every = int(pq_retrain_every)
        self._ticks = 0
        self._pq_key = jax.random.key(seed + 0x517C0DE)
        # observability plane: shared-schema stats facade + tracer (the
        # same key set as UBISDriver — pinned by tests/test_obs.py)
        self.obs = obs if obs is not None else Obs()
        ops.observe_fallbacks(self.obs)
        self.stats = self.obs.driver_stats()
        self._profile_dir = obs_profile_dir
        self._profiled = False

        specs = index_specs(cfg)
        self._shardings = jax.tree_util.tree_map(
            lambda sp: NamedSharding(self.mesh, sp), specs,
            is_leaf=lambda x: isinstance(x, P))
        self._rep = NamedSharding(self.mesh, P())
        state = initial_state(cfg, jnp.asarray(seed_vectors),
                              key=jax.random.key(seed))
        self.state: IndexState = jax.device_put(state, self._shardings)

        # cold-tier plane (cfg.use_tier): pinned host pool + planner;
        # per-shard accounting rides on contiguous pid blocks
        self.tier = (tier_mod.TierManager(
            cfg, max_moves=int(tier_moves_per_tick),
            rerank_host=tier_rerank_host, obs=self.obs)
            if cfg.use_tier else None)
        # dispatch the tier DMA at tick start, reconcile at tick end
        self.tier_async = bool(tier_async)
        self._insert_fn = make_sharded_insert(cfg, self.mesh,
                                              route_alpha=float(route_alpha))
        # replica-identical jitted cache admission (see module docstring)
        def _admit(state, vecs, ids, targets, want, _cfg=cfg):
            return update.cache_append(state, _cfg, vecs, ids, targets,
                                       want)
        self._cache_admit_fn = jax.jit(_admit)
        self._delete_fn = make_sharded_delete(cfg, self.mesh)
        self._background_fn = make_sharded_background(
            cfg, self.mesh, bg_ops=self.bg_ops,
            reassign=reassign_after_split)
        # cross-shard rebalance: host planner + one jitted migrate round
        self.n_shards = int(self.mesh.shape["model"])
        self.rebalance = bool(rebalance) and self.n_shards > 1
        self._pressure = None
        self.planner = RebalancePlanner(
            self.n_shards, cfg.max_postings // self.n_shards,
            watermark=rebalance_watermark, ratio_target=rebalance_ratio,
            max_moves=int(migrate_per_tick), min_gap=cfg.l_max)
        # built for every multi-shard mesh (compile is lazy), so
        # toggling ``self.rebalance`` after construction — as figskew's
        # on/off comparison does — can never hit a missing attribute
        self._migrate_jobs = int(migrate_per_tick)
        if self.n_shards > 1:
            self._migrate_fn = make_sharded_migrate(
                cfg, self.mesh, jobs=self._migrate_jobs)
        self._shard_cache_scan = shard_cache_scan
        self._search_fns = {}
        self._exact_fns = {}
        self._retrain_fn = None
        # queries shard over the data axes: batches pad to this multiple
        axes = self.mesh.axis_names
        qaxes = ("pod", "data") if "pod" in axes else ("data",)
        self._q_mult = 1
        for a in qaxes:
            self._q_mult *= self.mesh.shape[a]

    # ------------------------------------------------------------------
    # foreground
    # ------------------------------------------------------------------

    def insert(self, vecs, ids, *, tick_between: bool = True) -> UpdateResult:
        """Stream (vecs, ids) through padded sharded insert rounds.

        Rejected jobs retry up to ``insert_retries`` times with a
        background tick in between; survivors park in the host-mediated
        cache (searchable immediately, drained on later ticks) and only
        overflow beyond the cache is reported rejected.
        """
        vecs = np.asarray(vecs, np.float32)
        ids = np.asarray(ids, np.int64).astype(np.int32)
        if len(vecs) != len(ids):
            raise ValueError(f"vecs/ids length mismatch: {len(vecs)} vs "
                             f"{len(ids)}")
        if ids.size and (ids.min() < 0 or ids.max() >= self.cfg.max_ids):
            raise ValueError("ids out of range for cfg.max_ids")
        t0 = time.perf_counter()
        n_acc = 0
        pending, rej_t = (vecs, ids), None
        for attempt in range(self.retries + 1):
            acc, rej_v, rej_i, rej_t = self._insert_rounds(*pending)
            n_acc += acc
            if rej_i is None:
                pending = None
                break
            pending = (rej_v, rej_i)
            if tick_between:
                self.tick()
        n_cache = n_rej = 0
        if pending is not None:
            n_cache = self._cache_put(*pending, targets=rej_t)
            n_rej = len(pending[1]) - n_cache
        jax.block_until_ready(self.state.lengths)
        dt = time.perf_counter() - t0
        self.stats["insert_time"] += dt
        self.stats["inserted"] += n_acc + n_cache
        self.stats["rejected"] += n_rej
        self.obs.emit("insert", accepted=n_acc, cached=n_cache,
                      rejected=n_rej, seconds=round(dt, 6))
        return UpdateResult(accepted=n_acc, cached=n_cache, rejected=n_rej,
                            seconds=dt)

    def _insert_rounds(self, vecs, ids):
        """One pass of padded sharded insert rounds.  Returns
        (n_accepted, rej_vecs | None, rej_ids | None, rej_targets | None)
        — ``rej_targets`` is the global pid each rejected job was routed
        to (-1 if nothing insertable), carried into the cache so the
        pressure stats can attribute the parked backlog to its shard."""
        J = self.round_size
        n_acc = 0
        rej_v, rej_i, rej_t = [], [], []
        for off in range(0, len(ids), J):
            cv, ci = vecs[off:off + J], ids[off:off + J]
            n = len(ci)
            pad = J - n
            valid = np.concatenate([np.ones(n, bool), np.zeros(pad, bool)])
            cv = np.concatenate([cv, np.zeros((pad, self.cfg.dim),
                                              np.float32)])
            ci = np.concatenate([ci, np.zeros(pad, np.int32)])
            self.state, accm, routed = self._insert_fn(
                self.state, jnp.asarray(cv), jnp.asarray(ci),
                jnp.asarray(valid))
            accm = np.asarray(accm)[:n]
            n_acc += int(accm.sum())
            if self.tier is not None:       # appends heat their target
                self.tier.note_targets(np.asarray(routed)[:n][accm])
            if not accm.all():
                rej_v.append(cv[:n][~accm])
                rej_i.append(ci[:n][~accm])
                rej_t.append(np.asarray(routed)[:n][~accm])
        if not rej_i:
            return n_acc, None, None, None
        return (n_acc, np.concatenate(rej_v), np.concatenate(rej_i),
                np.concatenate(rej_t))

    def delete(self, ids) -> UpdateResult:
        ids = np.asarray(ids, np.int64).astype(np.int32)
        t0 = time.perf_counter()
        J = self.round_size
        n_done = 0
        for off in range(0, len(ids), J):
            ci = ids[off:off + J]
            pad = J - len(ci)
            valid = np.concatenate([np.ones(len(ci), bool),
                                    np.zeros(pad, bool)])
            ci = np.concatenate([ci, np.zeros(pad, np.int32)])
            self.state, done = self._delete_fn(
                self.state, jnp.asarray(ci), jnp.asarray(valid))
            n_done += int(np.asarray(done).sum())
        jax.block_until_ready(self.state.lengths)
        dt = time.perf_counter() - t0
        self.stats["delete_time"] += dt
        self.stats["deleted"] += n_done
        self.obs.emit("delete", deleted=n_done, blocked=0,
                      seconds=round(dt, 6))
        return UpdateResult(deleted=n_done, seconds=dt)

    def search(self, queries, k: int,
               nprobe: Optional[int] = None) -> SearchResult:
        return self.collect_search(self.dispatch_search(queries, k, nprobe))

    def dispatch_search(self, queries, k: int,
                        nprobe: Optional[int] = None) -> SearchDispatch:
        """Launch the jitted sharded search without awaiting it (the
        serving engine's overlap seam; pair with ``collect_search``)."""
        q = np.asarray(queries, np.float32)
        t0 = time.perf_counter()
        # cold tier + host rerank: widen the final candidate set to
        # rerank_k so the exact host pass has room to reorder (the
        # device top-k orders spilled candidates by ADC score; narrower
        # widths measurably cost recall on a mostly-cold index)
        k_eff = (max(k, self.cfg.rerank_k)
                 if self.tier is not None and self.tier.rerank_host
                 else k)
        key = (k_eff, nprobe)
        fn = self._search_fns.get(key)
        if fn is None:
            fn = self._search_fns[key] = make_sharded_search(
                self.cfg, self.mesh, k=k_eff, nprobe=nprobe,
                shard_cache_scan=self._shard_cache_scan)
        qp = q
        pad = (-q.shape[0]) % self._q_mult
        if pad:
            qp = np.concatenate([q, np.zeros((pad, q.shape[1]),
                                             np.float32)])
        # per-dispatch fallback accounting (see the single-device
        # driver): the signature covers routing, not batch shape
        sig = ("sharded-search", self.cfg.use_pallas, self.cfg.dim,
               self.cfg.capacity, self.cfg.use_pq, self.cfg.pq_ksub)
        with ops.count_fallback_dispatches(self.obs, sig):
            found, scores = fn(self.state, jnp.asarray(qp))
        return SearchDispatch(state=self.state, queries=q, k=k,
                              found=found, scores=scores, probe=None,
                              t0=t0)

    def collect_search(self, disp: SearchDispatch) -> SearchResult:
        """Await a dispatched sharded search and finish the host tail
        against the dispatch-time state."""
        Q = disp.queries.shape[0]
        found = np.asarray(disp.found)[:Q]
        scores = np.asarray(disp.scores)[:Q]
        if self.tier is not None:
            # search-heat: the postings holding the found candidates
            # (the sharded search does not export its probe list)
            safe = np.clip(found, 0, self.cfg.max_ids - 1)
            loc = np.asarray(disp.state.id_loc[jnp.asarray(safe)])
            pid = loc[(found >= 0) & (loc >= 0)] // self.cfg.capacity
            self.tier.note_probes(pid)
            if self.tier.rerank_host and len(self.tier.pool):
                found, scores, n_sp = tier_mod.host_rerank(
                    found, scores, disp.queries, self.tier.pool, loc,
                    np.asarray(disp.state.tier_spilled),
                    self.cfg.capacity)
                self.stats["search_spilled_hits"] += n_sp
            found, scores = found[:, :disp.k], scores[:, :disp.k]
        dt = time.perf_counter() - disp.t0
        self.stats["search_time"] += dt
        self.stats["queries"] += Q
        # introspection from the already-transferred result arrays (the
        # sharded search exports no probe list — see note_probes above)
        self.stats["search_results"] += int((found >= 0).sum())
        if self.cfg.use_pq:
            self.stats["search_adc_batches"] += 1
        else:
            self.stats["search_exact_batches"] += 1
        return SearchResult(ids=found, scores=scores, seconds=dt)

    # ------------------------------------------------------------------
    # background
    # ------------------------------------------------------------------

    def tick(self) -> TickReport:
        """One background round: the collective-free sharded
        select/mark/execute/GC program (which also reports per-shard
        pressure), then the cross-shard rebalance stage, then the host
        cache drain, then the PQ re-train on cadence."""
        if self._profile_dir and not self._profiled:
            self._profiled = True
            with self.obs.profile(self._profile_dir):
                return self._tick_impl()
        return self._tick_impl()

    def _tick_impl(self) -> TickReport:
        t0 = time.perf_counter()
        plan = None
        if self.tier is not None and self.tier_async:
            # tick-start dispatch: spill D2H + promote H2D overlap the
            # sharded background program; reconcile commits at tick end
            # (decayed=True — the sharded round decays every tick)
            st, plan = self.tier.dispatch(self.state, decayed=True)
            if st is not self.state:
                self.state = jax.device_put(st, self._shardings)
        executed, reclaimed, _ = self.exec_background()
        migrated = self._rebalance() if self.rebalance else 0
        drained = self.exec_drain()
        retrained = self._pq_retrain()
        if self.tier is not None and self.tier_async:
            st, n_s, n_p = self.tier.reconcile(self.state, plan)
            if st is not self.state:
                self.state = jax.device_put(st, self._shardings)
            self.stats["tier_spilled"] += n_s
            self.stats["tier_promoted"] += n_p
            self.stats["tier_resident"] = len(self.tier.pool)
            spilled, promoted = n_s, n_p
        else:
            spilled, promoted = self._tier_step()
        dt = time.perf_counter() - t0
        self.stats["bg_time"] += dt
        self.stats["drained"] += drained
        self.obs.emit("tick", executed=executed, drained=drained,
                      migrated=migrated, gc=reclaimed, pq=retrained,
                      spilled=spilled, promoted=promoted,
                      seconds=round(dt, 6))
        # marked=0, honestly: the sharded round selects and executes in
        # ONE atomic program, so there is no separate mark phase to
        # count — quiescence is executed == 0 (+ empty cache), and a
        # caller porting UBISDriver's flush check gets exactly that
        return TickReport(executed=executed, drained=drained,
                          migrated=migrated, gc=reclaimed,
                          pq_retrained=retrained, spilled=spilled,
                          promoted=promoted, seconds=dt)

    def flush(self, max_ticks: int = 200) -> int:
        """Tick until quiescent (no structural work, no migrations left
        to plan, cache empty, no tier moves in flight)."""
        for i in range(max_ticks):
            r = self.tick()
            cache_n = int(np.asarray(self.state.cache_valid).sum())
            if (r.executed == 0 and r.migrated == 0 and cache_n == 0
                    and r.spilled == 0 and r.promoted == 0):
                return i + 1
        return max_ticks

    # ---- plan/execute halves (the coordinator/worker seam) ------------
    # The cluster worker (``repro.cluster.worker``) drives these pieces
    # directly: observations (pressure, plan inputs) ship up to the
    # coordinator, plans (migrate moves, retrain slot, tier lanes) ship
    # back down, and ``_tick_impl`` above is just the in-process
    # composition of the same halves — one code path, two deployments.

    def exec_background(self):
        """Run ONE sharded background program (select/mark/execute/GC)
        and record the pressure rows.  Returns
        (executed, reclaimed, pressure)."""
        t0 = time.perf_counter()
        ver = int(jax.device_get(self.state.global_version))
        gc_min = ver - self.gc_lag if ver > self.gc_lag else 0
        self.state, ex, gc, press = self._background_fn(self.state,
                                                        jnp.uint32(gc_min))
        executed, reclaimed = int(ex), int(gc)
        self._pressure = np.asarray(press)
        self.stats["bg_exec_time"] += time.perf_counter() - t0
        self.stats["bg_ops"] += executed
        self.stats["bg_gc"] += reclaimed
        return executed, reclaimed, self._pressure

    def rebalance_inputs(self):
        """The migrate planner's (M,)-sized observation: live lengths
        plus the movable mask (allocated NORMAL postings).  Serializable
        — the cluster worker ships these to the coordinator."""
        lengths = np.asarray(self.state.lengths)
        status = np.asarray(vm.unpack_status(self.state.rec_meta))
        movable = (np.asarray(self.state.allocated)
                   & (status == STATUS_NORMAL))
        return lengths, movable

    def exec_migrate(self, src, dst) -> np.ndarray:
        """Execute one already-planned migration round (owner extract,
        free-stack install, id-map rewrite + tier-pool remap).  Returns
        the per-move committed mask."""
        src = np.asarray(src, np.int32)
        dst = np.asarray(dst, np.int32)
        B = self._migrate_jobs
        pad = B - len(src)
        valid = np.concatenate([np.ones(len(src), bool),
                                np.zeros(pad, bool)])
        src = np.concatenate([src, np.full(pad, -1, np.int32)])
        dst = np.concatenate([dst, np.zeros(pad, np.int32)])
        self.state, mig, new_pids = self._migrate_fn(
            self.state, jnp.asarray(src), jnp.asarray(dst),
            jnp.asarray(valid))
        mig = np.asarray(mig)[:B - pad] if pad else np.asarray(mig)
        if self.tier is not None:
            # spilled postings migrate WITHOUT promotion: the device
            # round carried codes + flags, the host pool entry follows
            new_pids = np.asarray(new_pids)
            for j in np.flatnonzero(mig):
                if int(src[j]) in self.tier.pool:
                    self.tier.pool.remap(int(src[j]), int(new_pids[j]))
        self.stats["migrated"] += int(mig.sum())
        return mig

    def _rebalance(self) -> int:
        """Plan + execute one migration round when the tick's pressure
        rows cross a trigger.  The planner's cheap ``needs`` gate keeps
        quiescent ticks free of the (M,)-sized host reads."""
        press = self._pressure
        if press is None or not self.planner.needs(press):
            return 0
        lengths, movable = self.rebalance_inputs()
        src, dst = self.planner.plan(press, lengths, movable)
        if len(src) == 0:
            return 0
        mig = self.exec_migrate(src, dst)
        n = int(mig.sum())
        # per-move decision trace: the planner recorded each accepted
        # move's trigger; mark which ones the device round committed
        self.obs.emit(
            "rebalance",
            trigger=(self.planner.last_moves[0]["trigger"]
                     if self.planner.last_moves else "none"),
            moves=[{**mv, "committed": bool(mig[j])}
                   for j, mv in enumerate(self.planner.last_moves)],
            migrated=n)
        return n

    def shard_pressure(self) -> Optional[np.ndarray]:
        """Last tick's (S, 4) pressure rows — ``(live_postings,
        free_slots, cache_backlog, live_vectors)`` per shard — or None
        before the first tick."""
        return self._pressure

    def shard_occupancy(self) -> np.ndarray:
        """Live vectors per posting-pool shard, computed host-side (no
        tick required) — the ``figskew`` spread metric."""
        from ..core.metrics import shard_live_vectors
        return shard_live_vectors(self.state, self.n_shards)

    # ---- host-mediated vector cache -----------------------------------

    def _replicate(self, x):
        return jax.device_put(jnp.asarray(x), self._rep)

    def _cache_put(self, vecs, ids, targets=None) -> int:
        """Park jobs in the replicated cache as ONE jitted
        ``update.cache_append`` round per chunk: the program is
        deterministic over the replicated cache arrays, so every replica
        writes identical bytes and no array ever round-trips through the
        host (id_loc takes the ``-2 - slot`` encoding, so the entries
        stay searchable and deletable).  ``targets`` carries the routed
        global pid per job — the pressure stats' backlog attribution
        (-1 when unknown)."""
        vecs = np.asarray(vecs, np.float32)
        ids = np.asarray(ids, np.int32)
        tgts = (np.full(len(ids), -1, np.int32) if targets is None
                else np.asarray(targets, np.int32))
        J = self.round_size
        n = 0
        for off in range(0, len(ids), J):
            cv, ci, ct = (vecs[off:off + J], ids[off:off + J],
                          tgts[off:off + J])
            pad = J - len(ci)
            want = np.concatenate([np.ones(len(ci), bool),
                                   np.zeros(pad, bool)])
            cv = np.concatenate([cv, np.zeros((pad, self.cfg.dim),
                                              np.float32)])
            ci = np.concatenate([ci, np.zeros(pad, np.int32)])
            ct = np.concatenate([ct, np.full(pad, -1, np.int32)])
            st, ok = self._cache_admit_fn(
                self.state, jnp.asarray(cv), jnp.asarray(ci),
                jnp.asarray(ct), jnp.asarray(want))
            self.state = jax.device_put(st, self._shardings)
            got = int(np.asarray(ok).sum())
            n += got
            if got < int(want.sum()):
                break                       # cache full — rest rejected
        self.stats["host_cached"] += n
        return n

    def _drain_cache(self) -> int:
        """Pop up to ``drain_per_tick`` cached vectors and feed them back
        through the sharded insert round; failures re-park."""
        cval = np.array(self.state.cache_valid)
        slots = np.flatnonzero(cval)[:self.drain_n]
        if slots.size == 0:
            return 0
        vecs = np.asarray(self.state.cache_vecs)[slots].astype(np.float32)
        ids = np.asarray(self.state.cache_ids)[slots]
        cval[slots] = False
        self.state = dataclasses.replace(
            self.state, cache_valid=self._replicate(cval))
        n_acc, rej_v, rej_i, rej_t = self._insert_rounds(vecs, ids)
        if rej_i is not None:
            self._cache_put(rej_v, rej_i, targets=rej_t)
        return n_acc

    # public plan/execute name for the cluster worker (same op)
    exec_drain = _drain_cache

    def _pq_retrain(self) -> int:
        """Versioned codebook re-train on tick cadence (quant plane):
        the cadence decision half; execution is ``exec_pq_retrain``.
        The cluster coordinator owns this counter instead — it sends an
        explicit retrain slot in the tick plan."""
        if not self.cfg.use_pq or self.pq_retrain_every <= 0:
            return 0
        self._ticks += 1
        if self._ticks % self.pq_retrain_every:
            return 0
        return self.exec_pq_retrain()

    def exec_pq_retrain(self) -> int:
        """Execute one codebook re-train round now
        (``sharded.make_sharded_retrain``); the output is re-pinned to
        the canonical specs so later shard_map calls see exact
        layouts."""
        if self.tier is not None:
            # promote spilled postings pinned to the evicted slot first
            # (see tier.TierManager.promote_retrain_pinned); the retrain
            # round below re-pins the canonical shardings
            self.state, n = self.tier.promote_retrain_pinned(self.state)
            self.stats["tier_promoted"] += n
        evict = (int(self.state.pq_active) + 1) % self.cfg.pq_versions
        self._pq_key, k = jax.random.split(self._pq_key)
        if self._retrain_fn is None:
            self._retrain_fn = make_sharded_retrain(self.cfg, self.mesh)
        st = self._retrain_fn(self.state, k)
        self.state = jax.device_put(st, self._shardings)
        self.stats["pq_retrains"] += 1
        self.stats["pq_generation"] = int(
            self.state.pq_slot_gen[self.state.pq_active])
        self.obs.emit("pq_retrain", reason="cadence", evicted_slot=evict,
                      generation=int(self.stats["pq_generation"]))
        return 1

    # ---- cold-tier plane ----------------------------------------------

    def _tier_step(self) -> tuple:
        """Spill/promote planning + moves; re-pins the canonical
        shardings after any mutation (the tier rounds are plain jit)."""
        if self.tier is None:
            return 0, 0
        # decayed=True: the sharded background program runs (and decays
        # the heat counters) every tick
        st, n_s, n_p = self.tier.tick(self.state, decayed=True)
        if st is not self.state:
            self.state = jax.device_put(st, self._shardings)
        self.stats["tier_spilled"] += n_s
        self.stats["tier_promoted"] += n_p
        self.stats["tier_resident"] = len(self.tier.pool)
        return n_s, n_p

    def force_spill(self, n: int) -> int:
        """Spill the ``n`` coldest hot postings now (test hook)."""
        if self.tier is None:
            return 0
        st, moved = self.tier.force_spill(self.state, n)
        self.state = jax.device_put(st, self._shardings)
        self.stats["tier_spilled"] += moved
        self.stats["tier_resident"] = len(self.tier.pool)
        return moved

    def force_promote(self, n=None) -> int:
        """Promote up to ``n`` spilled postings (all when None)."""
        if self.tier is None:
            return 0
        st, moved = self.tier.force_promote(self.state, n)
        self.state = jax.device_put(st, self._shardings)
        self.stats["tier_promoted"] += moved
        self.stats["tier_resident"] = len(self.tier.pool)
        return moved

    def tier_host_bytes_by_shard(self) -> np.ndarray:
        """Host-pool bytes per shard (contiguous pid blocks) — the
        per-shard tier-pool accounting."""
        out = np.zeros(self.n_shards, np.int64)
        if self.tier is not None:
            pool_span = self.cfg.max_postings // self.n_shards
            from ..core.types import tile_bytes
            tb = tile_bytes(self.state)
            for pid in self.tier.pool.pids():
                out[int(pid) // pool_span] += tb
        return out

    # ---- StreamingIndex protocol surface ------------------------------

    def snapshot(self) -> IndexState:
        """Gather to a single-device state with a canonical free stack
        (``update.ensure_free_stack`` asserts the contract — the sharded
        rounds hand back a fail-safe EMPTY stack).  With the cold tier
        on, spilled float tiles are written back into the gathered copy
        (flags stay set) so the snapshot is self-contained."""
        host = jax.device_get(self.state)
        st = jax.tree_util.tree_map(jnp.asarray, host)
        if self.tier is not None:
            st = self.tier.snapshot_fill(st)
        return update.ensure_free_stack(st)

    def load_snapshot(self, state: IndexState) -> "ShardedUBISDriver":
        """Adopt a ``snapshot()`` state: tier residency is re-derived
        from the persisted flags (spilled tiles move back to the host
        pool, device copies re-zeroed), then the state is re-pinned to
        this driver's mesh.  Returns self."""
        if self.tier is not None:
            state = self.tier.adopt(state)
        self.state = jax.device_put(state, self._shardings)
        return self

    def memory_bytes(self) -> int:
        """Total bytes across BOTH tiers (see ``memory_tiers``)."""
        from ..core.types import state_memory_bytes
        return state_memory_bytes(self.state)

    def memory_tiers(self) -> dict:
        """Device/host byte split; sums to ``memory_bytes()``."""
        if self.tier is not None:
            return self.tier.memory_tiers(self.state)
        return {"device": self.memory_bytes(), "host": 0}

    def exact(self, queries, k: int) -> SearchResult:
        """Exact top-k over live contents (recall oracle) — a
        ``shard_map``'d brute force: each shard scans only the postings
        and cache slice it owns against ITS OWN id rows, so the
        replicated-id-row partial-sum hazard of a plain GSPMD
        ``brute_force`` (ids silently scaled by the data-axis size)
        cannot arise, and the oracle no longer gathers the whole index
        to one device per call."""
        fn = self._exact_fns.get(k)
        if fn is None:
            fn = self._exact_fns[k] = make_sharded_exact(self.cfg,
                                                         self.mesh, k)
        queries = np.asarray(queries, np.float32)
        found, scores = fn(self.state, jnp.asarray(queries))
        if self.tier is not None:
            # spilled postings were excluded device-side; merge the
            # host-pool scan so the oracle stays exact under tiering
            found, scores = self.tier.exact_merge(self.state, queries,
                                                  found, scores, k)
        return SearchResult(ids=np.asarray(found),
                            scores=np.asarray(scores))

    def posting_lengths(self) -> np.ndarray:
        from ..core.metrics import live_posting_lengths
        return live_posting_lengths(self.state)

    def live_count(self) -> int:
        """Vectors in visible postings + the (replicated) cache."""
        return int(self.state.live_vector_count()) + int(
            np.asarray(self.state.cache_valid).sum())

    def throughput(self) -> dict:
        from ..core.metrics import throughput_from_stats
        return throughput_from_stats(self.stats)

    def close(self) -> None:
        """Detach this driver's ``Obs`` bundle from the process-global
        kernel-fallback plane (weakly held; see ``UBISDriver.close``)."""
        ops.discard_fallback_sink(self.obs)
