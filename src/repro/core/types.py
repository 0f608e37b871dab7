"""Core datatypes for the UBIS updatable cluster-based index.

The index is a fixed-shape JAX pytree so that every operation (search,
insert round, split, merge, reassign) is a jit-compiled SPMD program.
Postings are fixed-capacity tiles of a pooled ``(max_postings, capacity,
dim)`` array; a free-list provides allocation; the paper's 8-byte
*Posting Recorder* word is packed into two ``uint32`` lanes per posting
(see ``version_manager.py``).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# Posting status codes (paper Section IV-B1: 2 bits, four states).
# ---------------------------------------------------------------------------
STATUS_NORMAL = 0
STATUS_SPLITTING = 1
STATUS_MERGING = 2
STATUS_DELETED = 3

# Sentinel for "no successor" in the recorder's new-postings region.
NO_SUCC = 0xFFFF
# Sentinel for empty id slots.
NO_ID = -1

# ---------------------------------------------------------------------------
# Background-op kind codes (the int lane of a batched background round).
# ---------------------------------------------------------------------------
KIND_NONE = 0
KIND_SPLIT = 1
KIND_MERGE = 2
KIND_COMPACT = 3


@dataclasses.dataclass(frozen=True)
class UBISConfig:
    """Static configuration (hashable; safe as a jit static argument)."""

    dim: int = 64
    max_postings: int = 4096          # posting pool size (must be < 0xFFFF)
    capacity: int = 96                # physical tile size (>= l_max slack)
    l_min: int = 10                   # merge threshold  (paper Section V-A)
    l_max: int = 80                   # split threshold  (paper Section V-A)
    balance_factor: float = 0.15      # paper Fig. 9 default
    nprobe: int = 32                  # postings probed per query (paper: 32)
    cache_capacity: int = 2048        # vector cache (Section IV-B2)
    graph_degree: int = 8             # centroid neighbourhood graph degree
    kmeans_iters: int = 6             # Lloyd iterations for (2-)means
    max_ids: int = 1 << 20            # id -> location map size
    succ_chase_depth: int = 4         # bounded DELETED pointer chasing
    dtype: Any = jnp.float32          # vector storage dtype
    mode: str = "ubis"                # "ubis" | "spfresh" (baseline semantics)
    # kernel backend: "on" = Pallas kernels (compiled on a TPU, run in
    # interpret mode elsewhere), "off" = the jnp reference twins,
    # "auto" = kernels on a TPU backend, reference elsewhere
    use_pallas: str = "auto"
    # distributed search: cap owned probes scanned per shard (0 = nprobe);
    # ~4x phase-2 work reduction on a 16-way pod (EXPERIMENTS.md §Perf)
    shard_probe_cap: int = 0
    # --- product-quantization plane (quant/pq.py) ----------------------
    use_pq: bool = False              # two-stage ADC search + code upkeep
    pq_m: int = 8                     # subspaces per vector (codes: m bytes)
    pq_ksub: int = 256                # centroids per subspace (uint8 codes)
    pq_versions: int = 2              # codebook version slots kept live
    pq_sample: int = 2048             # training sample size (re-train)
    rerank_k: int = 64                # float candidates exact-reranked
    # --- cold-tier host spill (core/tier.py) ---------------------------
    # Spilled postings keep centroids + PQ codes device-resident; their
    # float tiles move to a pinned host pool (the FreshDiskANN
    # billion-scale tier).  Requires use_pq: the codes are what serves a
    # spilled posting at search time (ADC-only, optional host rerank).
    use_tier: bool = False            # enable cold-tier float-tile spill
    tier_hot_max: int = 0             # device high-watermark: max float-
    #                                   resident live postings (0 = no cap;
    #                                   spill only via force_spill)
    tier_cold_heat: int = 1           # heat <= this -> spill candidate
    tier_promote_heat: int = 8        # heat >= this -> promote (search-heat)

    def __post_init__(self):
        from ..kernels.ops import BACKENDS
        if self.use_pallas not in BACKENDS:
            raise ValueError(f"use_pallas={self.use_pallas!r}; choose from "
                             f"{BACKENDS}")
        assert self.max_postings < NO_SUCC, "successor ids are 16-bit"
        assert self.capacity >= self.l_max, "tile must hold an over-full posting"
        assert self.capacity <= 2 * self.l_max, \
            "median-bisection split guard needs capacity/2 <= l_max"
        assert self.mode in ("ubis", "spfresh")
        if self.use_pq:
            assert self.dim % self.pq_m == 0, "pq_m must divide dim"
        assert 2 <= self.pq_ksub <= 256, "codes are uint8"
        assert self.pq_versions >= 2, "need >= 2 slots for lazy re-encode"
        assert self.rerank_k >= 1
        if self.use_tier:
            assert self.use_pq, \
                "use_tier requires use_pq (spilled postings serve ADC-only)"

    @property
    def pq_m_eff(self) -> int:
        """Subspace count actually used for array shapes.  With the
        quant plane off the (always-present, fixed-pytree-shape) code
        arrays are dead weight, so they shrink to one subspace; with it
        on, the __post_init__ assert guarantees pq_m divides dim."""
        return self.pq_m if self.use_pq else 1

    @property
    def pq_dsub(self) -> int:
        return self.dim // self.pq_m_eff

    @property
    def is_ubis(self) -> bool:
        return self.mode == "ubis"


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class IndexState:
    """The full index as a pytree of device arrays (all fixed shape).

    Shapes use ``M = max_postings``, ``C = capacity``, ``d = dim``,
    ``K = cache_capacity``, ``N = max_ids``.
    """

    # --- posting tiles -----------------------------------------------------
    vectors: jax.Array        # (M, C, d) vector payloads
    ids: jax.Array            # (M, C) int32 external ids, NO_ID = empty slot
    slot_valid: jax.Array     # (M, C) bool, live (non-tombstoned) slots
    used: jax.Array           # (M,) int32 append high-water mark per tile
    lengths: jax.Array        # (M,) int32 live vector count per posting
    centroids: jax.Array      # (M, d)
    # --- posting recorder (version manager) -------------------------------
    rec_meta: jax.Array       # (M,) uint32: status(2) | weight(30)
    rec_succ: jax.Array       # (M,) uint32: succ1(16) | succ2(16)
    allocated: jax.Array      # (M,) bool, slot is in use (not on free list)
    # --- centroid neighbourhood graph --------------------------------------
    nbrs: jax.Array           # (M, G) int32 neighbour posting ids, -1 pad
    # --- vector cache (Section IV-B2, splitting/merging branch) -----------
    cache_vecs: jax.Array     # (K, d)
    cache_ids: jax.Array      # (K,) int32
    cache_target: jax.Array   # (K,) int32 posting the vector was bound for
    cache_valid: jax.Array    # (K,) bool
    # --- allocation + versions ---------------------------------------------
    free_list: jax.Array      # (M,) int32 stack of free posting ids
    free_top: jax.Array       # () int32 number of entries on the free stack
    global_version: jax.Array  # () uint32 monotone version counter
    # --- id -> flat location (pid * C + slot), -1 if absent ---------------
    id_loc: jax.Array         # (N,) int32
    # --- product-quantization plane (quant/pq.py; V = pq_versions) ---------
    # codes are subspace-major (m before C) so the ADC kernel streams
    # (1, m, C) tiles with the lane dim = capacity, like the float tiles.
    codes: jax.Array          # (M, m, C) uint8 PQ codes per slot
    pq_codebooks: jax.Array   # (V, m, ksub, dsub) f32 versioned codebooks
    pq_slot_gen: jax.Array    # (V,) uint32 generation held by each slot
    pq_active: jax.Array      # () int32 slot new codes are written under
    pq_posting_slot: jax.Array  # (M,) int32 codebook slot of each posting
    # --- cold-tier residency (core/tier.py) --------------------------------
    # heat: per-posting touch counter (probes + accepted appends), decayed
    # inside the background round; tier_spilled marks postings whose float
    # tile lives in the driver's pinned host pool (device copy zeroed,
    # codes/centroid stay device-resident).
    heat: jax.Array           # (M,) uint32 touch counter
    tier_spilled: jax.Array   # (M,) bool float tile is host-resident

    def num_alive(self) -> jax.Array:
        from .version_manager import unpack_status
        status = unpack_status(self.rec_meta)
        return jnp.sum((status != STATUS_DELETED) & self.allocated)

    def live_vector_count(self) -> jax.Array:
        """Vectors in *visible* postings (retired postings keep their tile
        data until GC but no longer own any live vectors)."""
        from .version_manager import unpack_status
        status = unpack_status(self.rec_meta)
        vis = self.allocated & (status != STATUS_DELETED)
        return jnp.sum(self.lengths * vis)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class BackgroundRound:
    """Outcome of one batched background round (all int32 scalars).

    One of these is the *only* device->host transfer the driver makes per
    background tick; every counter the scheduler/benchmarks need rides in
    the same small struct.
    """

    executed: jax.Array    # ops that ran (splits + merges + compacts)
    n_split: jax.Array     # true 2-means splits
    n_merge: jax.Array     # merges (incl. partnerless self-rebuilds)
    n_compact: jax.Array   # compactions (incl. split ops demoted in-round)
    deferred: jax.Array    # ops reverted to NORMAL (no slots / conflicts)
    moved_out: jax.Array   # small-side vectors appended to nearer postings
    spilled: jax.Array     # move-outs that diverted to the vector cache
    reassigned: jax.Array  # fused post-op reassign moves
    freed: jax.Array       # empty split-b slots returned to the free list


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class RoundResult:
    """Outcome of one foreground update round (fixed shape, padded)."""

    accepted: jax.Array   # (J,) bool appended directly to a posting
    cached: jax.Array     # (J,) bool parked in the vector cache
    rejected: jax.Array   # (J,) bool dropped (SPFresh lock model / cache full)
    target: jax.Array     # (J,) int32 resolved posting id (-1 if rejected)


def empty_state(cfg: UBISConfig) -> IndexState:
    """A fully-deallocated index (build() populates it)."""
    M, C, d = cfg.max_postings, cfg.capacity, cfg.dim
    K, G, N = cfg.cache_capacity, cfg.graph_degree, cfg.max_ids
    return IndexState(
        vectors=jnp.zeros((M, C, d), cfg.dtype),
        ids=jnp.full((M, C), NO_ID, jnp.int32),
        slot_valid=jnp.zeros((M, C), jnp.bool_),
        used=jnp.zeros((M,), jnp.int32),
        lengths=jnp.zeros((M,), jnp.int32),
        centroids=jnp.zeros((M, d), cfg.dtype),
        rec_meta=jnp.full((M,), 3, jnp.uint32),  # STATUS_DELETED, weight 0
        rec_succ=jnp.full((M,), (NO_SUCC << 16) | NO_SUCC, jnp.uint32),
        allocated=jnp.zeros((M,), jnp.bool_),
        nbrs=jnp.full((M, G), -1, jnp.int32),
        cache_vecs=jnp.zeros((K, d), cfg.dtype),
        cache_ids=jnp.full((K,), NO_ID, jnp.int32),
        cache_target=jnp.full((K,), -1, jnp.int32),
        cache_valid=jnp.zeros((K,), jnp.bool_),
        free_list=jnp.arange(M - 1, -1, -1, dtype=jnp.int32),
        free_top=jnp.array(M, jnp.int32),
        global_version=jnp.array(0, jnp.uint32),
        id_loc=jnp.full((N,), -1, jnp.int32),
        codes=jnp.zeros((M, cfg.pq_m_eff, C), jnp.uint8),
        pq_codebooks=jnp.zeros(
            (cfg.pq_versions, cfg.pq_m_eff, cfg.pq_ksub, cfg.pq_dsub),
            jnp.float32),
        pq_slot_gen=jnp.zeros((cfg.pq_versions,), jnp.uint32),
        pq_active=jnp.array(0, jnp.int32),
        pq_posting_slot=jnp.zeros((M,), jnp.int32),
        heat=jnp.zeros((M,), jnp.uint32),
        tier_spilled=jnp.zeros((M,), jnp.bool_),
    )


def state_memory_bytes(state: IndexState) -> int:
    """Host-side accounting of device bytes held by the index."""
    return int(
        sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(state))
    )


def tile_bytes(state: IndexState) -> int:
    """Bytes of ONE float posting tile (the unit the cold tier moves)."""
    C, d = state.vectors.shape[1:]
    return int(C * d * state.vectors.dtype.itemsize)


def state_tier_bytes(state: IndexState) -> dict:
    """Device/host byte split under cold-tier residency.

    ``host`` is the float bytes of spilled tiles (they live in the
    driver's pinned host pool; the device copies are zeroed); ``device``
    is everything else, so ``device + host == state_memory_bytes`` — the
    untiered total — by construction.  JAX pytrees are fixed-shape, so
    the zeroed device tiles still occupy their allocation; this split
    reports what a paging allocator holds per tier, which is the honest
    HBM figure for the tier's effect (benchmarks additionally report the
    live-tile payload split, see ``benchmarks.figures.figmem``).
    """
    host = int(jax.device_get(jnp.sum(state.tier_spilled))) * \
        tile_bytes(state)
    return {"device": state_memory_bytes(state) - host, "host": host}
