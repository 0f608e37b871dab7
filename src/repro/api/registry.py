"""Engine registry: ``make_index(engine, cfg, seed_vectors, **kw)``.

One constructor for every engine in the paper's comparison.  All
engines take the same ``UBISConfig`` (the registry rewrites ``mode``
and, for the graph baseline, translates to a ``GraphConfig``), and
keyword arguments unknown to an engine are silently dropped — which is
what lets one shared kwargs dict drive a whole engine-comparison loop
with zero engine-specific branches at the call site:

    for spec in list_engines():
        idx = make_index(spec.name, cfg, seed, seed_ids=ids0,
                         round_size=512, bg_ops_per_round=8)
        ...same insert/delete/search/tick/flush loop...

Each registry entry is an :class:`EngineSpec` — name, builder, allowed
kwargs, and **capability flags** (``supports_tier`` / ``supports_pq`` /
``supports_shards`` / ``updatable`` + the contract-harness ``audit``
tier), so callers that used to probe engines with try/except or
hard-coded name tuples (figengines, the contract harness, the tiered
property tests) now ask the registry.

``seed_vectors`` semantics follow each engine's construction story:
the cluster engines (ubis/spfresh/ubis-sharded/ubis-cluster) use them
for k-means seeding only (NOT inserted); the build-once engines (spann,
freshdiskann) ingest them under ``seed_ids`` (default ``arange``).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Tuple

import numpy as np

from ..core.types import UBISConfig
from .types import StreamingIndex

_DRIVER_KW = frozenset({
    "seed", "round_size", "bg_ops_per_round", "drain_per_tick",
    "insert_retries", "gc_lag", "reassign_after_split",
    "pq_retrain_every", "tier_moves_per_tick", "tier_rerank_host",
    "tier_async", "obs"})
_UBIS_KW = _DRIVER_KW | {"fused_tick"}
_SHARDED_KW = _DRIVER_KW | {"mesh", "shard_cache_scan", "rebalance",
                            "rebalance_watermark", "rebalance_ratio",
                            "migrate_per_tick", "route_alpha"}
_CLUSTER_KW = frozenset({
    "seed", "round_size", "bg_ops_per_round", "drain_per_tick",
    "insert_retries", "gc_lag", "reassign_after_split",
    "pq_retrain_every", "tier_moves_per_tick", "tier_rerank_host",
    "obs", "shard_cache_scan", "rebalance", "rebalance_watermark",
    "rebalance_ratio", "migrate_per_tick", "route_alpha", "workers",
    "backend", "worker_devices", "mesh_shape", "spread_ratio",
    "spread_per_tick", "rpc_timeout"})
_SPANN_KW = frozenset({"seed", "round_size", "obs"})
_GRAPH_KW = frozenset({"max_nodes", "degree", "beam", "alpha",
                       "consolidate_every", "obs"})


def _pick(kw: dict, allowed: frozenset) -> dict:
    return {k: v for k, v in kw.items() if k in allowed}


def _with_mode(cfg: UBISConfig, mode: str) -> UBISConfig:
    return cfg if cfg.mode == mode else dataclasses.replace(cfg, mode=mode)


@dataclasses.dataclass(frozen=True)
class EngineSpec:
    """One registry entry: how to build an engine + what it supports.

    ``audit`` is the contract-harness audit tier (``state`` = full
    IndexState multiset equality, ``count`` = live-count + no
    resurrection, ``static`` = every update refused); ``build`` is the
    lazily-importing constructor (same signature for every engine).
    """

    name: str
    description: str
    build: Callable[..., StreamingIndex]
    kwargs: frozenset
    supports_tier: bool = False
    supports_pq: bool = False
    supports_shards: bool = False
    updatable: bool = True
    audit: str = "state"

    def make(self, cfg: UBISConfig, seed_vectors, *, seed_ids=None,
             **kw) -> StreamingIndex:
        return self.build(cfg, seed_vectors, seed_ids, _pick(kw, self.kwargs))


def _build_ubis_mode(mode):
    def build(cfg, seed_vectors, seed_ids, kw):
        from ..core.driver import UBISDriver
        return UBISDriver(_with_mode(cfg, mode), seed_vectors, **kw)
    return build


def _build_sharded(cfg, seed_vectors, seed_ids, kw):
    from .sharded_driver import ShardedUBISDriver
    return ShardedUBISDriver(_with_mode(cfg, "ubis"), seed_vectors, **kw)


def _build_cluster(cfg, seed_vectors, seed_ids, kw):
    from ..cluster import ClusterCoordinator
    return ClusterCoordinator(_with_mode(cfg, "ubis"), seed_vectors, **kw)


def _seed_arrays(seed_vectors, seed_ids):
    seeds = np.asarray(seed_vectors, np.float32)
    ids = (np.arange(len(seeds)) if seed_ids is None
           else np.asarray(seed_ids, np.int64))
    return seeds, ids


def _build_spann(cfg, seed_vectors, seed_ids, kw):
    from ..core.spann import SPANNStatic
    seeds, ids = _seed_arrays(seed_vectors, seed_ids)
    return SPANNStatic(_with_mode(cfg, "ubis"), seeds, ids, **kw)


def _build_freshdiskann(cfg, seed_vectors, seed_ids, kw):
    from ..core.freshdiskann import FreshDiskANN, GraphConfig
    seeds, ids = _seed_arrays(seed_vectors, seed_ids)
    kw = dict(kw)
    obs = kw.pop("obs", None)
    kw.setdefault("max_nodes", 1 << 17)
    gcfg = GraphConfig(dim=cfg.dim, **kw)
    return FreshDiskANN(gcfg, seeds, ids, obs=obs)


_REGISTRY: dict[str, EngineSpec] = {spec.name: spec for spec in (
    EngineSpec(
        name="ubis",
        description="the paper's balanced updatable cluster index "
                    "(UBISDriver)",
        build=_build_ubis_mode("ubis"), kwargs=_UBIS_KW,
        supports_tier=True, supports_pq=True, audit="state"),
    EngineSpec(
        name="spfresh",
        description="UBISDriver in the SPFresh lock/strict-trigger mode",
        build=_build_ubis_mode("spfresh"), kwargs=_UBIS_KW,
        supports_tier=True, supports_pq=True, audit="state"),
    EngineSpec(
        name="spann",
        description="build-once SPANN snapshot (updates refused as "
                    "rejected/blocked counts)",
        build=_build_spann, kwargs=_SPANN_KW,
        updatable=False, audit="static"),
    EngineSpec(
        name="freshdiskann",
        description="FreshDiskANN Vamana graph baseline",
        build=_build_freshdiskann, kwargs=_GRAPH_KW, audit="count"),
    EngineSpec(
        name="ubis-sharded",
        description="ShardedUBISDriver: host orchestration over the "
                    "jitted pod-sharded programs",
        build=_build_sharded, kwargs=_SHARDED_KW,
        supports_tier=True, supports_pq=True, supports_shards=True,
        audit="state"),
    EngineSpec(
        name="ubis-cluster",
        description="coordinator/worker cluster plane: all planners on "
                    "the coordinator, ShardedUBISDriver workers behind "
                    "the serializable command protocol",
        build=_build_cluster, kwargs=_CLUSTER_KW,
        supports_tier=True, supports_pq=True, supports_shards=True,
        audit="state"),
)}

ENGINES = tuple(_REGISTRY)


def list_engines() -> Tuple[EngineSpec, ...]:
    """Every registered engine's spec, registration order."""
    return tuple(_REGISTRY.values())


def engine_spec(engine: str) -> EngineSpec:
    """The :class:`EngineSpec` for one engine name."""
    if engine not in _REGISTRY:
        raise ValueError(f"unknown engine {engine!r}; choose from "
                         f"{ENGINES}")
    return _REGISTRY[engine]


def make_index(engine: str, cfg: UBISConfig, seed_vectors, *,
               seed_ids=None, **kw) -> StreamingIndex:
    """Build any engine behind the ``StreamingIndex`` front door."""
    return engine_spec(engine).make(cfg, seed_vectors, seed_ids=seed_ids,
                                    **kw)
