"""FreshDiskANN-lite: the graph-based comparison baseline (paper V-A).

A reduced-scale but behaviourally-faithful Vamana/FreshDiskANN: fixed
out-degree proximity graph, greedy beam search, RobustPrune(alpha)
insertion with back-edges, lazy tombstone deletes with periodic
consolidation.  Pure JAX: the beam search is a bounded ``fori_loop``
over a fixed-size candidate list, vmapped over the query batch.

The paper's observations this must reproduce: (a) competitive QPS,
(b) recall degradation under heavy streaming churn (fresh inserts
re-wire neighbourhoods and tombstones break navigability until
consolidation), (c) higher memory than the cluster-based index.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from collections import defaultdict
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..api.types import SearchResult, TickReport, UpdateResult
from ..kernels import ref

BIG = 1e30


@dataclasses.dataclass(frozen=True)
class GraphConfig:
    dim: int = 64
    max_nodes: int = 1 << 17
    degree: int = 32              # R (memory-index out-degree)
    beam: int = 40                # L (search candidate list)
    alpha: float = 1.2            # RobustPrune slack
    consolidate_every: int = 4096  # deletes between consolidations


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class GraphState:
    vectors: jax.Array    # (N, d)
    nbrs: jax.Array       # (N, R) int32, -1 pad
    valid: jax.Array      # (N,) bool (tombstones False)
    ids: jax.Array        # (N,) int32 external ids
    n_used: jax.Array     # () int32
    entry: jax.Array      # () int32 medoid / entry point


def empty_graph(cfg: GraphConfig) -> GraphState:
    return GraphState(
        vectors=jnp.zeros((cfg.max_nodes, cfg.dim), jnp.float32),
        nbrs=jnp.full((cfg.max_nodes, cfg.degree), -1, jnp.int32),
        valid=jnp.zeros((cfg.max_nodes,), bool),
        ids=jnp.full((cfg.max_nodes,), -1, jnp.int32),
        n_used=jnp.zeros((), jnp.int32),
        entry=jnp.zeros((), jnp.int32),
    )


def _dist(a, b):
    d = a - b
    return jnp.sum(d * d, -1)


@functools.partial(jax.jit, static_argnames=("cfg", "iters"))
def beam_search(state: GraphState, cfg: GraphConfig, queries,
                iters: Optional[int] = None):
    """Batched greedy beam search.  Returns (cand_ids (Q, L) node
    indices sorted by distance, cand_dists)."""
    L = cfg.beam
    R = cfg.degree
    if iters is None:
        iters = L

    def one(q):
        cand = jnp.full((L,), -1, jnp.int32).at[0].set(state.entry)
        dist = jnp.full((L,), BIG).at[0].set(
            _dist(q, state.vectors[state.entry]))
        expanded = jnp.zeros((L,), bool)

        def body(_, carry):
            cand, dist, expanded = carry
            # best unexpanded candidate
            score = jnp.where(expanded | (cand < 0), BIG, dist)
            i = ref.exact_argmin(score)
            has = score[i] < BIG / 2
            expanded = expanded.at[i].set(True)
            node = jnp.maximum(cand[i], 0)
            nb = state.nbrs[node]                       # (R,)
            nb_ok = (nb >= 0) & has
            nbv = state.vectors[jnp.maximum(nb, 0)]
            nd = jnp.where(nb_ok, _dist(q[None], nbv), BIG)
            # skip neighbours already in the list
            dup = (nb[:, None] == cand[None, :]).any(1)
            nd = jnp.where(dup, BIG, nd)
            # merge: keep top-L by distance
            all_c = jnp.concatenate([cand, nb])
            all_d = jnp.concatenate([dist, nd])
            all_e = jnp.concatenate([expanded, jnp.zeros((R,), bool)])
            order = jnp.argsort(all_d)[:L]
            return all_c[order], all_d[order], all_e[order]

        cand, dist, expanded = jax.lax.fori_loop(
            0, iters, body, (cand, dist, expanded))
        return cand, dist

    return jax.vmap(one)(queries.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("cfg", "k"))
def _search_topk(state: GraphState, cfg: GraphConfig, queries, k: int):
    cand, dist = beam_search(state, cfg, queries)
    ok = (cand >= 0) & state.valid[jnp.maximum(cand, 0)]
    dist = jnp.where(ok, dist, BIG)
    order = jnp.argsort(dist, axis=1)[:, :k]
    ids = jnp.take_along_axis(
        state.ids[jnp.maximum(cand, 0)], order, axis=1)
    d = jnp.take_along_axis(dist, order, axis=1)
    return jnp.where(d < BIG / 2, ids, -1), d


def robust_prune(q_vec, cand_idx, cand_dist, vectors, R, alpha):
    """NumPy RobustPrune (host-side insert path)."""
    order = np.argsort(cand_dist)
    chosen: list = []
    for i in order:
        c = int(cand_idx[i])
        if c < 0 or cand_dist[i] >= BIG / 2:
            continue
        if any(c == x for x in chosen):
            continue
        ok = True
        for x in chosen:
            dxc = float(np.sum((vectors[x] - vectors[c]) ** 2))
            if alpha * dxc < cand_dist[i]:
                ok = False
                break
        if ok:
            chosen.append(c)
        if len(chosen) >= R:
            break
    return chosen


class FreshDiskANN:
    """Host-driven streaming graph index (insert path mirrors the
    paper's in-memory index + periodic consolidation)."""

    def __init__(self, cfg: GraphConfig, seed_vectors: np.ndarray,
                 seed_ids: np.ndarray, *, obs=None):
        from ..obs import Obs
        self.cfg = cfg
        self.state = empty_graph(cfg)
        self._host_vec = np.zeros((cfg.max_nodes, cfg.dim), np.float32)
        self._host_nbrs = np.full((cfg.max_nodes, cfg.degree), -1,
                                  np.int32)
        self._id2node: dict = {}
        self._deletes_pending = 0
        # same stats schema as every other engine (tests/test_obs.py);
        # graph-irrelevant keys simply stay 0
        self.obs = obs if obs is not None else Obs()
        self.stats = self.obs.driver_stats()
        if len(seed_vectors):
            self.insert(seed_vectors, seed_ids)

    # -- helpers -----------------------------------------------------------

    def _sync_device(self):
        n = int(self.state.n_used)
        self.state = dataclasses.replace(
            self.state,
            vectors=jnp.asarray(self._host_vec),
            nbrs=jnp.asarray(self._host_nbrs))

    def insert(self, vecs: np.ndarray, ids: np.ndarray,
               _chunk: int = 128) -> UpdateResult:
        """Chunked internally: each sub-batch links against a graph that
        already contains its predecessors (sequential-insert fidelity)."""
        if len(vecs) > _chunk:
            t0 = time.perf_counter()
            n_acc = 0
            for off in range(0, len(vecs), _chunk):
                n_acc += self.insert(vecs[off:off + _chunk],
                                     ids[off:off + _chunk]).accepted
            return UpdateResult(accepted=n_acc,
                                seconds=time.perf_counter() - t0)
        t0 = time.perf_counter()
        vecs = np.asarray(vecs, np.float32)
        ids = np.asarray(ids, np.int64)
        cfg = self.cfg
        # upsert semantics: re-inserting a live external id retires its
        # old node first — otherwise the stale duplicate stays valid
        # forever (deletes only track the newest node per id)
        stale = [self._id2node[int(i)] for i in ids
                 if int(i) in self._id2node]
        if stale:
            self.state = dataclasses.replace(
                self.state,
                valid=self.state.valid.at[jnp.asarray(stale)].set(False))
            self._deletes_pending += len(stale)
        n0 = int(self.state.n_used)
        n_new = len(vecs)
        # batched candidate search against the current graph
        if n0 > 0:
            cand, cd = beam_search(self.state, cfg, jnp.asarray(vecs))
            cand = np.asarray(cand)
            cd = np.asarray(cd)
        else:
            cand = np.full((n_new, cfg.beam), -1, np.int32)
            cd = np.full((n_new, cfg.beam), BIG, np.float32)
        valid_np = np.asarray(self.state.valid)
        new_nodes = np.arange(n0, n0 + n_new)
        self._host_vec[new_nodes] = vecs
        back: dict = defaultdict(list)
        for j, node in enumerate(new_nodes):
            cj = cand[j]
            dj = np.where((cj >= 0) & valid_np[np.maximum(cj, 0)],
                          cd[j], BIG)
            chosen = robust_prune(vecs[j], cj, dj, self._host_vec,
                                  cfg.degree, cfg.alpha)
            self._host_nbrs[node, :len(chosen)] = chosen
            for c in chosen:
                back[c].append(node)
        # back-edges with prune-on-overflow
        for c, incoming in back.items():
            row = [x for x in self._host_nbrs[c] if x >= 0]
            row.extend(incoming)
            if len(row) > cfg.degree:
                dists = np.sum(
                    (self._host_vec[row] - self._host_vec[c]) ** 2, -1)
                chosen = robust_prune(
                    self._host_vec[c], np.array(row), dists,
                    self._host_vec, cfg.degree, cfg.alpha)
                row = chosen
            self._host_nbrs[c, :] = -1
            self._host_nbrs[c, :len(row)] = row[:cfg.degree]
        for j, node in enumerate(new_nodes):
            self._id2node[int(ids[j])] = int(node)
        self.state = dataclasses.replace(
            self.state,
            valid=self.state.valid.at[jnp.asarray(new_nodes)].set(True),
            ids=self.state.ids.at[jnp.asarray(new_nodes)].set(
                jnp.asarray(ids.astype(np.int32))),
            n_used=jnp.asarray(n0 + n_new, jnp.int32))
        self._sync_device()
        if n0 == 0:
            # entry point: medoid of the first batch
            med = int(np.argmin(np.sum(
                (vecs - vecs.mean(0)) ** 2, -1)))
            self.state = dataclasses.replace(
                self.state, entry=jnp.asarray(med, jnp.int32))
        dt = time.perf_counter() - t0
        self.stats["insert_time"] += dt
        self.stats["inserted"] += n_new
        return UpdateResult(accepted=n_new, seconds=dt)

    def delete(self, ids: np.ndarray) -> UpdateResult:
        t0 = time.perf_counter()
        nodes = [self._id2node[i] for i in np.asarray(ids, np.int64)
                 if int(i) in self._id2node]
        if nodes:
            self.state = dataclasses.replace(
                self.state,
                valid=self.state.valid.at[jnp.asarray(nodes)].set(False))
            for i in np.asarray(ids, np.int64):
                self._id2node.pop(int(i), None)
        self._deletes_pending += len(nodes)
        if self._deletes_pending >= self.cfg.consolidate_every:
            self.consolidate()
        dt = time.perf_counter() - t0
        self.stats["delete_time"] += dt
        self.stats["deleted"] += len(nodes)
        return UpdateResult(deleted=len(nodes), seconds=dt)

    def consolidate(self):
        """FreshDiskANN's StreamingMerge analogue: splice tombstoned
        nodes out of neighbour lists (one-hop patch + prune)."""
        valid = np.asarray(self.state.valid)
        n = int(self.state.n_used)
        for u in range(n):
            if not valid[u]:
                continue
            row = self._host_nbrs[u]
            dead = [x for x in row if x >= 0 and not valid[x]]
            if not dead:
                continue
            keep = [x for x in row if x >= 0 and valid[x]]
            # adopt the dead neighbours' live neighbours
            for dnode in dead:
                keep.extend(x for x in self._host_nbrs[dnode]
                            if x >= 0 and valid[x])
            keep = list(dict.fromkeys(keep))[:4 * self.cfg.degree]
            if keep:
                dists = np.sum(
                    (self._host_vec[keep] - self._host_vec[u]) ** 2, -1)
                keep = robust_prune(self._host_vec[u], np.array(keep),
                                    dists, self._host_vec,
                                    self.cfg.degree, self.cfg.alpha)
            self._host_nbrs[u, :] = -1
            self._host_nbrs[u, :len(keep)] = keep
        self._deletes_pending = 0
        self._sync_device()

    def search(self, queries: np.ndarray, k: int) -> SearchResult:
        t0 = time.perf_counter()
        ids, d = _search_topk(self.state, self.cfg,
                              jnp.asarray(queries, jnp.float32), k)
        dt = time.perf_counter() - t0
        self.stats["search_time"] += dt
        self.stats["queries"] += len(queries)
        return SearchResult(ids=np.asarray(ids), scores=np.asarray(d),
                            seconds=dt)

    def tick(self) -> TickReport:
        return TickReport()

    def flush(self, max_ticks: int = 0) -> int:
        self.consolidate()
        return 1

    # ---- StreamingIndex protocol surface ------------------------------

    def snapshot(self) -> GraphState:
        return self.state

    def memory_bytes(self) -> int:
        return int(sum(x.size * x.dtype.itemsize for x in
                       jax.tree_util.tree_leaves(self.state)))

    def memory_tiers(self) -> dict:
        return {"device": self.memory_bytes(), "host": 0}

    def exact(self, queries: np.ndarray, k: int) -> SearchResult:
        """Exact top-k over the live (non-tombstoned) nodes."""
        valid = np.asarray(self.state.valid)
        live = np.flatnonzero(valid)
        q = np.asarray(queries, np.float32)
        if live.size == 0:
            shape = (len(q), k)
            return SearchResult(ids=np.full(shape, -1, np.int32),
                                scores=np.full(shape, BIG, np.float32))
        vecs = np.asarray(self.state.vectors)[live]
        ids = np.asarray(self.state.ids)[live]
        d2 = ((q[:, None, :] - vecs[None]) ** 2).sum(-1)
        order = np.argsort(d2, axis=1)[:, :k]
        found = ids[order]
        scores = np.take_along_axis(d2, order, axis=1)
        if found.shape[1] < k:   # fewer live nodes than k
            padn = k - found.shape[1]
            found = np.pad(found, ((0, 0), (0, padn)), constant_values=-1)
            scores = np.pad(scores, ((0, 0), (0, padn)),
                            constant_values=BIG)
        return SearchResult(ids=found, scores=scores)

    def posting_lengths(self) -> np.ndarray:
        return np.empty((0,), np.int32)

    def live_count(self) -> int:
        return int(np.asarray(self.state.valid).sum())

    def throughput(self) -> dict:
        from .metrics import throughput_from_stats
        return throughput_from_stats(self.stats)
