"""Initial index construction (SPANN-style, paper III-B1).

Seeds the posting pool with k-means centroids over a sample, builds the
centroid neighbourhood graph, then streams every vector through the
*production* insert path — so construction exercises exactly the same
machinery as the streaming workload (splits included), and the built
index automatically satisfies the structural invariants the property
tests check.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..kernels import ops
from .types import IndexState, UBISConfig, empty_state
from .update import alloc_postings, dataclasses_replace


@functools.partial(jax.jit, static_argnames=("k", "iters", "backend"))
def kmeans(points: jax.Array, k: int, iters: int, key: jax.Array,
           backend: str = "auto"):
    """Plain Lloyd k-means; empty clusters keep their previous centroid
    (they become zero-length postings and the merge path sweeps them)."""
    n, d = points.shape
    idx = jax.random.choice(key, n, (k,), replace=False)
    cents = points[idx].astype(jnp.float32)

    def body(_, cents):
        assign, _ = ops.kmeans_assign(points, cents, backend=backend)
        sums = jnp.zeros((k, d), jnp.float32).at[assign].add(points)
        counts = jnp.zeros((k,), jnp.float32).at[assign].add(1.0)
        new = sums / jnp.maximum(counts, 1.0)[:, None]
        return jnp.where(counts[:, None] > 0, new, cents)

    return jax.lax.fori_loop(0, iters, body, cents)


@functools.partial(jax.jit, static_argnames=("cfg", "k"))
def seed_postings(state: IndexState, cfg: UBISConfig, centroids_k, k: int):
    """Allocate ``k`` empty postings at the given centroids and wire the
    centroid neighbourhood graph (top-G mutual neighbours)."""
    state, pids = alloc_postings(state, cfg, k, centroids_k,
                                 jnp.uint32(0))
    sc = ops.centroid_score(centroids_k, centroids_k, backend=cfg.use_pallas)
    sc = sc + jnp.eye(k) * 1e30  # exclude self
    g = min(cfg.graph_degree, max(k - 1, 1))
    _, nn = jax.lax.top_k(-sc, g)
    nbr_rows = jnp.full((k, cfg.graph_degree), -1, jnp.int32)
    nbr_rows = nbr_rows.at[:, :g].set(pids[nn])
    nbrs = state.nbrs.at[pids].set(nbr_rows)
    return dataclasses_replace(state, nbrs=nbrs), pids


def initial_state(cfg: UBISConfig, seed_vectors, *, key=None,
                  sample_cap: int = 20000, target_fill: float = 0.7):
    """Empty index seeded with centroids fit on (a sample of) the data.

    The vectors themselves are NOT inserted here — the driver streams
    them through insert rounds (DESIGN.md §4).
    """
    if key is None:
        key = jax.random.key(0)
    n = seed_vectors.shape[0]
    k0 = max(1, min(int(round(n / (target_fill * cfg.l_max))),
                    cfg.max_postings // 4))
    sample = jnp.asarray(seed_vectors[:sample_cap], jnp.float32)
    cents = kmeans(sample, k0, cfg.kmeans_iters, key, backend=cfg.use_pallas)
    state = empty_state(cfg)
    state, _ = seed_postings(state, cfg, cents, k0)
    if cfg.use_pq:
        # generation-0 codebooks fit on the same seed sample; every
        # insert round encodes against them from the first vector on
        from ..quant import pq
        key, pk = jax.random.split(key)
        cb0 = pq.init_codebooks(sample, cfg.pq_m, cfg.pq_ksub,
                                cfg.kmeans_iters, pk,
                                backend=cfg.use_pallas)
        state = dataclasses_replace(
            state, pq_codebooks=state.pq_codebooks.at[0].set(cb0))
    return state
