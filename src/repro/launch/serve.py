"""Serving driver: batched generation + streaming UBIS retrieval.

This is the paper-kind end-to-end path: an embedding model produces
vectors for a *fresh* document stream, UBIS indexes them online
(insert/delete/split/merge concurrent with search), and queries are
answered with retrieve(-then-generate).

``RetrievalServer`` is a thin client of the serving layer: every ingest
batch and query goes through a ``repro.serving.ServingEngine`` (request
queue, fill-or-deadline batching, dispatch/collect overlap); the
synchronous shape the old server had — embed → insert → tick → search,
one tick per ingest — is the ``tick_every=1`` default of the engine's
cadence knob, so the default behavior is unchanged while ``--async-mode``
(or a custom ``ServingConfig``) turns on real overlap.
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import make_index
from repro.core import UBISConfig, metrics as ubis_metrics
from repro.models import get_model
from repro.models.layers import values
from repro.obs import Obs
from repro.serving import ServingConfig, ServingEngine


@dataclasses.dataclass
class ServeConfig:
    arch: str = "tinyllama-1.1b"
    reduced: bool = True
    embed_dim: int = 64              # PCA-ish projection of hidden states
    batch_size: int = 32
    k: int = 10
    index_dim: int = 64
    seed: int = 0
    # background-tick cadence: one index.tick() per N ingest batches
    # (0 = never; the old server ticked unconditionally per ingest)
    tick_every: int = 1
    # observability plane: sampled live-recall probe fraction, optional
    # JSONL trace sink, optional jax.profiler capture directory
    recall_probe: float = 0.0
    obs_trace_path: Optional[str] = None
    obs_profile_dir: Optional[str] = None


class EmbeddingServer:
    """Embeds token sequences with the LM backbone; random projection to
    the index dimensionality (frozen, seeded).  The backbone builds
    lazily on first use — vector-only serving never pays for it."""

    def __init__(self, cfg: ServeConfig):
        self.cfg = cfg
        self.model = get_model(cfg.arch, reduced=cfg.reduced)
        self.params = values(self.model.init(jax.random.key(cfg.seed)))
        d_model = self.model.cfg.d_model
        self.proj = jax.random.normal(
            jax.random.key(cfg.seed + 1),
            (d_model, cfg.embed_dim)) / (d_model ** 0.5)
        self._embed = jax.jit(self._embed_fn)

    def _embed_fn(self, params, tokens):
        # mean-pooled final hidden state -> fixed-dim embedding
        from repro.models.transformer import run_segments
        from repro.models.layers import rms_norm
        x = jnp.take(params["emb"], tokens, axis=0)
        x = run_segments(params, self.model.cfg, self.model.segments,
                         x, jnp.arange(tokens.shape[1]),
                         remat="none")
        x = rms_norm(x, params["ln_f"], self.model.cfg.norm_eps)
        return jnp.mean(x, axis=1) @ self.proj

    def embed(self, tokens: np.ndarray) -> np.ndarray:
        return np.asarray(self._embed(self.params, jnp.asarray(tokens)))


class RetrievalServer:
    """Batched streaming retrieval endpoint over any ``StreamingIndex``
    engine (``repro.api.make_index``; default the single-device UBIS
    driver, ``engine="ubis-sharded"`` for the pod-sharded one).

    All traffic rides the serving engine's queue.  The default
    ``serving_cfg`` preserves the classic synchronous loop (each ingest
    batch flushes immediately and ticks per ``ServeConfig.tick_every``);
    pass a ``ServingConfig`` with real deadlines for open-loop serving.
    """

    def __init__(self, cfg: ServeConfig, index_cfg: Optional[UBISConfig]
                 = None, seed_vectors: Optional[np.ndarray] = None,
                 engine: str = "ubis",
                 serving_cfg: Optional[ServingConfig] = None,
                 **engine_kw):
        self.cfg = cfg
        self._embedder: Optional[EmbeddingServer] = None
        if index_cfg is None:
            index_cfg = UBISConfig(dim=cfg.embed_dim, max_postings=2048,
                                   capacity=96, max_ids=1 << 20)
        if seed_vectors is None:
            seed_vectors = np.random.default_rng(cfg.seed).normal(
                size=(1024, index_cfg.dim)).astype(np.float32)
        # one plane covers the driver's internals AND the request spans
        self.obs = engine_kw.pop("obs", None) or Obs(
            trace_path=cfg.obs_trace_path)
        self.index = make_index(engine, index_cfg, seed_vectors,
                                obs=self.obs,
                                obs_profile_dir=cfg.obs_profile_dir,
                                **engine_kw)
        if serving_cfg is None:
            serving_cfg = ServingConfig(default_k=cfg.k,
                                        tick_every=cfg.tick_every,
                                        recall_probe=cfg.recall_probe,
                                        obs_profile_dir=cfg.obs_profile_dir)
        self.engine = ServingEngine(self.index, serving_cfg, obs=self.obs)
        self._next_id = 0
        self.stats = {"ingested": 0, "queries": 0}

    @property
    def embedder(self) -> EmbeddingServer:
        if self._embedder is None:
            self._embedder = EmbeddingServer(self.cfg)
        return self._embedder

    # -- streaming ingestion ------------------------------------------------

    def ingest_tokens(self, token_batch: np.ndarray) -> np.ndarray:
        """Embed + insert a batch of fresh documents; returns their ids."""
        vecs = self.embedder.embed(token_batch)
        return self.ingest_vectors(vecs)

    def ingest_vectors(self, vecs: np.ndarray) -> np.ndarray:
        """Enqueue + flush one ingest batch.  Background ticks follow
        the engine's ``tick_every`` cadence (the old unconditional
        tick-per-ingest is the default, ``tick_every=1``)."""
        ids = np.arange(self._next_id, self._next_id + len(vecs))
        self._next_id += len(vecs)
        self.engine.submit_insert(vecs, ids)
        self.engine.drain()
        self.stats["ingested"] += len(vecs)
        return ids

    def delete(self, ids: np.ndarray):
        self.engine.submit_delete(ids)
        self.engine.drain()

    # -- queries -------------------------------------------------------------

    def query_tokens(self, token_batch: np.ndarray, k: Optional[int] = None):
        return self.query_vectors(self.embedder.embed(token_batch), k)

    def query_vectors(self, vecs: np.ndarray,
                      k: Optional[int] = None):
        """Queue + resolve a query batch; returns a ``SearchResult``
        (named fields — the old tuple unpacking is gone)."""
        k = k or self.cfg.k
        tickets = [self.engine.submit_search(v, k) for v in
                   np.atleast_2d(np.asarray(vecs, np.float32))]
        self.engine.drain()
        rows = [t.result() for t in tickets]
        self.stats["queries"] += len(rows)
        from repro.api import SearchResult
        return SearchResult(
            ids=np.concatenate([r.ids for r in rows]),
            scores=np.concatenate([r.scores for r in rows]))

    def recall_check(self, vecs: np.ndarray, k: int = 10) -> float:
        found = self.index.search(vecs, k).ids
        true = self.index.exact(vecs, k).ids
        return ubis_metrics.recall_at_k(found, np.asarray(true))

    # -- observability -------------------------------------------------------

    def metrics_text(self) -> str:
        """Prometheus text exposition of the whole plane (driver stats,
        request-span histograms, live-recall gauge)."""
        return self.obs.to_prometheus()

    def metrics_snapshot(self) -> dict:
        """JSON-ready flat snapshot of every registered series."""
        return self.obs.snapshot()

    def trace_events(self, kind: Optional[str] = None):
        """Structured planner/request trace events (newest-capped ring)."""
        return self.obs.events(kind)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--engine", default="ubis",
                    help="any repro.api.ENGINES name")
    ap.add_argument("--docs", type=int, default=2000)
    ap.add_argument("--queries", type=int, default=128)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--tick-every", type=int, default=1,
                    help="background tick per N ingest batches (0=never)")
    ap.add_argument("--recall-probe", type=float, default=0.0,
                    help="shadow-execute this fraction of served query "
                         "batches against exact() (live recall gauge)")
    ap.add_argument("--obs-trace-path", default=None,
                    help="append structured trace events to this JSONL file")
    ap.add_argument("--obs-profile-dir", default=None,
                    help="capture a jax.profiler trace of the first "
                         "working pump/tick into this directory")
    ap.add_argument("--metrics", action="store_true",
                    help="print the Prometheus exposition at exit")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    cfg = ServeConfig(arch=args.arch, tick_every=args.tick_every,
                      recall_probe=args.recall_probe,
                      obs_trace_path=args.obs_trace_path,
                      obs_profile_dir=args.obs_profile_dir)
    server = RetrievalServer(cfg, engine=args.engine)
    rng = np.random.default_rng(0)
    vocab = server.embedder.model.cfg.vocab
    t0 = time.time()
    for off in range(0, args.docs, args.batch):
        n = min(args.batch, args.docs - off)
        toks = rng.integers(0, vocab, (n, args.seq)).astype(np.int32)
        server.ingest_tokens(toks)
    server.index.flush()
    t_ing = time.time() - t0
    qt = rng.integers(0, vocab, (args.queries, args.seq)).astype(np.int32)
    t0 = time.time()
    res = server.query_tokens(qt)
    t_q = time.time() - t0
    qv = server.embedder.embed(qt)
    rec = server.recall_check(qv)
    print(f"ingested {server.stats['ingested']} docs in {t_ing:.1f}s "
          f"({server.stats['ingested']/t_ing:.0f} docs/s); "
          f"{res.ids.shape[0]} queries in {t_q:.2f}s; recall@10 {rec:.3f}")
    if args.metrics:
        print(server.metrics_text())
    return 0


if __name__ == "__main__":
    sys.exit(main())
