#!/usr/bin/env python3
"""Chip smoke run: load and serve a SIFT1M-shaped UBIS index on a TPU.

Drives the main path once through the entry points a user calls —
``make_index`` -> ``insert`` / ``delete`` / ``tick`` -> ``ServingEngine``
search — at the shape of ann-benchmarks ``sift-128-euclidean`` (d=128,
L2, float32).  The vectors come from ``StaticVectorSet`` (the SIFT
analogue in ``repro.data.vectors``), made from ``--seed``; nothing is
downloaded.

    python chip_smoke.py              # one chip: PQ pass (1M) + float pass
    python chip_smoke.py --chips 4    # ubis-sharded over a 4-way model axis

Each pass loads its base vectors in batches (flushing the background
plane between batches), then runs rounds of 20k fresh inserts, 5k
deletes of live ids, a few ticks and 1,024 single-query searches (k=10)
through the serving engine.  Checks, against a live multiset this
script tracks itself:

  * recall@10 of the served results against a numpy brute force over
    the live vectors reaches the pass's floor, every round;
  * acknowledged inserts, used as queries, return their own id first;
  * no deleted id is ever returned (deleted vectors are queried too);
  * ``kernel_fallback`` is 0 and the compiled search program holds
    ``tpu_custom_call`` (the Pallas kernels, not their jnp twins).

The lines before the last describe this one smoke run; they are not
benchmark numbers.  The last line of stdout is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every check passed
on a TPU.  Without a TPU, or outside a checkout of the repository, the
script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
SRC = ROOT / "src"

# Recall@10 floors against the numpy brute force, from CPU rehearsals
# on the jnp twins (PERF.md, "Cells").  Float pass: at a tenth of the
# size with as many vectors per data cluster (so as many postings per
# cluster under nprobe=32) it measured 0.64-0.69.  PQ pass: with the
# real 64 clusters (the codebook then has ~4 centroids per cluster per
# subspace) it measured 0.18-0.19 at 100k, 0.12 at 300k, 0.10 at 500k,
# which extrapolates to ~0.07 at 1M.  On these isotropic clusters the
# true 10-NN sit barely closer than the rest, so the PQ floor separates
# little; the read-back check (every fresh insert finds itself first)
# is the sharp one for the quant plane.
RECALL_FLOOR_PQ = 0.05
RECALL_FLOOR_FLOAT = 0.5

# background batch and cache drain sized so a 1M load settles in a few
# hundred ticks (the driver defaults, 4 ops and 256 vectors per tick,
# are sized for the 8k-vector CPU benchmarks)
DRIVER_KW = dict(bg_ops_per_round=64, drain_per_tick=1024)


def sift_config(**overrides):
    """The SIFT1M deployment: d=128, L2, float32, PQ m=16 x 256."""
    from repro.core import UBISConfig
    kw = dict(dim=128, max_postings=32768, capacity=96, l_min=10, l_max=80,
              nprobe=32, max_ids=1 << 21, use_pq=True, pq_m=16, pq_ksub=256,
              rerank_k=64, use_pallas="auto")
    kw.update(overrides)
    return UBISConfig(**kw)


def numpy_topk(vecs: np.ndarray, live: np.ndarray, queries: np.ndarray,
               k: int, chunk: int = 64) -> np.ndarray:
    """Exact top-k ids over ``vecs[live]`` by plain numpy (the
    reference): scores ``||v||^2 - 2 q.v``, ties by id.  Query chunks
    run on a few threads (numpy releases the GIL in both steps)."""
    x = vecs[live]
    xn = np.einsum("ij,ij->i", x, x)

    def top(s):
        sc = xn[None, :] - 2.0 * (queries[s:s + chunk] @ x.T)
        part = np.argpartition(sc, k, axis=1)[:, :k]
        rows = np.arange(len(part))[:, None]
        order = np.lexsort((live[part], sc[rows, part]), axis=1)
        return live[part[rows, order]]

    with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
        return np.concatenate(list(pool.map(
            top, range(0, len(queries), chunk))))


def run_pass(label: str, cfg, *, n_base: int, seed: int = 0,
             engine: str = "ubis", engine_kw: dict | None = None,
             rounds: int = 5, round_insert: int = 20_000,
             round_delete: int = 5_000, n_queries: int = 1024,
             readback: int = 200, k: int = 10, load_batch: int = 131_072,
             round_ticks: int = 8, search_batch: int = 64,
             recall_floor: float = 0.0, n_clusters: int = 64,
             log=print) -> tuple:
    """One pass of the smoke traffic over one index.  Returns (failed
    checks, index); the list is empty when every check passed.
    ``n_clusters`` shapes the data (the recall floors were rehearsed
    with fewer clusters at a tenth of the size, see RECALL_FLOOR_*)."""
    import jax
    import jax.numpy as jnp
    from repro.api import make_index
    from repro.core import metrics
    from repro.data.vectors import StaticVectorSet
    from repro.serving import ServingConfig, ServingEngine

    fails: list = []
    rng = np.random.default_rng((seed, 1))
    t0 = time.perf_counter()
    data = StaticVectorSet(n=n_base + rounds * round_insert, dim=cfg.dim,
                           n_clusters=n_clusters, seed=seed)
    vecs = data.vectors
    base, fresh = data.order[:n_base], data.order[n_base:]
    log(f"[{label}] data: {len(vecs)} vectors d={cfg.dim} made in "
        f"{time.perf_counter() - t0:.1f}s")

    # k-means and PQ seeds: an eighth of the base, and never fewer than
    # the 20k-vector training sample the build takes
    n_seed = min(n_base, max(n_base // 8, 20_000))
    t0 = time.perf_counter()
    index = make_index(engine, cfg, vecs[base[:n_seed]], seed=seed,
                       **(engine_kw or {}))
    jax.block_until_ready(index.state)
    t_setup = time.perf_counter() - t0
    q_shape = jax.ShapeDtypeStruct((search_batch, cfg.dim), jnp.float32)
    t0 = time.perf_counter()
    if engine == "ubis":
        from repro.core.search import search as search_fn
        compiled = search_fn.lower(index.state, cfg, q_shape, k).compile()
    else:
        from repro.core.sharded import make_sharded_search
        compiled = make_sharded_search(cfg, index.mesh, k=k,
                                       nprobe=cfg.nprobe).lower(
            index.state, q_shape).compile()
    t_compile = time.perf_counter() - t0
    has_kernels = "tpu_custom_call" in compiled.as_text()
    log(f"[{label}] setup_s={t_setup:.2f} (make_index) "
        f"search_compile_s={t_compile:.2f} "
        f"search_program_has_tpu_custom_call={has_kernels}")
    if jax.default_backend() == "tpu" and not has_kernels:
        fails.append(f"{label}: search program holds no tpu_custom_call")

    alive = np.zeros(len(vecs), bool)
    deleted = np.zeros(len(vecs), bool)

    def insert(ids: np.ndarray) -> np.ndarray:
        """Insert until every job is acknowledged; returns the ids."""
        pending = ids
        for _ in range(4):
            r = index.insert(vecs[pending], pending)
            if r.rejected == 0:
                alive[pending] = True
                return ids
            # rejected jobs are absent from the id map: resubmit them
            # once the background plane has caught up
            held = np.asarray(index.state.id_loc)[pending] != -1
            if int((~held).sum()) != r.rejected:
                fails.append(f"{label}: {r.rejected} rejected but "
                             f"{int((~held).sum())} ids absent")
            alive[pending[held]] = True
            pending = pending[~held]
            index.flush()
        fails.append(f"{label}: {len(pending)} inserts never acknowledged")
        return ids[alive[ids]]

    t0 = time.perf_counter()
    for off in range(0, n_base, load_batch):
        t1 = time.perf_counter()
        insert(base[off:off + load_batch])
        t2 = time.perf_counter()
        ticks = index.flush()
        log(f"[{label}] load {min(off + load_batch, n_base):>8}: insert_s="
            f"{t2 - t1:.1f} flush_s={time.perf_counter() - t2:.1f} "
            f"flush_ticks={ticks}")
    t_load = time.perf_counter() - t0
    log(f"[{label}] load_s={t_load:.1f} live={index.live_count()} "
        f"tracked={int(alive.sum())} device_bytes={index.memory_bytes()}")
    if index.live_count() != int(alive.sum()):
        fails.append(f"{label}: live_count {index.live_count()} != "
                     f"{int(alive.sum())} acknowledged")

    serving = ServingEngine(index, ServingConfig(
        search_batch=search_batch, default_k=k, tick_every=0))

    def serve(queries: np.ndarray) -> np.ndarray:
        tickets = [serving.submit_search(q, k) for q in queries]
        serving.drain()
        found = np.concatenate([t.result().ids for t in tickets])
        hit = found[found >= 0]
        if deleted[hit].any():
            fails.append(f"{label}: served {int(deleted[hit].sum())} "
                         "deleted ids")
        return found

    recalls, n_readback = [], 0
    for rd in range(rounds):
        t0 = time.perf_counter()
        new = insert(fresh[rd * round_insert:(rd + 1) * round_insert])
        gone = rng.choice(np.flatnonzero(alive), round_delete,
                          replace=False)
        r = index.delete(gone)
        if r.deleted != len(gone):
            fails.append(f"{label}: deleted {r.deleted} of {len(gone)}")
        alive[gone] = False
        deleted[gone] = True
        for _ in range(round_ticks):
            index.tick()
        t_upd = time.perf_counter() - t0

        t0 = time.perf_counter()
        queries = data.queries(n_queries, seed=1000 + rd)
        found = serve(queries)
        t_srv = time.perf_counter() - t0
        true = numpy_topk(vecs, np.flatnonzero(alive), queries, k)
        rec = metrics.recall_at_k(found, true)
        recalls.append(rec)
        # read-back: fresh acknowledged inserts find themselves first
        kept = new[alive[new]]
        rb = rng.choice(kept, min(readback, len(kept)), replace=False)
        rb_found = serve(vecs[rb])
        rb_ok = int((rb_found[:, 0] == rb).sum())
        n_readback += len(rb)
        if rb_ok != len(rb):
            fails.append(f"{label}: round {rd}: {len(rb) - rb_ok} of "
                         f"{len(rb)} read-backs missed rank 1")
        # deleted vectors, queried directly, never come back
        serve(vecs[gone[:readback]])
        log(f"[{label}] round {rd}: +{len(new)} -{len(gone)} "
            f"update_s={t_upd:.1f} serve_s={t_srv:.1f} "
            f"recall@{k}={rec:.4f} (floor {recall_floor}) "
            f"readback_rank1={rb_ok}/{len(rb)} live={index.live_count()}")
        if rec < recall_floor:
            fails.append(f"{label}: round {rd} recall {rec:.4f} < floor "
                         f"{recall_floor}")

    ticks = index.flush()
    st = index.stats
    log(f"[{label}] host seconds: insert={st['insert_time']:.1f} "
        f"delete={st['delete_time']:.1f} tick={st['bg_time']:.1f} "
        f"search={st['search_time']:.1f}; bg_ops={st['bg_ops']:g} "
        f"queries={st['queries']:g}")
    fallback = index.obs.counter("kernel_fallback").value
    log(f"[{label}] flush_ticks={ticks} live={index.live_count()} "
        f"tracked={int(alive.sum())} readbacks={n_readback} "
        f"min_recall@{k}={min(recalls):.4f} kernel_fallback={fallback:g}")
    if index.live_count() != int(alive.sum()):
        fails.append(f"{label}: live_count {index.live_count()} != "
                     f"{int(alive.sum())} tracked after flush")
    if fallback != 0:
        fails.append(f"{label}: kernel_fallback={fallback:g}")
    return fails, index


def one_chip(seed: int, log=print) -> list:
    fails, _ = run_pass("pq", sift_config(), n_base=1_000_000, seed=seed,
                        engine_kw=DRIVER_KW, recall_floor=RECALL_FLOOR_PQ,
                        log=log)
    n_float = 250_000
    log(f"[float] cut: the float pass loads {n_float} base vectors, "
        "not 1,000,000")
    f2, _ = run_pass("float", sift_config(use_pq=False), n_base=n_float,
                     seed=seed + 1, engine_kw=DRIVER_KW,
                     recall_floor=RECALL_FLOOR_FLOAT, log=log)
    return fails + f2


def four_chips(seed: int, log=print) -> list:
    import jax
    mesh = jax.make_mesh((1, 4), ("data", "model"))
    cfg = sift_config(max_postings=65520, max_ids=1 << 22)
    fails, index = run_pass(
        "sharded", cfg, n_base=2_000_000, seed=seed, engine="ubis-sharded",
        engine_kw=dict(DRIVER_KW, mesh=mesh, migrate_per_tick=256),
        recall_floor=RECALL_FLOOR_PQ, log=log)
    shards = index.state.vectors.addressable_shards
    sizes = sorted((s.device.id, s.data.shape[0]) for s in shards)
    log(f"[sharded] state.vectors shards (device, postings): {sizes}")
    want = cfg.max_postings // 4
    if (len({d for d, _ in sizes}) != 4
            or any(n != want for _, n in sizes)):
        fails.append(f"sharded: vectors not split {want} per device")
    return fails


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}; run it from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    import jax
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: JAX finds no TPU (backend "
              f"{jax.default_backend()!r}); nothing was run",
              file=sys.stderr)
        return 3
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    devices = jax.devices()
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 3
    print(f"chip_smoke: one smoke run on {len(devices)} x "
          f"{devices[0].device_kind} (not a benchmark); compile cache "
          f"{cache}", flush=True)
    t0 = time.perf_counter()
    log = lambda s: print(s, flush=True)
    fails = (four_chips(args.seed, log) if args.chips == 4
             else one_chip(args.seed, log))
    print(f"chip_smoke: total_s={time.perf_counter() - t0:.1f}", flush=True)
    if fails:
        for f in fails:
            print(f"FAILED: {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
