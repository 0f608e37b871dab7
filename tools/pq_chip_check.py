#!/usr/bin/env python3
"""Quant-plane parity check: device PQ codes against a numpy encoder.

Fills a posting pool at the SIFT1M smoke's shapes (``chip_smoke.py``:
32,768 postings x 96 slots x d=128, PQ m=16 x 256) with clustered
vectors, then checks, on a sample of postings, that the codes each
device program writes equal a float64 numpy nearest-centroid encode
of the stored vectors (the invariant ``tests/test_pq.py`` checks at
small sizes on the CPU):

  * ``pq.encode_tiles`` over the whole pool;
  * ``ref.exact_argmin`` on near-ties below bfloat16 resolution, over
    a stored score array and fused behind the score matmul (and, for
    comparison, ``jnp.argmin`` as XLA compiles it there);
  * ``pq.retrain_round`` with half of the postings pinned to the evicted
    codebook slot (the full re-encode branch) and with a few pinned
    (the gathered branch); unpinned postings must keep their codes;
  * ``update.batched_append`` of a 16,384-vector batch.

Prints one line per check with the share of codes equal to the numpy
encode; exits 1 when any share is below ``--min-agree``.

``--scan`` instead times ``ops.pq_scan_topk`` alone at the PQ search
batch's shapes (64 queries x nprobe 32, m=16 x 256, 96 slots, the
rerank's k=64, every posting of the pool, ``pq_versions`` codebook
slots), after checking its output bit for bit: against the jnp twin on
integer tables, and against a numpy float32 sum over subspaces in
order on non-integer ones.  Prints the milliseconds per call (median
and quartiles of single calls waited on one by one, and the mean of a
back-to-back run); exits 1 on any mismatch.

    python tools/pq_chip_check.py            # smoke shapes (run on a TPU)
    python tools/pq_chip_check.py --small    # 1,024 postings, any backend
    python tools/pq_chip_check.py --scan     # the ADC kernel alone
"""
from __future__ import annotations

import argparse
import pathlib
import sys

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]


def np_encode(cb: np.ndarray, x: np.ndarray) -> np.ndarray:
    """Nearest centroid per subspace in float64: (m, ksub, dsub),
    (N, d) -> (N, m)."""
    m, ksub, dsub = cb.shape
    xs = x.astype(np.float64).reshape(len(x), m, dsub)
    c = cb.astype(np.float64)
    sc = (np.einsum("jkd,jkd->jk", c, c)[None]
          - 2.0 * np.einsum("njd,jkd->njk", xs, c))
    return sc.argmin(-1)


def agree(codes: np.ndarray, cbs: np.ndarray, slots: np.ndarray,
          vecs: np.ndarray) -> float:
    """Share of codes equal to the numpy encode under each posting's
    slot.  codes (P, m, C); cbs (V, m, ksub, dsub); slots (P,);
    vecs (P, C, d)."""
    hits = total = 0
    for p in range(len(codes)):
        want = np_encode(cbs[slots[p]], vecs[p])          # (C, m)
        hits += int((codes[p].T == want).sum())
        total += want.size
    return hits / total


def scan_check(args) -> int:
    """Time ``ops.pq_scan_topk`` alone at the search batch's shapes,
    after checking it bit for bit (see the module docstring)."""
    import time
    import jax
    import jax.numpy as jnp
    import chip_smoke
    from repro.kernels import ops, ref

    M = 1024 if args.small else 32768
    cfg = chip_smoke.sift_config(max_postings=M)
    Q, P, m, ksub = 64, cfg.nprobe, cfg.pq_m, cfg.pq_ksub
    C, k, V = cfg.capacity, cfg.rerank_k, cfg.pq_versions
    print(f"pq_chip_check --scan: backend={jax.default_backend()} "
          f"device={jax.devices()[0].device_kind} Q={Q} P={P} m={m} "
          f"ksub={ksub} C={C} k={k} M={M} V={V}", flush=True)
    rng = np.random.default_rng(args.seed)
    codes = rng.integers(0, ksub, (M, m, C)).astype(np.uint8)
    slot = rng.integers(0, V, M).astype(np.int32)
    slot_valid = rng.random((M, C)) < 0.8
    vis = rng.random(M) < 0.95
    probe = np.stack([rng.choice(M, P, replace=False)
                      for _ in range(Q)]).astype(np.int32)
    dev = [jnp.asarray(x) for x in (codes, slot, slot_valid, vis, probe)]

    def call(backend):
        return jax.jit(lambda lut, cd, sl, sv, vs, pr: ops.pq_scan_topk(
            lut, cd, sl, sv, vs, pr, k=k, backend=backend))

    kernel, twin = call(args.backend), call("off")
    int_luts = rng.integers(0, 4096, (Q, V, m, ksub)).astype(np.float32)
    float_luts = (rng.random((Q, V, m, ksub)) * 4096).astype(np.float32)
    ok = True
    s1, i1 = kernel(jnp.asarray(int_luts), *dev)
    s2, i2 = twin(jnp.asarray(int_luts), *dev)
    same = (np.array_equal(np.asarray(s1), np.asarray(s2))
            and np.array_equal(np.asarray(i1), np.asarray(i2)))
    print(f"  integer tables vs the jnp twin: bit-identical={same}",
          flush=True)
    ok &= same
    s1, i1 = kernel(jnp.asarray(float_luts), *dev)
    s3, i3 = ref.np_pq_scan_topk(float_luts, codes, slot,
                                 slot_valid & vis[:, None],
                                 np.ones((Q, P), np.int32), probe, k)
    same = (np.array_equal(np.asarray(s1), s3)
            and np.array_equal(np.asarray(i1), i3))
    print(f"  float tables vs numpy in-order f32 sum: bit-identical={same}",
          flush=True)
    ok &= same

    luts = jnp.asarray(float_luts)
    for _ in range(3):
        jax.block_until_ready(kernel(luts, *dev))
    one = []
    for _ in range(args.calls):
        t0 = time.perf_counter()
        jax.block_until_ready(kernel(luts, *dev))
        one.append((time.perf_counter() - t0) * 1e3)
    t0 = time.perf_counter()
    outs = [kernel(luts, *dev) for _ in range(args.calls)]
    jax.block_until_ready(outs)
    back = (time.perf_counter() - t0) * 1e3 / args.calls
    q1, q2, q3 = np.percentile(one, [25, 50, 75])
    print(f"  ms per call: median {q2:.4f} (quartiles {q1:.4f}-{q3:.4f}, "
          f"{args.calls} calls waited on one by one); back to back "
          f"{back:.4f}", flush=True)
    print(f"pq_chip_check --scan: {'ok' if ok else 'MISMATCH'}", flush=True)
    return 0 if ok else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--small", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sample", type=int, default=96,
                    help="postings compared with numpy per check")
    ap.add_argument("--min-agree", type=float, default=0.97)
    ap.add_argument("--scan", action="store_true",
                    help="time and check the ADC scan kernel alone")
    ap.add_argument("--calls", type=int, default=50,
                    help="timed kernel calls (--scan)")
    ap.add_argument("--backend", default="auto",
                    help="kernel backend of the timed calls (--scan)")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    if args.scan:
        return scan_check(args)
    import jax
    import jax.numpy as jnp
    import chip_smoke
    from repro.core import update
    from repro.core.types import empty_state
    from repro.core.update import dataclasses_replace
    from repro.kernels import ref
    from repro.quant import pq

    M = 1024 if args.small else 32768
    cfg = chip_smoke.sift_config(max_postings=M)
    C, d = cfg.capacity, cfg.dim
    print(f"pq_chip_check: backend={jax.default_backend()} "
          f"device={jax.devices()[0].device_kind} M={M} C={C} d={d} "
          f"m={cfg.pq_m}", flush=True)
    key = jax.random.key(args.seed)
    kc, kv, ka, kb, kr, ks = jax.random.split(key, 6)

    @jax.jit
    def make_pool(kc, kv, ka):
        # 64 Gaussian clusters, centres at scale 8 (StaticVectorSet)
        centres = jax.random.normal(kc, (64, d)) * 8.0
        a = jax.random.randint(ka, (M, C), 0, 64)
        return centres[a] + jax.random.normal(kv, (M, C, d))

    vectors = make_pool(kc, kv, ka)
    train = vectors.reshape(M * C, d)[:20_000]
    cb0 = jax.jit(lambda v, k: pq.init_codebooks(
        v, cfg.pq_m, cfg.pq_ksub, cfg.kmeans_iters, k,
        backend=cfg.use_pallas))(train, kb)
    rng = np.random.default_rng(args.seed)
    sample = np.sort(rng.choice(M, min(args.sample, M), replace=False))
    vec_s = np.asarray(vectors[sample])
    results = []

    def report(name, share):
        results.append((name, share))
        print(f"  {name}: agree={share:.6f}", flush=True)

    full = jax.jit(pq.encode_tiles)(cb0, vectors)
    report("encode_tiles whole pool",
           agree(np.asarray(full[sample]), np.asarray(cb0)[None],
                 np.zeros(M, int)[sample], vec_s))
    # near-ties below bfloat16 resolution (scores near -500, where a
    # bf16 step is 2-4, with the best few centroids a few units apart):
    # jnp.argmin as XLA compiles it for this backend, over a stored
    # array and fused behind the score matmul (reported, not checked),
    # and ref.exact_argmin in both forms (checked)
    pts = 8.0 + jax.random.normal(ks, (4096, 8))
    cents = pts[:256] + 0.5 * jax.random.normal(kr, (256, 8))

    def scores(p, c):
        return (jnp.sum(c * c, -1)[None]
                - 2.0 * jnp.matmul(p, c.T, precision=ref.HIGHEST))

    sc = np.asarray(jax.jit(scores)(pts, cents), np.float64)
    want = np.asarray(np_encode(np.asarray(cents)[None],
                                np.asarray(pts))[:, 0])
    for name, fn in (("jnp.argmin", lambda x: jnp.argmin(x, -1)),
                     ("ref.exact_argmin", ref.exact_argmin)):
        stored = float((np.asarray(jax.jit(fn)(
            jnp.asarray(sc, jnp.float32))) == sc.argmin(-1)).mean())
        fused = float((np.asarray(jax.jit(
            lambda p, c: fn(scores(p, c)))(pts, cents)) == want).mean())
        if name == "jnp.argmin":
            print(f"  {name} (not checked): stored agree={stored:.6f} "
                  f"fused agree={fused:.6f}", flush=True)
        else:
            report(f"{name} on stored near-ties", stored)
            report(f"{name} fused behind the score matmul", fused)

    st = empty_state(cfg)
    cbs = st.pq_codebooks.at[0].set(cb0).at[1].set(cb0)
    for label, pinned in (("half pinned", np.arange(M) % 2 == 1),
                          ("48 pinned", np.isin(np.arange(M),
                                                sample[::2]))):
        slot = jnp.asarray(pinned.astype(np.int32))
        s = dataclasses_replace(
            st, vectors=vectors, allocated=jnp.ones((M,), bool),
            slot_valid=jnp.ones((M, C), bool), used=jnp.full((M,), C),
            lengths=jnp.full((M,), C), codes=full,
            pq_codebooks=cbs, pq_active=jnp.array(0, jnp.int32),
            pq_posting_slot=slot)
        out = pq.retrain_round(s, cfg, kr)
        new_cbs = np.asarray(out.pq_codebooks)
        out_codes = np.asarray(out.codes[sample])
        out_slots = np.asarray(out.pq_posting_slot)[sample]
        pin_s = pinned[sample]
        report(f"retrain_round {label}: re-encoded postings",
               agree(out_codes[pin_s], new_cbs, out_slots[pin_s],
                     vec_s[pin_s]))
        same = np.array_equal(out_codes[~pin_s],
                              np.asarray(full[sample])[~pin_s])
        report(f"retrain_round {label}: untouched postings kept",
               float(same))

    # one insert round's append: J fresh vectors spread over postings
    # that have room (half the slots freed)
    J = 16384
    s = dataclasses_replace(
        st, vectors=vectors, allocated=jnp.ones((M,), bool),
        slot_valid=jnp.zeros((M, C), bool), used=jnp.zeros((M,), jnp.int32),
        lengths=jnp.zeros((M,), jnp.int32), pq_codebooks=cbs,
        pq_posting_slot=jnp.asarray((np.arange(M) % 2).astype(np.int32)))
    xs = make_pool(ks, kv, ka)[:J // C + 1].reshape(-1, d)[:J]
    pids = jnp.asarray(rng.integers(0, M, J).astype(np.int32))
    ids = jnp.arange(J, dtype=jnp.int32)
    s2, ok, flat = jax.jit(update.batched_append, static_argnums=1)(
        s, cfg, xs, ids, pids, jnp.ones((J,), bool))
    flat = np.asarray(flat)[np.asarray(ok)]
    written = np.asarray(s2.codes).transpose(0, 2, 1).reshape(M * C, -1)
    slots = np.asarray(s2.pq_posting_slot)[flat // C]
    x_ok = np.asarray(xs)[np.asarray(ok)]
    cb_np = np.asarray(cbs)
    hits = sum(int((written[f] == np_encode(cb_np[sl], x[None])[0]).sum())
               for f, sl, x in zip(flat[:4096], slots, x_ok))
    report("batched_append codes", hits / (min(len(flat), 4096) * cfg.pq_m))

    bad = [n for n, a in results if a < args.min_agree]
    print(f"pq_chip_check: {len(results) - len(bad)}/{len(results)} "
          f"checks at or above {args.min_agree}", flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
