"""Per-kernel roofline report for the UBIS Pallas kernel suite.

For every kernel in ``src/repro/kernels`` (the search/build hot loop:
centroid scoring, posting scans, fused top-k variants, ADC scans,
k-means assignment, flash attention) this module derives an *analytic*
roofline row — FLOPs, HBM bytes, arithmetic intensity, compute/memory
time at TPU v5e peaks, and the roofline fraction (attainable share of
peak FLOPs given the memory bound) — and measures wall time on the
selected backend for an achieved-vs-predicted column.

Two honesty metrics matter here:

* ``useful_flops`` vs ``flops``: the PQ ADC kernels execute a one-hot
  (C, ksub) @ (ksub, 1) matmul per subspace on the MXU — ``2*C*ksub``
  executed FLOPs for ``C`` useful adds.  The executed count feeds the
  compute-time estimate; the useful count is what recall per second
  actually buys.
* fused vs unfused bytes: the ``*_topk`` kernels write ``2*Q*k`` scalars
  instead of a (Q, M) / (Q, P, C) score tensor; the rows make the HBM
  traffic that fusion removes explicit.

Run:  PYTHONPATH=src:. python -m benchmarks.roofline \
          --backend pallas --preset smoke --check --out roofline.json
``--check`` asserts every kernel module in ``src/repro/kernels``
(excluding ``__init__``/``ops``/``ref``) contributes at least one row —
the CI smoke gate that keeps this report honest as kernels are added.
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Callable, Dict, List

PEAK_FLOPS = 197e12      # bf16 FLOP/s per chip (TPU v5e)
HBM_BW = 819e9           # HBM B/s per chip

# shape presets: smoke is small enough for CPU interpret mode in CI;
# full approximates the fig5 serving configuration; misaligned pins the
# alignment-free contract (real-world d=100, odd capacity, ksub=100 —
# the wrappers pad, the fused kernels serve, nothing falls back)
PRESETS = {
    "smoke": dict(Q=8, d=128, M=128, C=128, P=4, k=8,
                  m=2, ksub=128, V=2, N=256, K=128, R=32,
                  B=1, Hq=2, Hkv=1, L=128, D=128),
    "misaligned": dict(Q=8, d=100, M=33, C=100, P=4, k=8,
                       m=4, ksub=100, V=2, N=200, K=100, R=24,
                       B=1, Hq=2, Hkv=1, L=96, D=64),
    "full": dict(Q=128, d=128, M=1024, C=256, P=32, k=64,
                 m=8, ksub=256, V=4, N=4096, K=512, R=256,
                 B=4, Hq=8, Hkv=2, L=512, D=128),
}


def _row(kernel: str, module: str, shapes: str, flops: float,
         useful_flops: float, bytes_: float) -> Dict:
    t_compute = flops / PEAK_FLOPS
    t_memory = bytes_ / HBM_BW
    return {
        "kernel": kernel,
        "module": module,
        "shapes": shapes,
        "flops": flops,
        "useful_flops": useful_flops,
        "bytes": bytes_,
        "intensity": flops / bytes_,
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "bound": "compute" if t_compute >= t_memory else "memory",
        # attainable fraction of peak FLOPs under the memory roof
        "roofline_frac": t_compute / max(t_compute, t_memory),
    }


def build_cases(p: Dict, backend: str) -> List[Dict]:
    """Construct (row, runner) cases for every kernel entry point.

    Each runner is a no-arg closure calling the ``ops`` wrapper on the
    requested backend; analytic FLOP/byte counts model the kernel's
    streaming behaviour (fused top-k outputs are 2*Q*k scalars, the
    unfused scans write the full score tensor).
    """
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops

    kq, kc, kv, kp = jax.random.split(jax.random.key(0), 4)
    Q, d, M, C, P, k = p["Q"], p["d"], p["M"], p["C"], p["P"], p["k"]
    m, ksub, V, N, K = p["m"], p["ksub"], p["V"], p["N"], p["K"]
    B, Hq, Hkv, L, D = p["B"], p["Hq"], p["Hkv"], p["L"], p["D"]
    R = p["R"]
    f32 = 4

    q = jax.random.normal(kq, (Q, d), jnp.float32)
    cents = jax.random.normal(kc, (M, d), jnp.float32)
    vis = jnp.ones((M,), bool)
    vecs = jax.random.normal(kv, (M, C, d), jnp.float32)
    slot_valid = jnp.ones((M, C), bool)
    probe = jax.random.randint(kp, (Q, P), 0, M, jnp.int32)
    luts = jax.random.normal(kq, (Q, V, m, ksub), jnp.float32)
    codes = jax.random.randint(kc, (M, m, C), 0, ksub).astype(jnp.uint8)
    pslot = jnp.zeros((M,), jnp.int32)
    pts = jax.random.normal(kv, (N, d), jnp.float32)
    kcents = jax.random.normal(kc, (K, d), jnp.float32)
    qa = jax.random.normal(kq, (B, Hq, L, D), jnp.float32)
    ka = jax.random.normal(kc, (B, Hkv, L, D), jnp.float32)
    va = jax.random.normal(kv, (B, Hkv, L, D), jnp.float32)

    cases: List[Dict] = []

    def add(row: Dict, fn: Callable):
        row["backend"] = backend
        cases.append({"row": row, "fn": fn})

    # --- phase 1: centroid scoring --------------------------------------
    add(_row("centroid_score", "centroid_score", f"Q={Q} M={M} d={d}",
             flops=2.0 * Q * M * d, useful_flops=2.0 * Q * M * d,
             bytes_=f32 * (Q * d + M * d + Q * M)),
        lambda: ops.centroid_score(q, cents, vis, backend=backend))
    add(_row("centroid_topk", "centroid_topk",
             f"Q={Q} M={M} d={d} k={k}",
             flops=2.0 * Q * M * d + 1.0 * Q * k * M,
             useful_flops=2.0 * Q * M * d,
             bytes_=f32 * (Q * d + M * d + 2 * Q * k)),
        lambda: ops.centroid_topk(q, cents, vis, k=k, backend=backend))

    # --- phase 2: float posting scans -----------------------------------
    add(_row("posting_scan", "posting_scan", f"Q={Q} V={M * C} d={d}",
             flops=2.0 * Q * M * C * d, useful_flops=2.0 * Q * M * C * d,
             bytes_=f32 * (Q * d + M * C * d + Q * M * C)),
        lambda: ops.posting_scan(q, vecs, slot_valid, backend=backend))
    add(_row("posting_scan_topk", "posting_scan",
             f"Q={Q} P={P} C={C} d={d} k={k}",
             flops=2.0 * Q * P * C * d + 1.0 * Q * P * k * C,
             useful_flops=2.0 * Q * P * C * d,
             bytes_=f32 * (Q * d + Q * P * C * d + 2 * Q * k)),
        lambda: ops.posting_scan_topk(q, vecs, slot_valid, vis, probe,
                                      k=k, backend=backend))

    # --- quant plane: ADC scans (one-hot MXU trick: 2*C*ksub executed
    # FLOPs per (query, probe, subspace) for C useful adds) --------------
    adc_exec = 2.0 * Q * P * m * C * ksub
    adc_useful = 2.0 * Q * P * m * C
    adc_bytes = Q * P * (m * C + f32 * m * ksub)  # codes u8 + lut tile
    add(_row("pq_scan_topk", "pq_scan",
             f"Q={Q} P={P} C={C} m={m} ksub={ksub} k={k}",
             flops=adc_exec + 1.0 * Q * P * k * C,
             useful_flops=adc_useful,
             bytes_=adc_bytes + f32 * 2 * Q * k),
        lambda: ops.pq_scan_topk(luts, codes, pslot, slot_valid, vis,
                                 probe, k=k, backend=backend))

    # --- rerank: fused candidate gather + exact ||v||^2 - 2 q.v + ADC
    # passthrough + top-k (replaces the XLA gather+einsum rerank tail) --
    spilled = jnp.zeros((M,), bool)
    cand = jax.random.randint(kp, (Q, R), 0, M * C, jnp.int32)
    adc = jax.random.normal(kq, (Q, R), jnp.float32)
    add(_row("rerank_topk", "rerank",
             f"Q={Q} R={R} d={d} k={k}",
             flops=4.0 * Q * R * d + 1.0 * Q * R * k,
             useful_flops=4.0 * Q * R * d,
             bytes_=f32 * (Q * d + Q * R * d + 2 * Q * R + 2 * Q * k)),
        lambda: ops.rerank_topk(q, vecs, spilled, cand, adc,
                                k=min(k, R), backend=backend))

    # --- build/maintenance: k-means assignment --------------------------
    add(_row("kmeans_assign", "kmeans_assign", f"N={N} K={K} d={d}",
             flops=2.0 * N * K * d, useful_flops=2.0 * N * K * d,
             bytes_=f32 * (N * d + K * d + 2 * N)),
        lambda: ops.kmeans_assign(pts, kcents, backend=backend))

    # --- serving: attention over the request batch ----------------------
    # causal: half the (L, L) score square does useful work
    add(_row("flash_attention", "flash_attention",
             f"B={B} Hq={Hq} L={L} D={D}",
             flops=4.0 * B * Hq * L * L * D * 0.5,
             useful_flops=4.0 * B * Hq * L * L * D * 0.5,
             bytes_=f32 * (B * (Hq + 2 * Hkv) * L * D + B * Hq * L * D)),
        lambda: ops.flash_attention(qa, ka, va, causal=True,
                                    backend=backend))
    return cases


def measure(fn: Callable, iters: int = 3) -> float:
    """Best-of-N wall seconds, compile excluded (first call warms up)."""
    import jax
    jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def kernel_modules() -> List[str]:
    """Kernel module names under ``repro.kernels`` that must each have
    at least one roofline row (``ops``/``ref``/``__init__`` excluded)."""
    import pkgutil
    import repro.kernels as pkg
    skip = {"ops", "ref"}
    return sorted(m.name for m in pkgutil.iter_modules(pkg.__path__)
                  if m.name not in skip)


def check_rows(rows: List[Dict]) -> None:
    covered = {r["module"] for r in rows}
    missing = [m for m in kernel_modules() if m not in covered]
    if missing:
        raise SystemExit(
            f"roofline --check: kernel modules without a row: {missing}")


def render(rows: List[Dict]) -> str:
    head = ("| kernel | shapes | GFLOP | useful | MiB | FLOP/B | "
            "bound | roofline | ms |")
    lines = [head, "|---|---|---|---|---|---|---|---|---|"]
    for r in rows:
        lines.append(
            "| {} | {} | {:.3f} | {:.3f} | {:.2f} | {:.1f} | {} | "
            "{:.2f} | {} |".format(
                r["kernel"], r["shapes"], r["flops"] / 1e9,
                r["useful_flops"] / 1e9, r["bytes"] / 2 ** 20,
                r["intensity"], r["bound"], r["roofline_frac"],
                "{:.2f}".format(r["measured_ms"])
                if r.get("measured_ms") is not None else "-"))
    return "\n".join(lines)


def main(argv=None) -> List[Dict]:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--backend", default="auto",
                    choices=("auto", "off", "on"))
    ap.add_argument("--preset", default="smoke", choices=sorted(PRESETS))
    ap.add_argument("--out", default=None, help="write rows as JSON")
    ap.add_argument("--no-measure", action="store_true",
                    help="analytic columns only (skip timing)")
    ap.add_argument("--check", action="store_true",
                    help="fail unless every kernel module has a row")
    args = ap.parse_args(argv)

    cases = build_cases(PRESETS[args.preset], args.backend)
    rows = []
    for c in cases:
        r = c["row"]
        if args.no_measure:
            r["measured_ms"] = None
        else:
            t = measure(c["fn"])
            r["measured_ms"] = t * 1e3
            # predicted-vs-achieved only means something on real TPU;
            # on CPU interpret it is just a magnitude sanity column
            pred = max(r["t_compute_s"], r["t_memory_s"])
            r["achieved_frac"] = pred / t if t > 0 else 0.0
        rows.append(r)

    if args.check:
        check_rows(rows)
    print(render(rows))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
        print(f"wrote {len(rows)} rows -> {args.out}")
    return rows


if __name__ == "__main__":
    import sys
    sys.path.insert(0, ".")
    sys.path.insert(0, "src")
    main()
