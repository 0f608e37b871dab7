"""Benchmark entry point: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV per the harness contract, plus
the full per-figure rows.  The per-kernel roofline report is its own
entry point (``python -m benchmarks.roofline``).

    PYTHONPATH=src python -m benchmarks.run [--full] [--figures fig5,...]
"""
from __future__ import annotations

import argparse
import json
import sys
import time

sys.path.insert(0, ".")  # repo root (benchmarks package)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--figures",
                    default="fig5,fig6,fig7,table4,fig8,fig9,figpq,"
                            "figengines,figskew,figmem,figserve")
    ap.add_argument("--out", default="bench_results.json")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import figures, figserve
    from benchmarks.common import FULL, QUICK
    scale = FULL if args.full else QUICK

    fns = {
        "fig5": figures.fig5_posting_cdf,
        "fig6": figures.fig6_streaming_recall,
        "fig7": figures.fig7_streaming_throughput,
        "table4": figures.table4_full_update,
        "fig8": figures.fig8_fg_bg_ratio,
        "fig9": figures.fig9_balance_factor,
        "figpq": figures.figpq_memory_recall,
        "figengines": figures.figengines_comparison,
        "figskew": figures.figskew_skewed_stream,
        "figdist": figures.figdist_cluster_stream,
        "figmem": figures.figmem_cold_tier,
        "figserve": figserve.figserve_serving,
    }
    wanted = [f.strip() for f in args.figures.split(",") if f.strip()]
    all_rows = []
    print("name,us_per_call,derived")
    for name in wanted:
        t0 = time.perf_counter()
        rows = fns[name](scale)
        dt = time.perf_counter() - t0
        all_rows.extend(rows)
        derived = _headline(name, rows)
        print(f"{name},{dt * 1e6 / max(len(rows), 1):.0f},{derived}",
              flush=True)
    with open(args.out, "w") as f:
        json.dump(all_rows, f, indent=1)
    print(f"# wrote {len(all_rows)} rows to {args.out}")
    # echo rows for the log
    for r in all_rows:
        print("  " + ",".join(f"{k}={v}" for k, v in r.items()))


def _headline(name: str, rows) -> str:
    """One derived number per figure — the paper's comparison axis."""
    if not rows:
        return "skipped"
    by_mode = {}
    for r in rows:
        by_mode.setdefault(r.get("mode", r.get("balance_factor",
                                               r.get("fg"))), []).append(r)
    try:
        if name == "fig5":
            u = [r["small_frac"] for r in by_mode["ubis"]][-1]
            s = [r["small_frac"] for r in by_mode["spfresh"]][-1]
            return f"small_frac ubis={u} spfresh={s}"
        if name == "fig6":
            u = [r["recall"] for r in by_mode["ubis"] if r["recall"] >= 0]
            s = [r["recall"] for r in by_mode["spfresh"]
                 if r["recall"] >= 0]
            return (f"mean_recall ubis={sum(u)/len(u):.3f} "
                    f"spfresh={sum(s)/len(s):.3f}")
        if name == "fig7":
            u = [r["tps"] for r in by_mode["ubis"]]
            s = [r["tps"] for r in by_mode["spfresh"]]
            return (f"mean_tps ubis={sum(u)/len(u):.0f} "
                    f"spfresh={sum(s)/len(s):.0f}")
        if name == "table4":
            u = by_mode["ubis"][0]
            s = by_mode["spfresh"][0]
            return (f"recall {u['recall']:.3f}vs{s['recall']:.3f} "
                    f"tps {u['tps']:.0f}vs{s['tps']:.0f}")
        if name == "fig8":
            best = max(rows, key=lambda r: r["tps"])
            return f"best fg:bg={best['fg']}:{best['bg']}"
        if name == "fig9":
            return "recall rises with f, qps falls (see rows)"
        if name == "figpq":
            fl = next(r for r in rows if r["variant"] == "float")
            best = max((r for r in rows if r["variant"] != "float"),
                       key=lambda r: r["recall"])
            return (f"{best['variant']} {best['compression_x']}x smaller, "
                    f"recall {best['recall']:.3f} vs float "
                    f"{fl['recall']:.3f}")
        if name == "figengines":
            return " ".join(f"{r['mode']}={r['final_recall']:.3f}"
                            for r in rows)
        if name == "figskew":
            last = {(r["stream"], r["rebalance"]): r for r in rows}
            on = last[("zipf", "on")]
            off = last[("zipf", "off")]
            return (f"zipf occ_ratio on={on['occ_ratio']} "
                    f"off={off['occ_ratio']} recall on={on['recall']}")
        if name == "figdist":
            last = rows[-1]
            worst = max(r["occ_ratio"] for r in rows)
            return (f"2-proc zipf occ_ratio last={last['occ_ratio']} "
                    f"worst={worst} recall={last['recall']}")
        if name == "figmem":
            by = {r["variant"]: r for r in rows}
            off_, on_ = by["tier-off"], by["tier-on"]
            ratio = off_["vec_device_mb"] / max(on_["vec_device_mb"],
                                               1e-9)
            return (f"vec_device {off_['vec_device_mb']}->"
                    f"{on_['vec_device_mb']}MB ({ratio:.1f}x) recall "
                    f"{off_['recall']:.3f}->{on_['recall']:.3f}")
        if name == "figserve":
            by = {r["mode"]: r for r in rows}
            s, b = by["sync"], by["batched"]
            return (f"qps sync={s['qps']:.0f} batched={b['qps']:.0f} "
                    f"({b['qps'] / max(s['qps'], 1e-9):.1f}x) p99 "
                    f"{s['p99_ms']:.1f}->{b['p99_ms']:.1f}ms recall "
                    f"{s['recall']:.3f}/{b['recall']:.3f}")
    except Exception as e:  # pragma: no cover
        return f"derived-error:{e}"
    return ""


if __name__ == "__main__":
    main()
