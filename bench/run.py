#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload sift1m-pq.search --seed 7 \\
        --seconds 30 --trace 0

The cell, its deployment, traffic mix, settings and per-layer metrics
are found by name from ``BENCHMARK.json`` at the root of the checkout
(see ``bench/harness/cell.py``).  The program under test is the
checkout's ``src/repro``: the index from ``make_index("ubis", ...)``
served through ``ServingEngine`` on the real clock.

Set-up (``setup_s``) is everything before the window opens: making the
data from the seed, building the index, loading the corpus through the
program's insert and tick path, deleting a few small clusters and
inserting as many fresh vectors (so a merge runs), one of each request,
and serving the mix for ``warmup_s``.  Then the window runs
``--seconds``; with ``--trace 1`` the profiler runs from before the
warm-up until every search of the window is answered, and the
per-layer metrics, read over the window, are printed in place of the
end-to-end ones.

The last line of stdout is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, ..., ``checks`` last); the numbers
compared, each with its limit, are also the last lines of stderr.
Without a TPU, or with fewer chips than the cell asks for, it exits 3
and prints no result.  ``--control`` also judges the bfloat16 brute
force in the program's place (the benchmark's own runs never pass it).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT / "src"), str(BENCH)]
    from harness.cell import cell_files, read_json, run_cell
    manifest = read_json(ROOT / "BENCHMARK.json")
    files = cell_files(manifest, args.workload)

    import jax
    devices = jax.devices()
    chips = int(files["workload"]["chips"])
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"bench: the cell needs {chips} TPU chip(s); JAX finds "
              f"{len(devices)} {devices[0].platform} device(s). "
              "Nothing was run.", file=sys.stderr)
        return 3
    peaks = read_json(BENCH / "peaks.json")
    kind = devices[0].device_kind
    if kind not in peaks:
        print(f"bench: no peaks for device kind {kind!r} in peaks.json",
              file=sys.stderr)
        return 3
    files["peak"] = peaks[kind]

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    out = run_cell(files, seed=args.seed, seconds=args.seconds,
                   trace=bool(args.trace), t_start=T_START,
                   control=args.control)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
