#!/usr/bin/env python3
"""Find a cell's knee: serve its mix at several search rates in one
process and print, per rate, what was offered and what kept pace.

    python3 bench/sweep.py --workload sift1m-pq.search --seed 5 \\
        --rates 400,800,1200,1600 --seconds 15 [--tick-every 1,2]

One set-up, then for each (tick_every, rate) a window of ``--seconds``
after a short settle, each drained before the next.  A rate keeps pace
when the searches due in its window are answered by its close, less
one batch.  It also prints the postings over ``l_max`` at each
window's edges and the insert ids the index refused in the window
(refusals follow a full cache), which show whether a tick cadence keeps
the background backlog level.  The knee is read from the table;
the cell's rate (0.8 of it) is written into ``bench/cells/``.  Needs a
TPU, as ``run.py`` does.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def over_l_max(proxy, l_max: int) -> int:
    return int((proxy.index.posting_lengths() > l_max).sum())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--tick-every", default="")
    ap.add_argument("--fresh-pool", type=int, default=0,
                    help="fresh vectors to make (a sweep serves many windows)")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(BENCH)]
    from harness.cell import answered, build, cell_files, read_json
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: JAX finds no TPU; nothing was run", file=sys.stderr)
        return 3
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    files = cell_files(read_json(ROOT / "BENCHMARK.json"), args.workload)
    if args.fresh_pool:
        files["mix"]["fresh_pool"] = args.fresh_pool
    log = lambda s: print(s, file=sys.stderr, flush=True)
    su = build(files, seed=args.seed, t_start=T_START, log=log)
    tr, l_max = su.traffic, su.conf["index"]["l_max"]
    rates = [float(r) for r in args.rates.split(",")]
    ticks = ([int(t) for t in args.tick_every.split(",")]
             if args.tick_every else [su.engine.cfg.tick_every])
    rows = []
    for te in ticks:
        su.engine.cfg.tick_every = te
        for i, rate in enumerate(rates):
            tr.mix.rate = rate
            b0, r0 = over_l_max(su.proxy, l_max), su.proxy.refused
            n0, s0 = len(tr.searches), len(su.proxy.spans)
            w_open, w_close = tr.drive(args.seconds, args.seed + i)
            late = sum(not r.ticket.done() for r in tr.searches[n0:]
                       if r.in_window)
            b1, r1 = over_l_max(su.proxy, l_max), su.proxy.refused
            tr.finish()
            win = [r for r in tr.searches[n0:] if r.in_window]
            lat = np.array([answered(r.ticket) - r.due for r in win]) * 1e3
            ticks_w = [b - a for n, a, b in su.proxy.spans[s0:]
                       if n == "tick" and w_open <= a < w_close]
            acked = sum(n for _, t1, n in su.proxy.update_log
                        if w_open <= t1 < w_close)
            row = dict(tick_every=te, rate=rate, offered=len(win),
                       late_at_close=late,
                       keeps_pace=late <= su.engine.cfg.search_batch,
                       p50_ms=float(np.percentile(lat, 50)),
                       p99_ms=float(np.percentile(lat, 99)),
                       ticks=len(ticks_w),
                       tick_ms=float(np.mean(ticks_w) * 1e3) if ticks_w
                       else 0.0,
                       update_vps=acked / args.seconds,
                       refused=r1 - r0, over_l_max=[b0, b1])
            rows.append(row)
            print(json.dumps(row), flush=True)
    print(json.dumps({"sweep": args.workload, "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
