"""The observability plane, end to end on a streaming serving workload.

    PYTHONPATH=src python examples/observability.py [--profile-dir DIR]

One ``Obs`` plane is shared by the driver and the serving engine, so a
single exposition covers every layer:

* driver counters under the shared schema (``index_*`` series);
* structured trace events — every background mark/split/merge, tier
  move, and PQ retrain states its reason;
* request spans (queue wait, service, end-to-end latency) from the
  serving engine;
* program spans: the tick and each of its phases, inserts, searches
  and every blocking device->host read (``span_*_seconds``);
* the sampled live-recall probe, shadow-executing 25% of served query
  batches against ``exact()``.

The script streams ingest + query traffic through a ``ServingEngine``,
then prints the Prometheus exposition, a few trace events, and the
probe's rolling recall.  ``--profile-dir`` additionally captures a
``jax.profiler`` trace of the first working pump (view with
TensorBoard or Perfetto).
"""
import argparse

import numpy as np

from repro.api import make_index
from repro.core import UBISConfig
from repro.obs import parse_exposition
from repro.serving import ServingConfig, ServingEngine


def main(profile_dir=None):
    rng = np.random.default_rng(0)
    dim, n = 32, 6000
    cents = rng.normal(size=(24, dim)) * 5

    def batch(m):
        a = rng.integers(0, 24, m)
        return (cents[a] + rng.normal(size=(m, dim))).astype(np.float32)

    cfg = UBISConfig(dim=dim, max_postings=512, capacity=96, l_min=10,
                     l_max=80, max_ids=1 << 18, nprobe=16,
                     use_pallas="off")
    data = batch(n)
    index = make_index("ubis", cfg, data[:1500], seed=0, round_size=512,
                       bg_ops_per_round=8)
    engine = ServingEngine(index, ServingConfig(
        search_batch=16, search_deadline_s=1e-3, insert_deadline_s=5e-3,
        tick_every=1, default_k=10,
        recall_probe=0.25, recall_probe_rows=8,
        obs_profile_dir=profile_dir))

    per = n // 8
    tickets = []
    for step in range(8):
        lo = step * per
        tickets.append(engine.submit_insert(
            data[lo:lo + per], np.arange(lo, lo + per)))
        for _ in range(6):
            tickets.append(engine.submit_search(batch(1), 10))
        engine.drain()
    assert all(t.done() for t in tickets)

    # ---- one exposition, every layer --------------------------------
    text = engine.obs.to_prometheus()
    series = parse_exposition(text)            # proves it parses
    print(f"== exposition: {len(series)} series ==")
    for name in ("index_inserted", "index_bg_split", "index_bg_merge",
                 "index_search_probed", "serve_latency_seconds_count",
                 "live_recall", "live_recall_probes"):
        print(f"  {name} = {series.get(name)}")

    lat = engine.obs.snapshot()["serve_latency_seconds"]
    print(f"== request spans == n={lat['count']} "
          f"p50={lat['p50']*1e3:.2f}ms p99={lat['p99']*1e3:.2f}ms")

    snap = engine.obs.snapshot()
    print("== program spans ==")
    for name in ("tick", "tick.execute", "tick.drain", "tick.mark",
                 "tick.gc", "insert", "search.collect"):
        h = snap["span_" + name.replace(".", "_") + "_seconds"]
        print(f"  {name:15s} n={h['count']:3d} "
              f"mean={h['mean'] * 1e3:.2f}ms")
    syncs = sum(v["count"] for k, v in snap.items()
                if k.startswith("span_sync_"))
    print(f"  host syncs: {syncs} "
          f"({snap['span_tick_seconds']['count']} ticks)")

    evs = list(engine.obs.events())
    print(f"== trace ring: {len(evs)} events ==")
    for e in evs[-4:]:
        print("  " + str({k: e[k] for k in list(e)[:6]}))
    marks = engine.obs.events("bg_mark")
    if marks:
        print(f"  bg_mark reasons: "
              f"{sorted({e['reason'] for e in marks})}")

    if engine.probe is not None:
        print(f"== live recall (rolling over "
              f"{int(series['live_recall_probes'])} probes): "
              f"{engine.probe.rolling_recall:.3f} ==")
    if profile_dir:
        print(f"profiler trace written under {profile_dir}")
    return 0


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--profile-dir", default=None)
    raise SystemExit(main(ap.parse_args().profile_dir))
