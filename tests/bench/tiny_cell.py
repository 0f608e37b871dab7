"""A cell small enough for a CPU test run: the harness end to end on
the jnp twins, a few thousand vectors, a two-second window."""
import copy
import json
import pathlib

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"

INDEX = {"dim": 16, "max_postings": 512, "capacity": 16, "l_min": 2,
         "l_max": 12, "nprobe": 8, "max_ids": 16384, "use_pq": False,
         "pq_m": 8, "pq_ksub": 64, "rerank_k": 64, "use_pallas": "off",
         "cache_capacity": 256}
SHAPE = {"dim": 16, "n_top": 4, "n_sub": 16, "rank": 4, "top_spread": 30.0,
         "sub_spread": 8.0, "noise": 2.0, "mean_shape": 0.5,
         "mean_scale": 40.0}


def files(traffic: str = "search", use_pq: bool = False, *,
          rate: float = 200.0) -> dict:
    """The ``cell_files`` dict of a tiny cell under the committed mix
    ``traffic``, its sizes cut to run on the CPU in seconds."""
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    mix = json.loads((BENCH / "traffic" / f"{traffic}.json").read_text())
    mix = copy.deepcopy(mix)
    mix["updates"]["insert_n"] = 64
    mix["updates"]["delete_n"] = 64 if traffic == "churn" else 16
    mix["fresh_pool"] = 8192
    mix["warmup_s"] = 1.0
    mix["recall_sample"] = 64
    if traffic == "churn":
        mix["searches"]["rate"] = 50.0
    config = {
        "index": dict(INDEX, use_pq=use_pq),
        "driver": {"round_size": 64, "bg_ops_per_round": 8,
                   "drain_per_tick": 64, "pq_retrain_every": 4},
        "corpus": {"n_base": 3000, "query_pool": 512, "shape": SHAPE},
        "load": {"seed_sample": 1000, "batch": 1000,
                 "merge_clusters": 4},
        "guarantees": {"recall_at_10_floor": 0.5, "score_gap_limit": 0.5},
    }
    name = f"tiny-{'pq' if use_pq else 'float'}.{traffic}"
    return dict(
        workload={"name": name, "chips": 1},
        config=config, mix=mix,
        cell={"search_rate": rate,
              "engine": {"search_batch": 16, "tick_every": 1}},
        end_to_end=manifest["end_to_end"],
        per_layer=[m for m in manifest["per_layer"]
                   if any(w.endswith("." + traffic)
                          for w in m.get("workloads", ["." + traffic]))],
        peak={"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
