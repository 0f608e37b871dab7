"""One run of one cell: set-up, the measured window, the checks, the
metrics and the result line.

Everything a cell is made of is found by name: the deployment in
``configs/<config>.json``, the mix in ``traffic/<traffic>.json``, the
cell's own settings (search rate, engine cadence) in
``cells/<cell>.json``, and each per-layer metric's reader in
``layer_metrics/<metric>.py``.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import pathlib
import shutil
import sys
import tempfile
import time

import numpy as np

from . import reference as ref
from .data import SiftLike, SiftShape
from .proxy import IndexProxy
from .traffic import Mix, Traffic

BENCH = pathlib.Path(__file__).resolve().parents[1]


def read_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_files(manifest: dict, name: str, bench=BENCH) -> dict:
    """The entries and files of cell ``name``, found by name."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    return dict(
        workload=w,
        config=read_json(bench / "configs" / f"{w['config']}.json"),
        mix=read_json(bench / "traffic" / f"{w['traffic']}.json"),
        cell=read_json(bench / "cells" / f"{name}.json"),
        end_to_end=[m for m in manifest["end_to_end"]
                    if name in m.get("workloads", [name])],
        per_layer=[m for m in manifest["per_layer"]
                   if reports(manifest, m, name)])


def reports(manifest: dict, metric: dict, cell: str) -> bool:
    """Whether a per-layer metric is read in ``cell``: listed there, or
    unlisted and the cell reports the end-to-end metric it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    moved = [m for m in manifest["end_to_end"] if m["name"] == metric["moves"]]
    return bool(moved) and cell in moved[0].get("workloads", [cell])


def metric_reader(name: str, bench=BENCH):
    path = bench / "layer_metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"layer_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileCounter:
    """Counts compilations (and persistent-cache loads) and traces while
    ``on``, with the names of the functions traced."""

    def __init__(self):
        import jax
        self.on = False
        self.compiles = 0
        self.traced: list = []
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, _secs, fun_name="?", **_):
        if self.on and "backend_compile" in event:
            self.compiles += 1
        if self.on and "jaxpr_trace" in event:
            self.traced.append(fun_name)

    def _event(self, event, **_):
        if self.on and event.endswith("/cache_hits"):
            self.compiles += 1


def int_seed(seed: int) -> int:
    """A 31-bit seed for the index's own PRNG, drawn from ``seed``."""
    return int(np.random.default_rng([seed, 3]).integers(2 ** 31 - 1))


@dataclasses.dataclass
class Ctx:
    """What a per-layer reader sees of one run."""
    config: dict
    spans: list            # (name, t0, t1) host spans inside the window
    queue_wait_s: np.ndarray
    batch_s: np.ndarray    # dispatch -> collect returns, per batch
    acked_updates: int
    trace: object          # tracefile.Reduced or None
    probes: list           # (real rows, P) probed postings per traced batch
    peak: dict


def load_index(index, vecs, ids, batch: int, log) -> None:
    """Insert ``ids`` through the program's insert path in ``batch``
    sized submissions, each flushed to quiescence; refused ids go again
    after the flush."""
    for off in range(0, len(ids), batch):
        pending = ids[off:off + batch]
        for _ in range(8):
            index.insert(vecs[pending], pending)
            index.flush()
            refused = index.take_rejected()
            if not refused:
                break
            pending = np.concatenate(refused)
        else:
            raise RuntimeError(f"{len(pending)} loads never acknowledged")
    log(f"loaded {len(ids)}: live={index.live_count()}")


@dataclasses.dataclass
class Setup:
    conf: dict
    mix: Mix
    proxy: IndexProxy
    engine: object
    traffic: Traffic
    vecs: np.ndarray
    queries: np.ndarray


def build(files: dict, *, seed: int, t_start: float, log, patch=None):
    """Set-up up to the warm-up: data from the seed, the index, the
    corpus loaded and flushed, the engine and the traffic over it."""
    from repro.api import make_index
    from repro.core import UBISConfig
    from repro.serving import ServingConfig, ServingEngine

    clock = time.perf_counter
    conf, cellp = files["config"], files["cell"]
    mix = Mix.from_files(files["mix"], cellp)
    corpus = conf["corpus"]
    gen = SiftLike(SiftShape.from_json(corpus["shape"]), seed)
    n_base = int(corpus["n_base"])
    base, base_label = gen.rows(0, n_base)
    fresh, label = gen.rows(2, mix.fresh_pool)
    order = (gen.clustered_order(label) if mix.order == "clustered"
             else np.arange(mix.fresh_pool))
    vecs = np.concatenate([base, fresh])
    del base, fresh
    queries, _ = gen.rows(1, int(corpus["query_pool"]))
    base_ids = np.arange(n_base, dtype=np.int64)
    fresh_ids = n_base + order.astype(np.int64)
    log(f"data: {len(vecs)} vectors d={vecs.shape[1]} "
        f"({clock() - t_start:.1f}s)")

    cfg = UBISConfig(**conf["index"])
    if len(vecs) > cfg.max_ids:
        raise ValueError(f"{len(vecs)} ids exceed max_ids={cfg.max_ids}")
    load = conf["load"]
    index = make_index("ubis", cfg, vecs[:int(load["seed_sample"])],
                       seed=int_seed(seed), **conf["driver"])
    proxy = IndexProxy(index, len(vecs), clock)
    load_index(proxy, vecs, base_ids, int(load["batch"]), log)
    proxy.take_acked()
    if patch is not None:
        patch(index)
    log(f"loaded ({clock() - t_start:.1f}s)")
    engine = ServingEngine(proxy, ServingConfig(
        default_k=mix.k, **cellp["engine"]), clock=clock)
    traffic = Traffic(mix, engine, proxy, vecs, queries, base_ids=base_ids,
                      fresh_ids=fresh_ids, seed=seed, clock=clock)
    merges = index.stats.get("bg_merge")
    traffic.prime_merge(merge_ids(base_label, int(load["merge_clusters"]),
                                  seed))
    log(f"primed a merge: bg_merge {merges} -> "
        f"{index.stats.get('bg_merge')}, live={proxy.live_count()}")
    traffic.prime()
    return Setup(conf, mix, proxy, engine, traffic, vecs, queries)


def merge_ids(label: np.ndarray, n_clusters: int, seed: int) -> np.ndarray:
    """The base ids of ``n_clusters`` small clusters drawn from the seed.
    Deleting them empties the postings that hold them: a load only
    splits, and set-up needs a merge before the window opens."""
    picked = np.random.default_rng([seed, 13]).choice(
        np.unique(label), n_clusters, replace=False)
    return np.flatnonzero(np.isin(label, picked)).astype(np.int64)


def run_cell(files: dict, *, seed: int, seconds: float, trace: bool,
             t_start: float, control: bool = False, patch=None,
             log=None) -> dict:
    """One run; returns the result line's object.  ``patch(index)``, if
    given, replaces parts of the loaded index before the warm-up (the
    tests' planted faults)."""
    import jax

    log = log or (lambda s: print(s, file=sys.stderr, flush=True))
    clock = time.perf_counter
    counter = CompileCounter()
    su = build(files, seed=seed, t_start=t_start, log=log, patch=patch)
    conf, mix, proxy = su.conf, su.mix, su.proxy
    engine, traffic, vecs, queries = su.engine, su.traffic, su.vecs, su.queries
    del su

    # -- the window ----------------------------------------------------
    tracer = _Tracer(proxy, engine) if trace else None
    marks = [(0.0, lambda: setattr(counter, "on", True))]
    if tracer:
        # the profiler starts before the warm-up and stops once the
        # window's searches are answered: neither stall falls inside it
        tracer.start()
        marks += [(0.0, tracer.open_window), (seconds, tracer.close_window)]
    w_open, w_close = traffic.drive(seconds, seed, marks=marks)
    counter.on = False
    setup_s = w_open - t_start
    traffic.finish()
    if tracer:
        tracer.stop()
    first_del = min((t1 for kind, t1, _ in proxy.update_log
                     if kind == "delete"), default=float("nan"))
    log(f"window: {seconds}s, compiles={counter.compiles} "
        f"traced={sorted(set(counter.traced))}; first delete "
        f"{first_del - w_open:+.1f}s from the window's open")

    # -- what the window produced --------------------------------------
    reqs = [r for r in traffic.searches if r.in_window]
    sizes = [n for lane, n, _ in engine.batch_log if lane == "search"]
    first = np.searchsorted(np.cumsum(sizes), np.arange(len(traffic.searches)),
                            side="right")
    pos = {id(r): i for i, r in enumerate(traffic.searches)}
    batch = np.array([first[pos[id(r)]] for r in reqs])
    done = np.array([r.ticket.done() for r in reqs])
    t_end = clock()
    lat = np.array([(answered(r.ticket) if d else t_end) - r.due
                    for r, d in zip(reqs, done)])
    disp = np.array(proxy.dispatches)
    ok_b = np.minimum(batch, len(disp) - 1)
    u = disp[ok_b, 0].astype(np.int64)
    t_disp = disp[ok_b, 1]
    k = mix.k
    served = np.full((len(reqs), k), -1, np.int64)
    served_sc = np.zeros((len(reqs), k), np.float32)
    for i, (r, d) in enumerate(zip(reqs, done)):
        if d:
            res = r.ticket.result()
            ids = np.asarray(res.ids).reshape(-1)[:k]
            served[i, :len(ids)] = ids
            served_sc[i, :len(ids)] = np.asarray(res.scores).reshape(-1)[:k]
    upd_w = [x for x in traffic.updates if w_open <= x[1] < w_close]
    upd_failed = sum(not x[2].done() for x in upd_w)
    acked = sum(n for kind, t1, n in proxy.update_log
                if w_open <= t1 < w_close)
    tally = int(((proxy.ins_seq < proxy.seq)
                 & (proxy.del_seq >= proxy.seq)).sum())
    live = int(proxy.live_count())
    refused_unmatched = proxy.refused_unmatched
    dev = jax.devices()
    peak = max(d.memory_stats().get("peak_bytes_in_use", 0)
               if d.memory_stats() else 0 for d in dev)
    spans_w = [s for s in proxy.spans if w_open <= s[1] < w_close]
    batch_s = _batch_seconds(proxy.spans, w_open, w_close)
    probes = tracer.real_probes(sizes) if tracer else []
    # free the program's state before the reference runs
    proxy.index = None
    del engine
    gc.collect()

    # -- the checks ----------------------------------------------------
    norms = np.einsum("ij,ij->i", vecs, vecs)
    ins_seq, del_seq = proxy.ins_seq, proxy.del_seq
    is_q = np.array([r.target < 0 for r in reqs], bool)
    rng = np.random.default_rng([seed, 11])
    cand = np.flatnonzero(is_q & done)
    sample = np.sort(rng.choice(cand, min(mix.recall_sample, len(cand)),
                                replace=False))
    q_s = queries[[reqs[i].query for i in sample]]
    rb = np.flatnonzero(~is_q & done)
    target = np.array([reqs[i].target for i in rb], np.int64)
    t_ref = clock()
    rec = ref.recall(vecs, norms, ins_seq, del_seq, q_s, u[sample],
                     served[sample])
    not_live = int((~ref.live_at(ins_seq, del_seq, served, u)
                    & (served >= 0)).any(1)[done].sum())
    rb_miss = ref.readback_misses(vecs, served[rb, 0], target)
    q_all = np.stack([queries[r.query] if r.target < 0 else vecs[r.target]
                      for r in reqs])
    gap = ref.score_gap(vecs, norms, q_all[done], served[done],
                        served_sc[done])
    log(f"reference: {len(sample)} recall queries, {len(rb)} read-backs "
        f"in {clock() - t_ref:.1f}s")
    floor = float(conf["guarantees"]["recall_at_10_floor"])
    gap_limit = float(conf["guarantees"]["score_gap_limit"])
    checks = _checks(float(rec.mean()), floor, gap, gap_limit,
                     int((~done).sum()), not_live, rb_miss,
                     abs(live - tally), upd_failed, refused_unmatched)
    result_control = None
    if control:
        c_ids, c_sc = ref.control_bf16(vecs, ins_seq, del_seq, q_s,
                                       u[sample])
        c_rb, c_rb_sc = ref.control_bf16(vecs, ins_seq, del_seq,
                                         vecs[target], u[rb])
        c_rec = ref.recall(vecs, norms, ins_seq, del_seq, q_s, u[sample],
                           c_ids)
        c_nl = int((~ref.live_at(ins_seq, del_seq, c_ids, u[sample])
                    ).any(1).sum())
        c_gap = max(ref.score_gap(vecs, norms, q_s, c_ids, c_sc),
                    ref.score_gap(vecs, norms, vecs[target], c_rb, c_rb_sc))
        result_control = _checks(
            float(c_rec.mean()), floor, c_gap, gap_limit, 0, c_nl,
            ref.readback_misses(vecs, c_rb[:, 0], target), 0, 0, 0)

    # -- metrics -------------------------------------------------------
    lat_ms = lat * 1e3
    values = {
        "search_p50_ms": float(np.percentile(lat_ms, 50)),
        "search_p99_ms": float(np.percentile(lat_ms, 99)),
        "recall_at_10": float(rec.mean()),
        "update_vps": acked / (w_close - w_open),
        "setup_s": setup_s,
    }
    red = tracer.reduce() if tracer else None
    if trace:
        ctx = Ctx(config=conf, spans=spans_w,
                  queue_wait_s=t_disp - np.array([r.due for r in reqs]),
                  batch_s=batch_s, acked_updates=acked, trace=red,
                  probes=probes,
                  peak=files["peak"])
        wanted = files["per_layer"]
    else:
        wanted = files["end_to_end"]
    metrics = {}
    for m in wanted:
        v = (metric_reader(m["name"])(ctx) if trace
             else values.get(m["name"]))
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    correct = all(c["ok"] for c in checks.values())
    failed = (int((~done).sum()) + not_live + rb_miss + upd_failed
              + int(gap > gap_limit))
    out = {
        "correct": correct,
        "attempted": len(reqs) + len(upd_w),
        "failed": failed,
        "metrics": metrics,
        "device": {"platform": dev[0].platform, "kind": dev[0].device_kind,
                   "count": len(dev), "memory_peak_bytes": int(peak)},
        "compiles_in_window": counter.compiles,
        "traced_in_window": sorted(set(counter.traced)),
        "searches": len(reqs),
        "recall_sample": len(sample),
        "refused_ids": proxy.refused,
        "latency_ms": {f"p{q}": float(np.percentile(lat_ms, q))
                       for q in (90, 95, 99)},
    }
    if red is not None:
        out["device"].update(busy_s=red.busy_s, window_s=red.window_s)
        out["breakdown"] = {"device_ops": red.top_ops,
                            "idle_gaps": red.gaps}
    if result_control is not None:
        out["control"] = {k_: {"value": c["value"], "limit": c["limit"],
                               "ok": c["ok"]}
                          for k_, c in result_control.items()}
        out["control_correct"] = all(c["ok"]
                                     for c in result_control.values())
    out["checks"] = {k_: {"value": c["value"], "limit": c["limit"]}
                     for k_, c in checks.items()}
    return out


def answered(ticket) -> float:
    """When a resolved ticket was answered, on the engine's clock."""
    return ticket.t_submit + ticket.latency_s


def _checks(recall, floor, gap, gap_limit, unanswered, not_live, rb_miss,
            live_diff, upd_failed, refused_unmatched) -> dict:
    """Each number compared, its limit and whether it holds.  The recall
    floor and the score gap's limit are the deployment's own (its config
    file); the others are exact comparisons, limit 0."""
    c = {"recall_at_10": (recall, floor, recall >= floor),
         "score_gap": (gap, gap_limit, gap <= gap_limit)}
    for name, v in (("unanswered", unanswered), ("served_not_live", not_live),
                    ("readback_missed", rb_miss),
                    ("live_count_diff", live_diff),
                    ("updates_unacked", upd_failed),
                    ("refusals_unmatched", refused_unmatched)):
        c[name] = (v, 0, v <= 0)
    return {k: {"value": v, "limit": lim, "ok": bool(ok)}
            for k, (v, lim, ok) in c.items()}


def _batch_seconds(spans, lo, hi) -> np.ndarray:
    """Per search batch dispatched in [lo, hi): dispatch start to the
    return of its collect (the n-th collect answers the n-th dispatch)."""
    d = [s for s in spans if s[0] == "dispatch_search"]
    c = [s for s in spans if s[0] == "collect_search"]
    return np.array([cs[2] - ds[1] for ds, cs in zip(d, c)
                     if lo <= ds[1] < hi])


class _Tracer:
    """The profiler around the window.  It runs from before the warm-up
    to after the last answer; the ``bench.window`` annotation marks the
    window, to which every reduction clips, and only the window's
    search batches keep their probes."""

    def __init__(self, proxy, engine):
        self.proxy, self.engine = proxy, engine
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")

    def _n_batches(self):
        return sum(1 for lane, _, _ in self.engine.batch_log
                   if lane == "search")

    def start(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.proxy.annotate = True

    def open_window(self):
        import jax
        self.b0 = self._n_batches()
        self._window = jax.profiler.TraceAnnotation("bench.window")
        self._window.__enter__()
        self.proxy.keep_probes = True

    def close_window(self):
        self.proxy.keep_probes = False
        self._window.__exit__(None, None, None)

    def stop(self):
        import jax
        self.proxy.annotate = False
        jax.profiler.stop_trace()

    def real_probes(self, sizes) -> list:
        """Probes of the traced batches, padding rows dropped."""
        return [p[:n] for p, n in zip(self.proxy.probes, sizes[self.b0:])]

    def reduce(self):
        from . import tracefile
        try:
            return tracefile.reduce(tracefile.load(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
