"""Trace reduction and roofline counts, checked by hand."""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2] / "bench"))


import numpy as np
import pytest

from harness import roofline, tracefile as tf
from harness.tracefile import Event

DATA = pathlib.Path(__file__).resolve().parent / "data"
DEV = "/device:TPU:0"


def test_recorded_cpu_trace_gives_window_and_host_spans():
    ev = tf.load(str(DATA))
    lo, hi = tf.window(ev)
    spans = tf.host_spans(ev, lo, hi)
    names = sorted(s.name for s in spans)
    assert names == sorted(["bench.dispatch_search", "bench.collect_search",
                            "bench.tick"] * 3)
    assert all(lo <= s.start_ns and s.end_ns <= hi for s in spans)
    with pytest.raises(RuntimeError, match="no device op"):
        tf.reduce(ev)          # a CPU trace has no device plane


def synthetic():
    """Window [0, 100) ns.  Ops: a [10, 30) and b [20, 40) overlap, c
    [60, 70), d [95, 120) runs past the close.  Host spans: tick
    [40, 60), collect [70, 100)."""
    ops = [("%pq_scan_topk.1 = (f32[64,1,64]) custom-call(...)", 10, 20),
           ("fusion.2", 20, 20), ("pq_scan_topk.3", 60, 10),
           ("%copy.4 = f32[8] copy(f32[8] %x)", 95, 25)]
    ev = [Event("/host:CPU", "python", "bench.window", 0, 100),
          Event("/host:CPU", "python", "bench.tick", 40, 20),
          Event("/host:CPU", "python", "bench.collect_search", 70, 30),
          Event(DEV, "XLA Modules", "jit_search(7)", 10, 60),
          Event(DEV, "XLA Modules", "jit_background_round(9)", 95, 25),
          Event("/device:TPU_NON_CORE:0", "XLA Ops", "dma", 0, 100)]
    ev += [Event(DEV, "XLA Ops", n, s, d) for n, s, d in ops]
    return ev


def test_busy_is_the_union_clipped_to_the_window():
    r = tf.reduce(synthetic())
    assert r.window_s == pytest.approx(100e-9)
    # [10, 40) + [60, 70) + [95, 100) = 45 ns
    assert r.busy_s == pytest.approx(45e-9)


def test_op_and_program_seconds_by_base_name():
    r = tf.reduce(synthetic())
    assert r.op_s["pq_scan_topk"] == pytest.approx(30e-9)
    assert r.op_n["pq_scan_topk"] == 2
    assert r.op_s["copy"] == pytest.approx(5e-9)
    assert r.spans == {"tick": 1, "collect_search": 1}
    assert r.module_s == pytest.approx({"jit_search": 60e-9,
                                        "jit_background_round": 5e-9})
    assert r.top_ops[0] == ["pq_scan_topk", pytest.approx(30e-9)]


def test_idle_gaps_longest_first_labelled_by_host_span():
    r = tf.reduce(synthetic())
    # gaps: [0, 10) host, [40, 60) tick, [70, 95) collect
    assert [g[0] for g in r.gaps] == ["collect_search", "tick", "host"]
    assert [g[1] for g in r.gaps] == pytest.approx([25e-9, 20e-9, 10e-9])


def test_union_merges_touching_intervals():
    assert tf.union([(5, 7), (0, 2), (2, 3), (6, 9)]) == [(0, 3), (5, 9)]


def test_pq_scan_work_counts_distinct_postings_and_useful_adds():
    # batch 1: 2 queries probe {0, 1} and {1, -1}; batch 2: {1, 2}
    probes = [np.array([[0, 1], [1, -1]]), np.array([[1, 2]])]
    nbytes, ops = roofline.pq_scan_work(probes, capacity=8, m=4, ksub=16)
    # distinct per batch: 2 + 2 postings x 8 slots x 4 code bytes = 128;
    # luts: 3 queries x 4 x 16 x 4 bytes = 768
    assert nbytes == 128 + 768
    # 5 real (query, posting) pairs x 8 slots x 4 adds
    assert ops == 5 * 8 * 4


def test_posting_scan_work_counts_float_rows_once_per_batch():
    probes = [np.array([[3, 3], [3, 4]])]
    nbytes, ops = roofline.posting_scan_work(probes, capacity=8, dim=16)
    # postings {3, 4}: 2 x 8 x 16 x 4 = 1024 bytes; 2 queries x 64 bytes
    assert nbytes == 1024 + 128
    # 4 pairs x 8 slots x (16 multiplies + 16 adds)
    assert ops == 4 * 8 * 32


def test_share_names_its_bound():
    peak = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert roofline.share(20.0, 100.0, 4.0, peak) == (50.0, "memory")
    assert roofline.share(1.0, 1000.0, 20.0, peak) == (50.0, "compute")
