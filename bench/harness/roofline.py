"""Roofline counts of the two posting-scan kernels.

A kernel's roofline share is the least time the work its algorithm
needs could take at the chip's peaks, over the kernel's summed device
time in the trace.  The work counted is what the algorithm requires,
not what an implementation executes:

* bytes: each posting probed by a batch is read once per batch, however
  many of the batch's queries probe it (the distinct-postings rule), as
  its tile is stored (``capacity`` slots).  PQ reads the uint8 code
  tile (``capacity * m`` bytes) and one float32 lookup table per query
  (``m * ksub * 4``); the float scan reads the float32 rows
  (``capacity * d * 4``) and the queries (``d * 4`` each);
* operations: useful adds and multiplies per (query, probed posting)
  pair: ``capacity * m`` table-entry adds for PQ (never the executed
  one-hot matmul FLOPs), ``capacity * 2 * d`` for the float scan.

So no implementation can read over 100%, and one that shares tile reads
across queries or drops the one-hot trick does not move the yardstick.
"""
from __future__ import annotations

import numpy as np


def _pairs_and_distinct(probes) -> tuple:
    pairs = distinct = 0
    for p in probes:                       # one (Q, P) array per batch
        p = np.asarray(p)
        ok = p[p >= 0]
        pairs += ok.size
        distinct += np.unique(ok).size
    return pairs, distinct


def pq_scan_work(probes, *, capacity: int, m: int, ksub: int) -> tuple:
    """(bytes, operations) of ``pq_scan_topk`` over the traced batches."""
    pairs, distinct = _pairs_and_distinct(probes)
    queries = sum(np.asarray(p).shape[0] for p in probes)
    nbytes = distinct * capacity * m + queries * m * ksub * 4
    return float(nbytes), float(pairs * capacity * m)


def posting_scan_work(probes, *, capacity: int, dim: int) -> tuple:
    """(bytes, operations) of ``posting_scan_topk`` over the batches."""
    pairs, distinct = _pairs_and_distinct(probes)
    queries = sum(np.asarray(p).shape[0] for p in probes)
    nbytes = distinct * capacity * dim * 4 + queries * dim * 4
    return float(nbytes), float(pairs * capacity * 2 * dim)


def share(nbytes: float, ops: float, seconds: float, peak: dict) -> tuple:
    """(percent of the roofline, the bound: "memory" or "compute")."""
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    t_ops = ops / peak["flops_per_s"]
    bound = "memory" if t_mem >= t_ops else "compute"
    return 100.0 * max(t_mem, t_ops) / seconds, bound
