"""Index-level workload on the PALLAS kernel path (interpret=True on
CPU) — not the jnp reference the index normally dispatches to off-TPU.

This is the ROADMAP "run the kernel path periodically" item: the weekly
``kernels-interpret`` CI job runs it (marked slow, so the per-PR quick
suite skips it).  The kernels are alignment-free, so BOTH an aligned
config (d=128, C=128, ksub=256) and a deliberately misaligned one
(d=100, odd C, non-power-of-two ksub) exercise the same fused Pallas
``centroid_topk``, ``posting_scan_topk``, ``pq_scan_topk`` and
``rerank_topk`` kernels (plus ``posting_scan``/``centroid_score`` via
the exact oracle) end to end through the driver — no fallback gates.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import UBISConfig, UBISDriver, brute_force, metrics
from conftest import make_clustered

pytestmark = pytest.mark.slow


@pytest.mark.parametrize("use_pq", [False, True])
@pytest.mark.parametrize("dim,capacity,pq_m,ksub", [(128, 128, 8, 256),
                                                    (100, 96, 10, 100)])
def test_driver_workload_on_pallas_interpret(use_pq, dim, capacity, pq_m,
                                             ksub):
    cfg = UBISConfig(dim=dim, max_postings=64, capacity=capacity, l_min=8,
                     l_max=int(capacity * 0.75), cache_capacity=256,
                     max_ids=1 << 12, nprobe=8, use_pallas="on",
                     use_pq=use_pq, pq_m=pq_m, pq_ksub=ksub, rerank_k=64)
    data = make_clustered(700, d=cfg.dim, k=5, seed=2)
    drv = UBISDriver(cfg, data[:200], round_size=128, bg_ops_per_round=4,
                     pq_retrain_every=3)
    drv.insert(data, np.arange(700))
    drv.delete(np.arange(0, 120))
    drv.flush(max_ticks=12)
    assert drv.stats["bg_ops"] > 0, "workload exercised no structural ops"
    q = make_clustered(8, d=cfg.dim, k=5, seed=7)
    found = drv.search(q, 10).ids
    true, _ = brute_force(drv.state, cfg, jnp.asarray(q), 10)
    rec = metrics.recall_at_k(found, np.asarray(true))
    floor = 0.8 if use_pq else 0.9
    assert rec > floor, rec
