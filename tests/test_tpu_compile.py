"""Compile the main-path Pallas kernels for a TPU v5e, without a chip.

Interpret mode (the rest of ``test_kernels.py``) cannot see what the
TPU compiler refuses: block shapes off the (8, 128) tiling, layouts
Mosaic cannot relayout, more VMEM than a kernel may use.  Each test
here lowers one kernel through its ``ops`` wrapper (``backend="on"``)
for a described ``v5e:2x2`` topology at the chip smoke's shapes (the
SIFT1M deployment: d=128, 32,768 postings of 96 slots, nprobe=32, PQ
m=16 x 256, rerank 64, a 64-query serving batch) and checks that the
compiled program holds the kernel (``tpu_custom_call``).

The topology is described inside fixtures, never at import: only one
process may load the TPU compiler library at a time, and every xdist
worker imports every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

D, M, C, Q, P = 128, 32768, 96, 64, 32       # dim, postings, slots, batch, nprobe
PQ_M, KSUB, V, R, K = 16, 256, 2, 64, 10     # PQ, codebook slots, rerank, k


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compile_tpu(one_chip, monkeypatch):
    """Compile ``fn`` for one v5e chip and return the program text.
    The persistent compile cache is off: a chip-less compile can be
    written to it but never read back."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def run(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
                for s, dt in shapes]
        return jax.jit(fn).lower(*args).compile().as_text()

    yield run
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


F32, I32, U8, B = jnp.float32, jnp.int32, jnp.uint8, jnp.bool_


def test_centroid_topk_compiles_for_v5e(compile_tpu):
    txt = compile_tpu(
        lambda q, c, v: ops.centroid_topk(q, c, v, k=P, backend="on"),
        ((Q, D), F32), ((M, D), F32), ((M,), B))
    assert "tpu_custom_call" in txt


def test_centroid_score_compiles_for_v5e(compile_tpu):
    # the insert round's locate step: a 1,024-job batch vs every centroid
    txt = compile_tpu(
        lambda q, c, v: ops.centroid_score(q, c, v, backend="on"),
        ((1024, D), F32), ((M, D), F32), ((M,), B))
    assert "tpu_custom_call" in txt


def test_posting_scan_compiles_for_v5e(compile_tpu):
    # the exact oracle (exact(), the live-recall probe)
    txt = compile_tpu(
        lambda q, t, v: ops.posting_scan(q, t, v, backend="on"),
        ((Q, D), F32), ((M, C, D), F32), ((M, C), B))
    assert "tpu_custom_call" in txt


def test_posting_scan_topk_compiles_for_v5e(compile_tpu):
    txt = compile_tpu(
        lambda q, x, sv, vis, pr: ops.posting_scan_topk(
            q, x, sv, vis, pr, k=K, backend="on"),
        ((Q, D), F32), ((M, C, D), F32), ((M, C), B), ((M,), B),
        ((Q, P), I32))
    assert "tpu_custom_call" in txt


def test_pq_scan_topk_compiles_for_v5e(compile_tpu):
    txt = compile_tpu(
        lambda lut, cd, ps, sv, vis, pr: ops.pq_scan_topk(
            lut, cd, ps, sv, vis, pr, k=R, backend="on"),
        ((Q, V, PQ_M, KSUB), F32), ((M, PQ_M, C), U8), ((M,), I32),
        ((M, C), B), ((M,), B), ((Q, P), I32))
    assert "tpu_custom_call" in txt


def test_rerank_topk_compiles_for_v5e(compile_tpu):
    txt = compile_tpu(
        lambda q, x, sp, cand, adc: ops.rerank_topk(
            q, x, sp, cand, adc, k=K, backend="on"),
        ((Q, D), F32), ((M, C, D), F32), ((M,), B), ((Q, R), I32),
        ((Q, R), F32))
    assert "tpu_custom_call" in txt


def test_kmeans_assign_compiles_for_v5e(compile_tpu):
    # build-time k-means: a 20k seed sample vs the initial centroids
    txt = compile_tpu(
        lambda p, c: ops.kmeans_assign(p, c, backend="on"),
        ((20000, D), F32), ((2232, D), F32))
    assert "tpu_custom_call" in txt


def test_pq_training_assign_compiles_for_v5e(compile_tpu):
    # PQ codebook training: the assignment mapped over the subspaces
    assign = lambda p, c: jax.lax.map(
        lambda pc: ops.kmeans_assign(*pc, backend="on"), (p, c))
    txt = compile_tpu(assign, ((PQ_M, 2048, D // PQ_M), F32),
                      ((PQ_M, KSUB, D // PQ_M), F32))
    assert "tpu_custom_call" in txt


def test_pq_encode_keeps_f32_selection_on_v5e(compile_tpu):
    # XLA's TPU backend narrows the unused value half of jnp.argmin's
    # reduce to bfloat16; the whole-pool PQ encode (a retrain's full
    # re-encode) must select codes in f32 and fit the chip
    from repro.quant import pq
    txt = compile_tpu(pq.encode_tiles, ((PQ_M, KSUB, D // PQ_M), F32),
                      ((M, C, D), F32))
    assert "bf16" not in txt


def test_sharded_pq_retrain_compiles_for_v5e_mesh(topo, compile_tpu):
    # the sharded driver's codebook retrain over the smoke's --chips 4
    # state (65,520 postings on a 4-way model axis): its training kernel
    # must sit inside shard_map (a Mosaic kernel cannot be partitioned)
    import numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec
    from repro.core import UBISConfig
    from repro.core.sharded import index_specs, make_sharded_retrain
    from repro.core.types import empty_state
    cfg = UBISConfig(dim=D, max_postings=65520, capacity=C, nprobe=P,
                     max_ids=1 << 22, use_pq=True, pq_m=PQ_M, pq_ksub=KSUB,
                     rerank_k=R, use_pallas="on")
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    shard = lambda a, sp: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=NamedSharding(mesh, sp))
    st = jax.tree_util.tree_map(
        shard, jax.eval_shape(lambda: empty_state(cfg)), index_specs(cfg),
        is_leaf=lambda x: isinstance(x, PartitionSpec))
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                               sharding=NamedSharding(mesh, PartitionSpec()))
    txt = jax.jit(make_sharded_retrain(cfg, mesh)).lower(
        st, key).compile().as_text()
    assert "tpu_custom_call" in txt
    assert "bf16" not in txt
