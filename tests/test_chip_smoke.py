"""CPU rehearsal of ``chip_smoke.py`` (keeps the chip script from rotting
between chip runs).

The smoke's pass function runs here at a tiny size with the Pallas
kernels in interpret mode (``use_pallas="on"``); ``main`` itself refuses
to run without a TPU, which the last tests pin.
"""
import importlib.util
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("use_pq", [True, False])
def test_smoke_pass_rehearsal_on_interpreted_kernels(smoke, use_pq):
    cfg = smoke.sift_config(max_postings=256, max_ids=1 << 14,
                            use_pallas="on", use_pq=use_pq)
    lines = []
    fails, index = smoke.run_pass(
        "rehearsal", cfg, n_base=3000, rounds=2, round_insert=400,
        round_delete=150, n_queries=48, readback=24, load_batch=2048,
        round_ticks=2, search_batch=32, recall_floor=0.8,
        engine_kw=dict(round_size=256, bg_ops_per_round=16,
                       drain_per_tick=256),
        log=lines.append)
    assert fails == [], "\n".join(lines + fails)
    assert index.live_count() == 3000 + 2 * (400 - 150)
    assert any("readback_rank1=24/24" in s for s in lines)


def test_numpy_reference_is_exact_top_k(smoke):
    import numpy as np
    rng = np.random.default_rng(0)
    vecs = rng.normal(size=(500, 16)).astype(np.float32)
    live = np.flatnonzero(rng.random(500) > 0.3)
    q = rng.normal(size=(7, 16)).astype(np.float32)
    got = smoke.numpy_topk(vecs, live, q, 5, chunk=3)
    d = ((q[:, None, :] - vecs[live][None]) ** 2).sum(-1)
    want = live[np.argsort(d, axis=1, kind="stable")[:, :5]]
    np.testing.assert_array_equal(got, want)


def test_main_refuses_without_a_tpu(smoke, capsys):
    assert jax.default_backend() != "tpu"
    assert smoke.main([]) != 0
    assert capsys.readouterr().out.strip() == ""


def test_script_alone_fails_without_the_repo(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def test_compile_cache_placement(monkeypatch):
    from repro.launch import compile_cache
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        jax.config.update("jax_compilation_cache_dir", None)
        assert compile_cache.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir is None  # left to JAX
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        got = compile_cache.enable_compile_cache()
        assert got == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
        assert ".jax_cache/" in (ROOT / ".gitignore").read_text()
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
