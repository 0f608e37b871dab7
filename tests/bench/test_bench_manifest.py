"""BENCHMARK.json against the benchmark's contract, and every piece of a
cell found by name."""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2] / "bench"))

import json
import os
import re
import subprocess

import pytest

from harness.cell import cell_files, metric_reader, reports

ROOT = pathlib.Path(__file__).resolve().parents[2]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def test_top_level_keys_and_command():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["command"] == ["python3", "bench/run.py"]
    for p in MANIFEST["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p)
        assert (ROOT / p).is_dir() and not p.startswith("/")
    assert MANIFEST["command"][1].split("/")[0] in MANIFEST["paths"]


@pytest.mark.parametrize("cell", CELLS)
def test_every_piece_of_a_cell_is_found_by_name(cell):
    f = cell_files(MANIFEST, cell)
    assert f["config"]["name"] == f["workload"]["config"]
    assert f["workload"]["chips"] == 1
    for m in f["per_layer"]:
        assert callable(metric_reader(m["name"]))
    # max_ids holds the corpus and every fresh vector the mix can make
    n = f["config"]["corpus"]["n_base"] + f["mix"]["fresh_pool"]
    assert n <= f["config"]["index"]["max_ids"]


def test_names_units_and_keys_use_the_allowed_characters():
    metrics = [m["name"] for m in MANIFEST["end_to_end"]
               + MANIFEST["per_layer"]]
    configs = [c["name"] for c in MANIFEST["configs"]]
    for group in (metrics, configs, CELLS):
        assert len(set(group)) == len(group)
    pairs = {(w["config"], w["traffic"]) for w in MANIFEST["workloads"]}
    assert len(pairs) == len(CELLS)
    for n in metrics + configs + CELLS + [w["traffic"] for w in
                                          MANIFEST["workloads"]]:
        assert NAME.fullmatch(n), n
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert UNIT.fullmatch(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (ROOT / c["file"]).is_file()
    for w in MANIFEST["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]


def test_bounds_and_run_length_fit_the_contract():
    names = {m["name"]: m for m in MANIFEST["end_to_end"]}
    assert "setup_s" in names
    for m in MANIFEST["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    r = MANIFEST["run_seconds"]
    assert 1 <= r <= 51
    assert (2 + 14 * 24) * (r + 60) + 24 * 180 + 1200 <= 43200


@pytest.mark.parametrize("metric", [m["name"] for m in MANIFEST["per_layer"]])
def test_every_metric_moves_what_its_cells_report(metric):
    m = {x["name"]: x for x in MANIFEST["per_layer"]}[metric]
    e2e = {x["name"]: x for x in MANIFEST["end_to_end"]}
    assert m["moves"] in e2e
    for cell in CELLS:
        if reports(MANIFEST, m, cell):
            assert cell in e2e[m["moves"]].get("workloads", CELLS)
    assert any(reports(MANIFEST, m, c) for c in CELLS)


def test_run_without_a_tpu_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    for line in p.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)
    assert "Nothing was run" in p.stderr
