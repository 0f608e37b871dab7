"""The comparison that decides ``correct``: sound runs pass, and the
control and each planted fault come out not correct.

Runs the whole harness past its look for a chip (``run_cell``), on a
tiny cell on the CPU (jnp twins)."""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2] / "bench"))

import dataclasses
import time

import numpy as np
import pytest

import tiny_cell
from harness.cell import run_cell

SEED = 2**31 + 1234


def run(traffic="search", use_pq=False, patch=None, control=False):
    return run_cell(tiny_cell.files(traffic, use_pq), seed=SEED,
                    seconds=1.5, trace=False, t_start=time.perf_counter(),
                    control=control, patch=patch, log=lambda s: None)


@pytest.mark.parametrize("traffic,use_pq", [("search", False),
                                            ("churn", True)])
def test_sound_run_is_correct_and_the_control_is_not(traffic, use_pq):
    out = run(traffic, use_pq, control=True)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"
    assert not out["control_correct"], out["control"]
    assert out["control"]["score_gap"]["value"] > 0.5


def unchanged_state(index):
    """An insert that acknowledges but leaves the state as it was."""
    insert = index.insert

    def fn(vecs, ids):
        before = index.state
        res = insert(vecs, ids)
        index.state = before
        return res
    index.insert = fn


def half_batch_left_out(index):
    """Search answers only half of each batch's rows (every other one:
    a tiny cell's batches are mostly padding)."""
    collect = index.collect_search

    def fn(disp):
        res = collect(disp)
        ids = np.array(res.ids)
        ids[1::2] = -1
        return dataclasses.replace(res, ids=ids)
    index.collect_search = fn


def answer_altered(index):
    """Every served id is shifted by one where the search produces it."""
    collect = index.collect_search

    def fn(disp):
        res = collect(disp)
        ids = np.array(res.ids)
        return dataclasses.replace(res, ids=np.where(ids >= 0, ids + 1, ids))
    index.collect_search = fn


@pytest.mark.parametrize("fault", [unchanged_state, half_batch_left_out,
                                   answer_altered])
def test_planted_fault_is_not_correct(fault):
    out = run(patch=fault)
    assert not out["correct"], out["checks"]
