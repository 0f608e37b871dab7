"""The SIFT-shaped generator and the arrival schedule."""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2] / "bench"))

import numpy as np

from harness.data import SiftLike, SiftShape
from harness.traffic import arrival_times

SMALL = SiftShape(n_top=4, n_sub=8)


def test_same_seed_same_rows_other_seed_other_rows():
    a, la = SiftLike(SMALL, 2**31 + 9).rows(0, 5000)
    b, lb = SiftLike(SMALL, 2**31 + 9).rows(0, 5000)
    c, _ = SiftLike(SMALL, 2**31 + 10).rows(0, 5000)
    assert np.array_equal(a, b) and np.array_equal(la, lb)
    assert not np.array_equal(a, c)


def test_a_shorter_draw_is_a_prefix_of_a_longer_one():
    g = SiftLike(SMALL, 3)
    a, la = g.rows(1, 20_000)
    b, lb = g.rows(1, 40_000)
    assert np.array_equal(a, b[:20_000]) and np.array_equal(la, lb[:20_000])


def test_values_are_sift_range_integers_in_float32():
    x, label = SiftLike(SiftShape(), 4).rows(0, 20_000)
    assert x.dtype == np.float32 and x.shape == (20_000, 128)
    assert x.min() >= 0 and x.max() <= 255
    assert np.array_equal(x, np.rint(x))
    assert 0 <= label.min() and label.max() < 16 * 256


def test_streams_differ_and_queries_are_not_base_rows():
    g = SiftLike(SMALL, 5)
    base, _ = g.rows(0, 3000)
    q, _ = g.rows(1, 300)
    assert not (q[:, None, :] == base[None, :, :]).all(-1).any()


def test_true_neighbours_stand_apart():
    """A query's 10th neighbour is clearly nearer than its 100th, and
    both far nearer than the typical row: the local structure that
    isotropic Gaussian clusters in 128 dimensions lack (there the 1st
    to the 100th neighbour lie at nearly one distance)."""
    g = SiftLike(SiftShape(), 6)
    x, _ = g.rows(0, 200_000)
    q, _ = g.rows(1, 50)
    d = (x * x).sum(1)[None] - 2 * q @ x.T + (q * q).sum(1)[:, None]
    s = np.sort(d, axis=1)
    assert np.median(s[:, 99] / s[:, 9]) > 1.1
    assert np.median(s[:, 9] / np.median(d, axis=1)) < 0.1


def test_clustered_order_visits_large_clusters_in_turn():
    g = SiftLike(SMALL, 7)
    _, label = g.rows(2, 4000)
    order = g.clustered_order(label)
    assert np.array_equal(np.sort(order), np.arange(4000))
    top = label[order] // SMALL.n_sub
    assert (np.diff(top) != 0).sum() == SMALL.n_top - 1


def test_arrivals_same_gaps_every_seed_in_another_order():
    a = arrival_times(500.0, 4.0, 1)
    b = arrival_times(500.0, 4.0, 2**31 + 5)
    assert len(a) == len(b) == 2000
    assert (a >= 0).all() and a[-1] <= 4.0 and (np.diff(a) >= 0).all()
    assert np.allclose(np.sort(np.diff(a, prepend=0)),
                       np.sort(np.diff(b, prepend=0)))
    assert not np.allclose(a, b)
