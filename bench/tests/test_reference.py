"""The plain references recover the known clusters of generated blobs."""

import numpy as np
import pytest

import compare
import loadgen
import reference

CONFIG = {"generator": {"center_range": 10.0, "min_sigma": 0.15,
                        "max_sigma": 0.8}}


def _separated(seed, d, c, per):
    """Blobs whose centres are far apart relative to their spread."""
    rng = np.random.default_rng(seed)
    centers = np.arange(c)[:, None] * 40.0 + rng.uniform(-1, 1, (c, d))
    labels = np.repeat(np.arange(c), per)
    x = centers[labels] + rng.normal(0, 0.5, (c * per, d))
    perm = rng.permutation(c * per)
    return x[perm].astype(np.float32), labels[perm]


@pytest.mark.parametrize("d,c", [(1, 2), (2, 4), (4, 8)])
def test_kmeans_recovers_blobs(d, c):
    x, truth = _separated(d * 10 + c, d, c, 64)
    init = np.array([np.flatnonzero(truth == j)[0] for j in range(c)])
    got = reference.kmeans(x, init, tol=1e-6, max_iters=300)
    assert got["converged"]
    # centroid j started inside true cluster j, so labels equal the truth
    np.testing.assert_array_equal(got["labels"], truth)
    want = sum(((x[truth == j] - x[truth == j].mean(0)) ** 2).sum()
               for j in range(c))
    assert got["inertia"] == pytest.approx(want, rel=1e-4)


@pytest.mark.parametrize("d,c", [(1, 2), (2, 4), (4, 8)])
def test_dbscan_recovers_blobs(d, c):
    x, truth = _separated(d * 100 + c, d, c, 64)
    got = reference.dbscan(x, eps=3.0, min_pts=5)
    labels = got["labels"]
    assert labels.min() >= 1                     # no noise
    matched = compare.match_clusters(labels, truth + 1)
    np.testing.assert_array_equal(matched, truth + 1)
    # clusters are numbered in discovery order from the lowest index
    assert labels[0] == 1


def test_dbscan_noise_and_border():
    # a core pair chain, a border point, and an isolated point
    x = np.array([[0.0], [0.5], [1.0], [1.9], [10.0]], np.float32)
    got = reference.dbscan(x, eps=1.0, min_pts=3)
    np.testing.assert_array_equal(got["labels"], [1, 1, 1, 1, 0])
    # level 1 claims {0, 1, 2} from the seed 0; level 2 claims the border
    # point 3 and finds no new core point
    assert got["expansions"] == 2


def test_sample_init_is_distinct_and_seeded():
    a = reference.sample_init(2**33 + 5, 1000, 8)
    b = reference.sample_init(2**33 + 5, 1000, 8)
    np.testing.assert_array_equal(a, b)
    assert len(set(a.tolist())) == 8 and a.max() < 1000


def test_generator_is_seeded_and_shaped():
    shape = loadgen.Shape(4, 8, 128)
    a = loadgen.points(CONFIG, 7, 3, shape)
    b = loadgen.points(CONFIG, 7, 3, shape)
    c = loadgen.points(CONFIG, 7, 4, shape)
    assert a.shape == (1024, 4) and a.dtype == np.float32
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
