"""The plain references recover the known clusters of generated blobs,
give the answers of a plain whole-matrix computation whatever their block
size, and keep DBSCAN's memory bounded whatever the host's CPU count."""

import json
import os
import subprocess
import sys

import ml_dtypes
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


def _bf16(v):
    return np.asarray(v, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float32)


def _whole_sq_dists(a, b, precision):
    """Every pair at once, by the formulas of ``reference``'s docstring."""
    if precision == "float32":
        out = np.zeros((len(a), len(b)), np.float32)
        for j in range(a.shape[1]):
            diff = a[:, j, None] - b[None, :, j]
            out += diff * diff
        return out
    a, b = _bf16(a), _bf16(b)
    cross = np.zeros((len(a), len(b)), np.float32)
    for j in range(a.shape[1]):
        cross += a[:, j, None] * b[None, :, j]
    na = _bf16(np.sum(a * a, axis=1, dtype=np.float32))
    nb = _bf16(np.sum(b * b, axis=1, dtype=np.float32))
    return _bf16(_bf16(na[:, None] - _bf16(np.float32(2.0) * _bf16(cross)))
                 + nb[None, :])


def _whole_dbscan(x, eps, min_pts, precision):
    near = _whole_sq_dists(x, x, precision) <= np.float32(eps) ** 2
    core = near.sum(axis=1) >= min_pts
    labels = np.zeros(len(x), np.int64)
    cid = expansions = 0
    while (core & (labels == 0)).any():
        cid += 1
        frontier = np.flatnonzero(core & (labels == 0))[:1]
        while frontier.size:
            new = near[frontier].any(axis=0) & (labels == 0)
            labels[new] = cid
            frontier = np.flatnonzero(new & core)
            expansions += 1
    return labels, expansions


def _whole_kmeans(x, init, tol, max_iters, precision):
    xs = _bf16(x) if precision == "bf16" else x
    c = x[init].copy()
    for _ in range(max_iters):
        d2 = _whole_sq_dists(x, c, precision)
        labels = np.argmin(d2, axis=1)
        inertia = float(np.sum(np.maximum(d2[np.arange(len(x)), labels], 0),
                               dtype=np.float64))
        c_new = c.copy()
        for j in range(len(c)):
            if (labels == j).any():
                total = np.sum(xs[labels == j], axis=0, dtype=np.float32)
                if precision == "bf16":
                    total = _bf16(total)
                c_new[j] = total / np.float32((labels == j).sum())
        if precision == "bf16":
            c_new = _bf16(c_new)
        shift = float(np.sum(np.abs(c_new - c), dtype=np.float32))
        c = c_new
        if shift < tol:
            break
    return labels, inertia


def _with_noise(seed, d):
    """The paper's blobs (n = 1,024) and 200 points scattered over a wider
    box, most of them noise at eps = sqrt(d), min_pts = 10 d."""
    x = loadgen.points(CONFIG, seed, 0, loadgen.Shape(d, 4, 256))
    rng = np.random.default_rng([seed, d])
    noise = rng.uniform(-60, 60, (200, d)).astype(np.float32)
    return np.concatenate([x, noise])[rng.permutation(len(x) + 200)]


@pytest.mark.parametrize("precision", reference.PRECISIONS)
@pytest.mark.parametrize("d", [1, 2, 4])
@pytest.mark.parametrize("seed", [3, 2**33 + 17])
def test_dbscan_equals_the_whole_matrix(seed, d, precision):
    x = _with_noise(seed, d)
    eps, min_pts = float(np.sqrt(d)), 10 * d
    got = reference.dbscan(x, eps=eps, min_pts=min_pts, precision=precision)
    labels, expansions = _whole_dbscan(x, eps, min_pts, precision)
    assert (labels == 0).any() and labels.max() >= 2
    np.testing.assert_array_equal(got["labels"], labels)
    assert got["expansions"] == expansions


@pytest.mark.parametrize("precision", reference.PRECISIONS)
@pytest.mark.parametrize("d", [1, 2, 4])
@pytest.mark.parametrize("seed", [3, 2**33 + 17])
def test_kmeans_equals_the_whole_matrix(seed, d, precision):
    x = _with_noise(seed, d)
    init = reference.sample_init(seed, len(x), 4)
    got = reference.kmeans(x, init, tol=1e-6, max_iters=300,
                           precision=precision)
    labels, inertia = _whole_kmeans(x, init, 1e-6, 300, precision)
    np.testing.assert_array_equal(got["labels"], labels)
    assert got["inertia"] == inertia


@pytest.mark.parametrize("precision", reference.PRECISIONS)
@pytest.mark.parametrize("rows", [0, 1, 3, 50])
def test_dbscan_block_size_changes_nothing(monkeypatch, rows, precision):
    """Two workers of ``rows`` rows a block, the frontier (up to 254 rows
    on this data) OR-ed 128 · ``rows`` rows at a time; 0: a bound under
    one row, so one worker fills one row at a time and every frontier row
    is OR-ed alone."""
    x = _with_noise(5, 4)
    n = len(x)
    want = reference.dbscan(x, eps=2.0, min_pts=40, precision=precision)
    monkeypatch.setattr(reference, "WORK_BYTES",
                        2 * rows * reference.PAIR_BYTES * n if rows
                        else (n + 7) // 8)
    assert reference._plan(n, 2) == ((2, rows) if rows else (1, 1))
    got = reference.dbscan(x, eps=2.0, min_pts=40, precision=precision,
                           threads=2)
    np.testing.assert_array_equal(got["labels"], want["labels"])
    assert got["expansions"] == want["expansions"]


_PEAK_CHILD = """
import json, os, resource, sys
sys.path.insert(0, sys.argv[1])
import loadgen, reference
os.cpu_count = lambda: 224
x = loadgen.points(json.loads(sys.argv[2]), 1, 0, loadgen.Shape(4, 8, 8192))
before = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
got = reference.dbscan(x, eps=2.0, min_pts=40)
after = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
print(json.dumps({"rise": after - before, "workers": reference._plan(
    len(x), 0)[0], "clusters": int(got["labels"].max())}))
"""


def test_dbscan_memory_does_not_grow_with_the_cpu_count():
    n = 65_536
    bench = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run([sys.executable, "-c", _PEAK_CHILD, bench,
                          json.dumps(CONFIG)], capture_output=True,
                         text=True, timeout=600, check=True)
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["workers"] == 224 and got["clusters"] >= 1
    assert got["rise"] <= n * n // 8 + int(1.25 * 2**30), got
