"""System-level clustering tests: paper semantics, oracle agreement,
cancellation behaviour, distributed equivalence (subprocess, 8 devices)."""

import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypo_shim import HealthCheck, given, settings, strategies as st

from repro.core import dbscan, kmeans
from repro.core.cancellation import CancellationToken, CancelReason
from repro.data.synthetic import ClusterSpec, make_blobs, paper_grid

_HYPO = dict(
    deadline=None,
    max_examples=8,
    suppress_health_check=[HealthCheck.too_slow, HealthCheck.data_too_large],
)


# -- paper grid sanity ---------------------------------------------------------


def test_paper_grid_is_60_tuples():
    grid = paper_grid()
    assert len(grid) == 60
    spec = grid[0]
    assert spec.dbscan_min_pts == 10 * spec.features
    assert abs(spec.dbscan_eps - np.sqrt(spec.features)) < 1e-6


def test_make_blobs_shapes_and_shuffle(rng_key):
    spec = ClusterSpec(2, 4, 128)
    x, y, centers = make_blobs(rng_key, spec)
    assert x.shape == (512, 2) and y.shape == (512,)
    assert centers.shape == (4, 2)
    assert x.dtype == jnp.float32  # paper: single precision
    # shuffled: first 128 labels are not all cluster 0
    assert len(np.unique(np.asarray(y)[:128])) > 1


def test_make_blobs_unequal_sizes(rng_key):
    spec = ClusterSpec(2, 3, 0)
    x, y, _ = make_blobs(rng_key, spec, sizes=[10, 50, 100])
    assert x.shape == (160, 2)
    counts = np.bincount(np.asarray(y), minlength=3)
    assert list(counts) == [10, 50, 100]


# -- DBSCAN ---------------------------------------------------------------


@pytest.mark.parametrize("features,clusters,size", [(1, 2, 128), (2, 6, 128),
                                                    (4, 4, 64), (2, 8, 256)])
def test_dbscan_matches_oracle(features, clusters, size):
    key = jax.random.PRNGKey(features * 100 + clusters * 10)
    x, _, _ = make_blobs(key, ClusterSpec(features, clusters, size))
    cfg = dbscan.DBSCANConfig.paper_defaults(features)
    res = dbscan.fit(x, cfg)
    oracle = dbscan.fit_oracle(np.asarray(x), cfg)
    assert (np.asarray(res.labels) == oracle).all()
    res_host = dbscan.fit_cancellable(x, cfg)
    assert (np.asarray(res_host.labels) == oracle).all()


@pytest.mark.parametrize("state_interval", [1, 3, 8, 10**6])
def test_dbscan_resumable_matches_oracle_at_every_interval(state_interval):
    """One device program per stretch of ``state_interval`` expansions
    gives the oracle's labels and the jitted solver's expansions; every
    snapshot falls on a multiple of the interval and resumes to the same
    answer."""
    x, _, _ = make_blobs(jax.random.PRNGKey(62), ClusterSpec(2, 6, 128))
    cfg = dbscan.DBSCANConfig(eps=float(np.sqrt(2)), min_pts=20,
                              use_kernel=False)
    oracle = dbscan.fit_oracle(np.asarray(x), cfg)
    expansions = int(dbscan.fit(x, cfg).expansions)
    snapshots = []
    res, _ = dbscan.fit_resumable(
        x, cfg, state_interval=state_interval,
        on_state=lambda read: snapshots.append(read()))
    assert (np.asarray(res.labels) == oracle).all()
    assert int(res.expansions) == expansions
    assert [s.nexp for s in snapshots] == list(
        range(state_interval, expansions, state_interval))
    for snap in snapshots:
        resumed, _ = dbscan.fit_resumable(
            x, cfg, state=dbscan.DBSCANRunState.from_tree(snap.as_tree()),
            state_interval=state_interval)
        assert (np.asarray(resumed.labels) == oracle).all()
        assert int(resumed.expansions) == expansions
        assert int(resumed.n_clusters) == int(res.n_clusters)


def test_dbscan_kernel_vs_ref_path():
    key = jax.random.PRNGKey(11)
    x, _, _ = make_blobs(key, ClusterSpec(2, 4, 128))
    cfg_k = dbscan.DBSCANConfig.paper_defaults(2)
    cfg_r = dbscan.DBSCANConfig(eps=cfg_k.eps, min_pts=cfg_k.min_pts,
                                use_kernel=False)
    a = dbscan.fit(x, cfg_k)
    b = dbscan.fit(x, cfg_r)
    assert (np.asarray(a.labels) == np.asarray(b.labels)).all()
    assert int(a.n_clusters) == int(b.n_clusters)


def test_dbscan_all_noise_and_one_cluster():
    # far-apart points: all noise
    x = jnp.arange(32, dtype=jnp.float32)[:, None] * 100.0
    cfg = dbscan.DBSCANConfig(eps=1.0, min_pts=3)
    res = dbscan.fit(x, cfg)
    assert int(res.n_clusters) == 0
    assert (np.asarray(res.labels) == 0).all()
    # one tight blob: one cluster, no noise
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 2)) * 0.01
    res = dbscan.fit(x, dbscan.DBSCANConfig(eps=1.0, min_pts=3))
    assert int(res.n_clusters) == 1
    assert (np.asarray(res.labels) == 1).all()


def test_dbscan_state_word_roundtrip():
    """The paper's int16 packed state: 3 flag bits + 13-bit cluster id."""
    labels = jnp.array([0, 1, 5, 4095], jnp.int32)
    vis = jnp.array([True, True, False, True])
    mem = jnp.array([False, True, False, True])
    core = jnp.array([False, True, True, False])
    w = dbscan.pack_state(labels, vis, mem, core)
    assert w.dtype == jnp.int16
    l2, v2, m2, c2 = dbscan.unpack_state(w)
    assert (np.asarray(l2) == np.asarray(labels)).all()
    assert (np.asarray(v2) == np.asarray(vis)).all()
    assert (np.asarray(m2) == np.asarray(mem)).all()
    assert (np.asarray(c2) == np.asarray(core)).all()
    # finish() deletes the first three bits (paper)
    fin = dbscan.finish(w)
    assert (np.asarray(fin) == np.asarray(labels)).all()


def test_dbscan_cancellation_midway():
    key = jax.random.PRNGKey(5)
    x, _, _ = make_blobs(key, ClusterSpec(2, 8, 256))
    cfg = dbscan.DBSCANConfig.paper_defaults(2)
    token = CancellationToken()
    token.cancel(CancelReason.USER)  # cancel before start: must stop fast
    res = dbscan.fit_cancellable(x, cfg, token=token)
    assert res.cancelled
    assert int(res.n_clusters) == 0


@given(seed=st.integers(0, 2**31 - 1), features=st.integers(1, 3),
       clusters=st.integers(2, 5))
@settings(**_HYPO)
def test_dbscan_invariants(seed, features, clusters):
    """Properties: every core point is clustered; noise points are non-core;
    labels bounded by n_clusters; deterministic across runs."""
    key = jax.random.PRNGKey(seed)
    x, _, _ = make_blobs(key, ClusterSpec(features, clusters, 64))
    cfg = dbscan.DBSCANConfig.paper_defaults(features)
    res = dbscan.fit(x, cfg)
    labels = np.asarray(res.labels)
    core = np.asarray(res.core_mask)
    assert (labels[core] > 0).all()          # core points always clustered
    assert (labels >= 0).all() and (labels <= int(res.n_clusters)).all()
    res2 = dbscan.fit(x, cfg)
    assert (np.asarray(res2.labels) == labels).all()


# -- K-Means -------------------------------------------------------------------


def test_kmeans_paper_stop_rule(rng_key):
    x, _, _ = make_blobs(rng_key, ClusterSpec(2, 6, 128))
    cfg = kmeans.KMeansConfig(k=6)
    res = kmeans.fit(jax.random.PRNGKey(7), x, cfg)
    assert bool(res.converged)
    assert int(res.iterations) < kmeans.PAPER_MAX_ITERS
    assert res.labels.dtype == jnp.int16  # paper's 16-bit label word


def test_kmeans_monotone_inertia(rng_key):
    """Lloyd iterations never increase inertia."""
    x, _, _ = make_blobs(rng_key, ClusterSpec(2, 4, 128))
    cfg = kmeans.KMeansConfig(k=4)
    c = kmeans.init_centroids(jax.random.PRNGKey(1), x, cfg)
    last = np.inf
    for _ in range(10):
        _, c, _, inertia = jax.jit(
            lambda x, c: kmeans.kmeans_step(x, c, cfg)
        )(x, c)
        assert float(inertia) <= last + 1e-3
        last = float(inertia)


def test_kmeans_kernel_vs_ref_path(rng_key):
    x, _, _ = make_blobs(rng_key, ClusterSpec(4, 4, 128))
    k0 = jax.random.PRNGKey(3)
    r1 = kmeans.fit(k0, x, kmeans.KMeansConfig(k=4, use_kernel=True))
    r2 = kmeans.fit(k0, x, kmeans.KMeansConfig(k=4, use_kernel=False))
    np.testing.assert_allclose(r1.centroids, r2.centroids, rtol=1e-4,
                               atol=1e-4)


def test_kmeans_empty_cluster_keeps_center():
    # k > distinct points: some clusters must stay empty and keep centers
    x = jnp.array([[0.0, 0.0], [0.0, 0.0], [10.0, 10.0], [10.0, 10.0]])
    cfg = kmeans.KMeansConfig(k=3, max_iters=5)
    res = kmeans.fit(jax.random.PRNGKey(0), x, cfg)
    assert np.isfinite(np.asarray(res.centroids)).all()


def test_kmeans_plus_plus_beats_random_seeding():
    key = jax.random.PRNGKey(123)
    x, _, _ = make_blobs(key, ClusterSpec(2, 8, 128))
    inert = {}
    for init in ("sample", "kmeans++"):
        tot = 0.0
        for s in range(5):
            cfg = kmeans.KMeansConfig(k=8, init=init)
            tot += float(kmeans.fit(jax.random.PRNGKey(s), x, cfg).inertia)
        inert[init] = tot / 5
    assert inert["kmeans++"] <= inert["sample"] * 1.05


def test_kmeans_cancellable_matches_jit(rng_key):
    x, _, _ = make_blobs(rng_key, ClusterSpec(2, 4, 128))
    cfg = kmeans.KMeansConfig(k=4)
    a = kmeans.fit(jax.random.PRNGKey(9), x, cfg)
    b = kmeans.fit_cancellable(jax.random.PRNGKey(9), x, cfg)
    np.testing.assert_allclose(a.centroids, b.centroids, rtol=1e-5)
    assert int(a.iterations) == int(b.iterations)


def test_kmeans_cancel_latency():
    """Cancel must be honoured between steps (paper: 'timely')."""
    x, _, _ = make_blobs(jax.random.PRNGKey(2), ClusterSpec(4, 8, 512))
    cfg = kmeans.KMeansConfig(k=8, tol=0.0, max_iters=100_000)  # never converges
    token = CancellationToken()
    steps_done = []

    def progress(it, shift):
        steps_done.append(it)
        if it == 3:
            token.cancel()

    res = kmeans.fit_cancellable(jax.random.PRNGKey(0), x, cfg, token=token,
                                 on_progress=progress)
    assert res.cancelled
    assert int(res.iterations) == 3  # stopped at the next boundary


def test_minibatch_kmeans_reasonable(rng_key):
    x, _, _ = make_blobs(rng_key, ClusterSpec(2, 4, 512))
    full = kmeans.fit(jax.random.PRNGKey(1), x, kmeans.KMeansConfig(k=4))
    # mini-batch is init-sensitive: random "sample" seeding can collapse two
    # centers onto one blob and never recover from partial updates
    mb = kmeans.minibatch_fit(jax.random.PRNGKey(1), x,
                              kmeans.KMeansConfig(k=4, init="kmeans++"),
                              batch_size=256, steps=100)
    assert float(mb.inertia) < 3.0 * float(full.inertia)


# -- distributed equivalence (subprocess with 8 host devices) -----------------

_DISTRIBUTED_SCRIPT = r"""
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import sys; sys.path.insert(0, {src!r})
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.distributed import (make_sharded_kmeans_step, ring_degree,
                                    ring_expand)
from repro.core.kmeans import KMeansConfig, kmeans_step
from repro.kernels.neighbor.ref import epsilon_degree_ref, expand_frontier_ref
from repro.data.synthetic import ClusterSpec, make_blobs

mesh = jax.make_mesh((4, 2), ('data', 'model'),
                     (jax.sharding.AxisType.Auto,) * 2)
x, _, _ = make_blobs(jax.random.PRNGKey(0), ClusterSpec(2, 4, 128))
cfg = KMeansConfig(k=4, use_kernel=False)
c0 = x[:4].astype(jnp.float32)
step = make_sharded_kmeans_step(mesh, cfg)
xs = jax.device_put(x, NamedSharding(mesh, P(('data',), None)))
a, c1, shift, inertia = step(xs, c0)
_, c1r, _, _ = jax.jit(lambda x, c: kmeans_step(x, c, cfg))(x, c0)
np.testing.assert_allclose(np.asarray(c1), np.asarray(c1r), rtol=1e-5)

deg = ring_degree(mesh, xs, 1.4)
assert (np.asarray(deg) == np.asarray(epsilon_degree_ref(x, 1.4))).all()
f = np.zeros(x.shape[0], bool); f[::17] = True
fs = jax.device_put(jnp.asarray(f), NamedSharding(mesh, P(('data',))))
r = ring_expand(mesh, xs, fs, 1.4)
assert (np.asarray(r) == np.asarray(expand_frontier_ref(x, jnp.asarray(f), 1.4))).all()
print('DISTRIBUTED_OK')
"""


@pytest.mark.slow
def test_distributed_equivalence_subprocess():
    import os

    src = os.path.join(os.path.dirname(__file__), "..", "src")
    script = _DISTRIBUTED_SCRIPT.format(src=os.path.abspath(src))
    out = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert "DISTRIBUTED_OK" in out.stdout, out.stderr[-2000:]
