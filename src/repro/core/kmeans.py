"""K-Means (Lloyd) — the paper's algorithm, single-device and distributed.

Paper semantics kept exactly (§II.C):
- Lloyd iterations, single precision;
- stop when the sum of absolute centroid displacements < 1e-6, or after
  100,000 iterations ("should avoid endless loops due to cycling which
  occurs from time to time with single precision");
- the assignment step is the accelerator kernel (one kernel: distance to
  every center + argmin) — here :mod:`repro.kernels.distance`;
- the per-point cluster id is stored in a 16-bit word (int16 labels).

TPU adaptations:
- the centroid *update* is also MXU work: one-hot(assign)ᵀ · X is a
  (k, n) x (n, d) matmul instead of a scatter-add (TPUs have no fast
  scatter; the systolic array eats this shape);
- the distributed path needs **no custom communication**: with points
  sharded over the (pod, data) mesh axes and centroids replicated, GSPMD
  turns the one-hot matmul + counts into partial sums + an all-reduce over
  exactly the sharded axes.  `distributed_fit` below is the single-device
  `fit` jitted with shardings — the paper's "same OpenCL code, different
  device" portability story, at pod scale.

Two execution modes, mirroring the paper's abort protocol:
- :func:`fit` — fully jitted `lax.while_loop`; one uninterruptible dispatch
  (the fastest path; used by benchmarks);
- :func:`fit_cancellable` — host loop calling the jitted step, polling a
  :class:`~repro.core.cancellation.CancellationToken` between steps ("the
  flag is tested between OpenCL kernel executions").
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cancellation import CancellationToken
from repro.kernels.distance.ops import assign_clusters
from repro.kernels.distance.ref import assign_clusters_ref

# Paper defaults.
PAPER_TOL = 1e-6
PAPER_MAX_ITERS = 100_000


@dataclasses.dataclass(frozen=True)
class KMeansConfig:
    k: int
    max_iters: int = PAPER_MAX_ITERS
    tol: float = PAPER_TOL
    init: str = "sample"          # "sample" (paper: random points) | "kmeans++"
    use_kernel: bool = True        # Pallas assignment kernel vs jnp oracle
    block_n: Optional[int] = None
    block_k: Optional[int] = None


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=("centroids", "labels", "inertia", "iterations", "converged"),
    meta_fields=("cancelled",),
)
@dataclasses.dataclass
class KMeansResult:
    centroids: jax.Array   # (k, d) f32
    labels: jax.Array      # (n,) int16 — paper's 16-bit per-point word
    inertia: jax.Array     # () f32 sum of squared distances
    iterations: jax.Array  # () i32
    converged: jax.Array   # () bool (False if cancelled / max_iters)
    cancelled: bool = False


def _assign(x, c, cfg: KMeansConfig):
    if cfg.use_kernel:
        return assign_clusters(x, c, block_n=cfg.block_n, block_k=cfg.block_k)
    return assign_clusters_ref(x, c)


def _update_centroids(x, assign, k: int, c_old):
    """One-hot matmul centroid update (MXU-friendly; GSPMD-reducible)."""
    onehot = jax.nn.one_hot(assign, k, dtype=jnp.float32)      # (n, k)
    sums = jnp.einsum("nk,nd->kd", onehot, x.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)
    counts = jnp.sum(onehot, axis=0)                            # (k,)
    has_pts = counts > 0
    safe = jnp.where(has_pts, counts, 1.0)[:, None]
    # empty cluster: keep the old center (paper does not respawn centers)
    return jnp.where(has_pts[:, None], sums / safe, c_old)


def kmeans_step(x, c, cfg: KMeansConfig):
    """(assignment, new centroids, displacement, inertia)."""
    assign, d2 = _assign(x, c, cfg)
    c_new = _update_centroids(x, assign, cfg.k, c)
    shift = jnp.sum(jnp.abs(c_new - c))
    return assign, c_new, shift, jnp.sum(d2)


@functools.partial(jax.jit, static_argnames=("cfg",))
def kmeans_step_jit(x, c, cfg: KMeansConfig):
    """Module-level jitted step: cached across host-loop invocations, so a
    service running many same-shaped requests compiles once per shape."""
    return kmeans_step(x, c, cfg)


def masked_kmeans_step(x, c, mask, cfg: KMeansConfig):
    """Lloyd step over a padded batch item: masked-out rows carry no weight.

    With ``mask`` all-True this is bit-for-bit :func:`kmeans_step` on the
    same rows; padded rows are still assigned (row-wise kernel) but
    contribute zero to the centroid sums, counts, and inertia — the
    service's micro-batcher pads requests to a bucket size without
    perturbing their results.
    """
    assign, d2 = _assign(x, c, cfg)
    w = mask.astype(jnp.float32)
    onehot = jax.nn.one_hot(assign, cfg.k, dtype=jnp.float32) * w[:, None]
    sums = jnp.einsum("nk,nd->kd", onehot, x.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)
    counts = jnp.sum(onehot, axis=0)
    has_pts = counts > 0
    safe = jnp.where(has_pts, counts, 1.0)[:, None]
    c_new = jnp.where(has_pts[:, None], sums / safe, c)
    shift = jnp.sum(jnp.abs(c_new - c))
    return assign, c_new, shift, jnp.sum(d2 * w)


@functools.partial(jax.jit, static_argnames=("cfg",))
def masked_kmeans_step_jit(x, c, mask, cfg: KMeansConfig):
    return masked_kmeans_step(x, c, mask, cfg)


def fused_masked_kmeans_step(x, c, mask, cfg: KMeansConfig):
    """:func:`masked_kmeans_step` via the fused single-pass pallas kernel.

    Distance, argmin, and the masked per-centroid sum/count/inertia
    accumulation happen in ONE pass over ``x`` (see
    ``kernels/distance/fused.py``); only the empty-cluster fix-up and the
    shift reduction remain host-side XLA.  Same (assign, c_new, shift,
    inertia) contract as the reference step — ``tests/test_fused_kernel.py``
    pins the agreement.
    """
    from repro.kernels.distance.fused import fused_masked_assign_update

    assign, sums, counts, inertia = fused_masked_assign_update(
        x, c, mask, block_n=cfg.block_n)
    has_pts = counts > 0
    safe = jnp.where(has_pts, counts, 1.0)[:, None]
    # empty cluster: keep the old center (paper does not respawn centers)
    c_new = jnp.where(has_pts[:, None], sums / safe, c)
    shift = jnp.sum(jnp.abs(c_new - c))
    return assign, c_new, shift, inertia


@functools.partial(jax.jit, static_argnames=("cfg",))
def fused_masked_kmeans_step_jit(x, c, mask, cfg: KMeansConfig):
    return fused_masked_kmeans_step(x, c, mask, cfg)


def masked_step_fn(cfg: KMeansConfig):
    """The serving hot loop's step: the fused pallas kernel for kernel
    configs, the XLA reference otherwise (the ``jax-ref`` fallback path)."""
    if cfg.use_kernel:
        return fused_masked_kmeans_step_jit
    return masked_kmeans_step_jit


def init_centroids(key: jax.Array, x: jax.Array, cfg: KMeansConfig) -> jax.Array:
    if cfg.init == "sample":
        # paper: "initial cluster centers were selected randomly by each
        # implementation"
        idx = jax.random.choice(key, x.shape[0], (cfg.k,), replace=False)
        return x[idx].astype(jnp.float32)
    if cfg.init == "kmeans++":
        return _kmeans_pp(key, x, cfg.k)
    raise ValueError(f"unknown init {cfg.init!r}")


def _kmeans_pp(key: jax.Array, x: jax.Array, k: int) -> jax.Array:
    """k-means++ seeding (beyond-paper; D^2 sampling)."""
    n, d = x.shape
    xf = x.astype(jnp.float32)
    k0, key = jax.random.split(key)
    first = xf[jax.random.randint(k0, (), 0, n)]
    cents = jnp.zeros((k, d), jnp.float32).at[0].set(first)
    mind2 = jnp.sum((xf - first) ** 2, axis=1)

    def body(i, carry):
        cents, mind2, key = carry
        key, kc = jax.random.split(key)
        p = mind2 / jnp.maximum(jnp.sum(mind2), 1e-30)
        nxt = xf[jax.random.choice(kc, n, p=p)]
        cents = cents.at[i].set(nxt)
        mind2 = jnp.minimum(mind2, jnp.sum((xf - nxt) ** 2, axis=1))
        return cents, mind2, key

    cents, _, _ = jax.lax.fori_loop(1, k, body, (cents, mind2, key))
    return cents


@functools.partial(jax.jit, static_argnames=("cfg",))
def fit(key: jax.Array, x: jax.Array, cfg: KMeansConfig) -> KMeansResult:
    """Fully jitted Lloyd loop (paper stop rule)."""
    c0 = init_centroids(key, x, cfg)

    def cond(state):
        _, _, shift, it, _ = state
        return (shift >= cfg.tol) & (it < cfg.max_iters)

    def body(state):
        _, c, _, it, _ = state
        assign, c_new, shift, inertia = kmeans_step(x, c, cfg)
        return assign, c_new, shift, it + 1, inertia

    n = x.shape[0]
    state0 = (
        jnp.zeros((n,), jnp.int32),
        c0,
        jnp.float32(jnp.inf),
        jnp.int32(0),
        jnp.float32(jnp.inf),
    )
    assign, c, shift, it, inertia = jax.lax.while_loop(cond, body, state0)
    return KMeansResult(
        centroids=c,
        labels=assign.astype(jnp.int16),
        inertia=inertia,
        iterations=it,
        converged=shift < cfg.tol,
    )


def fit_cancellable(
    key: jax.Array,
    x: jax.Array,
    cfg: KMeansConfig,
    token: Optional[CancellationToken] = None,
    on_progress: Optional[Callable[[int, float], None]] = None,
    *,
    centroids: Optional[jax.Array] = None,
    start_iteration: int = 0,
) -> KMeansResult:
    """Host-driven Lloyd loop; abort flag polled between jitted steps.

    ``centroids``/``start_iteration`` resume an interrupted run: the full
    run state of Lloyd's algorithm is the centroid matrix plus the iteration
    counter, both of which live in the returned result — checkpoint those,
    pass them back in, and the loop continues exactly where it stopped.
    """
    c = (jnp.asarray(centroids, jnp.float32) if centroids is not None
         else init_centroids(key, x, cfg))
    assign = jnp.zeros((x.shape[0],), jnp.int32)
    inertia = jnp.float32(jnp.inf)
    it = start_iteration
    converged = False
    cancelled = False
    for it in range(start_iteration + 1, cfg.max_iters + 1):
        if token is not None and token.cancelled():
            cancelled = True
            it -= 1
            break
        assign, c, shift, inertia = kmeans_step_jit(x, c, cfg)
        if on_progress is not None:
            on_progress(it, float(shift))
        if float(shift) < cfg.tol:
            converged = True
            break
    return KMeansResult(
        centroids=c,
        labels=assign.astype(jnp.int16),
        inertia=inertia,
        iterations=jnp.int32(it),
        converged=jnp.asarray(converged),
        cancelled=cancelled,
    )


@dataclasses.dataclass
class MiniBatchState:
    """Running mini-batch K-Means model: the whole state of a stream.

    ``centroids`` and per-cluster ``counts`` are the Sculley (2010)
    accumulator; ``step`` counts applied mini-batches.  The tree form
    (:meth:`as_tree` / :meth:`from_tree`) is what the service's streaming
    sessions write through the checkpoint store, so a stream's model
    survives the process exactly like a suspended batch job does.
    """

    centroids: jax.Array   # (k, d) f32
    counts: jax.Array      # (k,) f32 — per-cluster points seen so far
    step: int = 0          # mini-batches applied
    n_seen: int = 0        # raw points consumed

    def as_tree(self) -> dict:
        return {
            "centroids": np.asarray(self.centroids, np.float32),
            "counts": np.asarray(self.counts, np.float32),
            "step": np.int64(self.step),
            "n_seen": np.int64(self.n_seen),
        }

    @staticmethod
    def from_tree(tree: dict) -> "MiniBatchState":
        return MiniBatchState(
            centroids=jnp.asarray(tree["centroids"], jnp.float32),
            counts=jnp.asarray(tree["counts"], jnp.float32),
            step=int(tree["step"]),
            n_seen=int(tree["n_seen"]),
        )


def minibatch_init(key: jax.Array, x0: jax.Array,
                   cfg: KMeansConfig) -> MiniBatchState:
    """Seed a stream's model from its first ``>= k`` points."""
    if x0.shape[0] < cfg.k:
        raise ValueError(
            f"need at least k={cfg.k} points to initialise, got {x0.shape[0]}")
    return MiniBatchState(
        centroids=init_centroids(key, x0, cfg),
        counts=jnp.zeros((cfg.k,), jnp.float32),
    )


def _minibatch_update(c, counts, xb, cfg: KMeansConfig):
    """One Sculley step: per-cluster learning rate 1/count."""
    assign, d2 = _assign(xb, c, cfg)
    onehot = jax.nn.one_hot(assign, cfg.k, dtype=jnp.float32)
    bcounts = jnp.sum(onehot, axis=0)
    bsums = jnp.einsum("nk,nd->kd", onehot, xb.astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST)
    counts_new = counts + bcounts
    lr = jnp.where(bcounts > 0, bcounts / jnp.maximum(counts_new, 1.0), 0.0)
    bmean = bsums / jnp.maximum(bcounts, 1.0)[:, None]
    c_new = c + lr[:, None] * (bmean - c)
    return c_new, counts_new, assign, jnp.sum(d2)


@functools.partial(jax.jit, static_argnames=("cfg",))
def minibatch_update_jit(c, counts, xb, cfg: KMeansConfig):
    """Module-level jitted stream step: one compile per (batch shape, cfg),
    shared by every streaming session in the process."""
    return _minibatch_update(c, counts, xb, cfg)


def minibatch_step(state: MiniBatchState, xb: jax.Array,
                   cfg: KMeansConfig) -> MiniBatchState:
    """Advance a stream's model by one mini-batch (jitted under the hood)."""
    c, counts, _, _ = minibatch_update_jit(
        state.centroids, state.counts, jnp.asarray(xb, jnp.float32), cfg)
    return MiniBatchState(
        centroids=c,
        counts=counts,
        step=state.step + 1,
        n_seen=state.n_seen + int(xb.shape[0]),
    )


def minibatch_fit(
    key: jax.Array,
    x: jax.Array,
    cfg: KMeansConfig,
    *,
    batch_size: int = 1024,
    steps: int = 200,
) -> KMeansResult:
    """Mini-batch K-Means (Sculley 2010) — beyond-paper extra for streams."""
    kinit, kloop = jax.random.split(key)
    c0 = init_centroids(kinit, x, cfg)
    n = x.shape[0]

    def body(i, carry):
        c, counts = carry
        kb = jax.random.fold_in(kloop, i)
        idx = jax.random.randint(kb, (batch_size,), 0, n)
        c, counts, _, _ = _minibatch_update(c, counts, x[idx], cfg)
        return c, counts

    c, _ = jax.lax.fori_loop(0, steps, body, (c0, jnp.zeros((cfg.k,))))
    assign, d2 = _assign(x, c, cfg)
    return KMeansResult(
        centroids=c,
        labels=assign.astype(jnp.int16),
        inertia=jnp.sum(d2),
        iterations=jnp.int32(steps),
        converged=jnp.asarray(True),
    )
