"""Jit'd public wrappers around the K-Means assignment kernel.

Handles shape padding to the kernel's tiling contract:
- points padded to a multiple of ``block_n`` with zero rows (sliced off);
- feature dim padded to a multiple of 128 with zeros (distance-neutral);
- centroids padded to a multiple of ``block_k`` with rows of 1e19 so padding
  can never win the argmin (the kernel treats centroid norms as scores).

``interpret`` defaults to True on non-TPU backends so the same call sites run
on this CPU container and compile to Mosaic on real v5e.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.distance.distance import (
    DEFAULT_BLOCK_K,
    DEFAULT_BLOCK_N,
    assign_clusters_kernel,
)
from repro.kernels.distance.ref import pairwise_sq_dists_ref
from repro.runtime.backend import pallas_interpret


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


@functools.partial(
    jax.jit, static_argnames=("block_n", "block_k", "interpret", "with_dists")
)
def assign_clusters(
    x: jnp.ndarray,
    c: jnp.ndarray,
    *,
    block_n: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    with_dists: bool = True,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Nearest-centroid assignment.

    Args:
      x: (n, d) points.
      c: (k, d) centroids.
    Returns:
      (assignment int32 (n,), min squared distance f32 (n,)).
      If ``with_dists=False`` the second output is the kernel score
      (distance minus ||x||^2) — cheaper, argmin-equivalent.
    """
    if interpret is None:
        interpret = pallas_interpret()
    n, d = x.shape
    k, _ = c.shape

    bn = block_n or min(DEFAULT_BLOCK_N, _round_up(n, 8))
    bk = block_k or min(DEFAULT_BLOCK_K, _round_up(k, 8))

    n_pad = _round_up(n, bn)
    k_pad = _round_up(k, bk)
    d_pad = _round_up(d, 128)

    xp = jnp.zeros((n_pad, d_pad), x.dtype).at[:n, :d].set(x)
    # padding centroids: huge coordinates -> huge ||c||^2 score, never chosen
    cp = jnp.full((k_pad, d_pad), 0.0, c.dtype).at[:, :1].set(1e19)
    cp = cp.at[:k, :d].set(c)

    score, idx = assign_clusters_kernel(
        xp, cp, block_n=bn, block_k=bk, interpret=interpret
    )
    idx = idx[:n, 0]
    score = score[:n, 0]
    if with_dists:
        xnorm = jnp.sum(x.astype(jnp.float32) ** 2, axis=1)
        # clamp tiny negatives from the decomposition (catastrophic
        # cancellation when a point sits on a centroid)
        score = jnp.maximum(score + xnorm, 0.0)
    return idx, score


def pairwise_sq_dists(x: jnp.ndarray, c: jnp.ndarray) -> jnp.ndarray:
    """Full (n, k) squared-distance matrix (oracle-backed; small inputs)."""
    return pairwise_sq_dists_ref(x, c)
