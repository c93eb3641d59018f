"""Fused masked K-Means step kernel: one pass over the points matrix.

The unfused Lloyd step (``core/kmeans.py``) reads ``x`` twice — once in the
assignment kernel, once in the one-hot centroid-update matmul — and pushes
the full ``(n, k)`` one-hot intermediate (plus the assignment vector)
through HBM between the two.  This kernel computes distances, the argmin
assignment, *and* the masked per-centroid sum/count/inertia accumulators in
a single pass over each point tile, so per step ``x`` streams through VMEM
exactly once and the only HBM outputs are the assignment ``(n, 1)`` and the
``(k, d)``-sized accumulators.  ``benchmarks/roofline.py`` quantifies the
traffic saved (the Green-Computing survey's "memory operations dominate"
finding, applied to our own hot loop).

Kernel layout (all distance.py conventions kept):
- grid is point tiles only, marked "arbitrary" (sequential): the sum /
  count / inertia output blocks map every grid step to block (0, 0), so
  they live in VMEM across the whole pass and are written to HBM once;
- the full padded centroid matrix rides in VMEM per tile (k is small for
  clustering workloads — k_pad * d_pad floats);
- the cross term and the one-hotᵀ·x update are both MXU matmuls;
- padding centroid rows carry 1e19 in feature 0 (ops.py scheme), so they
  can never win the argmin and therefore never accumulate mass;
- masked-out point rows enter with weight 0: they are still *assigned*
  (row-wise work, sliced off by the wrapper) but contribute nothing to the
  sums, counts, or inertia — identical semantics to ``masked_kmeans_step``.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.distance.distance import DEFAULT_BLOCK_N, _BIG
from repro.kernels.distance.ops import _round_up
from repro.runtime.backend import pallas_interpret


def _fused_step_kernel(x_ref, c_ref, w_ref, idx_ref, sums_ref, cnt_ref,
                       inert_ref, *, block_k: int):
    """One point-tile grid step.

    x_ref:     (bn, d)  VMEM — point tile
    c_ref:     (kp, d)  VMEM — the WHOLE padded centroid matrix
    w_ref:     (bn, 1)  VMEM — per-point mask weight (0.0 for padding)
    idx_ref:   (bn, 1)  VMEM — assignment for this tile
    sums_ref:  (kp, d)  VMEM — masked per-centroid coordinate sums (persistent)
    cnt_ref:   (1, kp)  VMEM — masked per-centroid counts (persistent)
    inert_ref: (1, 1)   VMEM — masked inertia accumulator (persistent)
    """
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)
        inert_ref[...] = jnp.zeros_like(inert_ref)

    x = x_ref[...].astype(jnp.float32)
    c = c_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)            # (bn, 1)

    # MXU: cross term.  (bn, d) @ (d, kp) -> (bn, kp), fp32 accumulation.
    cross = jax.lax.dot_general(
        x, c,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    cnorm = jnp.sum(c * c, axis=1)                # (kp,)
    # score = ||c||^2 - 2 x·c; ||x||^2 is argmin-neutral and re-added for
    # the inertia below (we have the tile in hand — no extra pass)
    score = cnorm[None, :] - 2.0 * cross          # (bn, kp)
    score = jnp.minimum(score, _BIG)

    tile_min = jnp.min(score, axis=1, keepdims=True)            # (bn, 1)
    col = jax.lax.broadcasted_iota(jnp.int32, score.shape, 1)
    idx = jnp.min(
        jnp.where(score == tile_min, col, jnp.int32(block_k)),
        axis=1, keepdims=True)                    # first-occurrence argmin
    idx_ref[...] = idx

    # in-register masked one-hot: no (n, k) HBM intermediate, and the
    # centroid update becomes a second MXU matmul over the SAME x tile
    onehot = (col == idx).astype(jnp.float32) * w               # (bn, kp)
    sums_ref[...] += jax.lax.dot_general(
        onehot, x,
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )                                             # (kp, d)
    cnt_ref[...] += jnp.sum(onehot, axis=0, keepdims=True).reshape(
        cnt_ref.shape)
    xnorm = jnp.sum(x * x, axis=1, keepdims=True)               # (bn, 1)
    d2 = jnp.maximum(tile_min + xnorm, 0.0)
    inert_ref[...] += jnp.sum(d2 * w, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def fused_step_kernel(
    x: jnp.ndarray,
    c: jnp.ndarray,
    w: jnp.ndarray,
    *,
    block_n: int = DEFAULT_BLOCK_N,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Raw kernel entry.  Requires pre-padded shapes:

    x: (n, d) with n % block_n == 0, d % 128 == 0
    c: (k, d) with k % 8 == 0; padding centroid rows must be _BIG-normed
    w: (n, 1) f32 mask weights, 0.0 on every padding row

    Returns (argmin (n,1) i32, sums (k,d) f32, counts (1,k) f32,
    inertia (1,1) f32).
    """
    n, d = x.shape
    k, dc = c.shape
    assert d == dc, (d, dc)
    assert n % block_n == 0 and k % 8 == 0 and d % 128 == 0
    assert w.shape == (n, 1), w.shape

    grid = (n // block_n,)
    return pl.pallas_call(
        functools.partial(_fused_step_kernel, block_k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),
            pl.BlockSpec((k, d), lambda i: (0, 0)),
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
            pl.BlockSpec((k, d), lambda i: (0, 0)),
            pl.BlockSpec((1, k), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.int32),
            jax.ShapeDtypeStruct((k, d), jnp.float32),
            jax.ShapeDtypeStruct((1, k), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(x, c, w)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def fused_masked_assign_update(
    x: jnp.ndarray,
    c: jnp.ndarray,
    mask: jnp.ndarray,
    *,
    block_n: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused assignment + masked accumulation over unpadded shapes.

    Args:
      x: (n, d) points.
      c: (k, d) centroids.
      mask: (n,) bool — False rows carry no weight.
    Returns:
      (assignment i32 (n,), masked sums f32 (k, d), masked counts f32 (k,),
      masked inertia f32 ()).
    """
    if interpret is None:
        interpret = pallas_interpret()
    n, d = x.shape
    k, _ = c.shape

    bn = block_n or min(DEFAULT_BLOCK_N, _round_up(n, 8))
    n_pad = _round_up(n, bn)
    k_pad = _round_up(k, 8)
    d_pad = _round_up(d, 128)

    xp = jnp.zeros((n_pad, d_pad), x.dtype).at[:n, :d].set(x)
    # padding centroids: huge coordinates -> huge ||c||^2 score, never chosen
    cp = jnp.full((k_pad, d_pad), 0.0, c.dtype).at[:, :1].set(1e19)
    cp = cp.at[:k, :d].set(c)
    wp = jnp.zeros((n_pad, 1), jnp.float32).at[:n, 0].set(
        mask.astype(jnp.float32))

    idx, sums, cnt, inert = fused_step_kernel(
        xp, cp, wp, block_n=bn, interpret=interpret)
    return idx[:n, 0], sums[:k, :d], cnt[0, :k], inert[0, 0]
