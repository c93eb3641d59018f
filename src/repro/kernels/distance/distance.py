"""Pallas TPU kernel: K-Means assignment (distance-to-centroids + argmin).

TPU adaptation of the paper's OpenCL assignment kernel.  On the Mali GPU each
work-item loops over centroids computing one distance at a time.  On TPU the
same computation is recast for the MXU:

    ||x - c||^2 = ||x||^2 - 2 x·c + ||c||^2

The cross term is a (bn, d) x (d, bk) matmul executed on the 128x128 systolic
array; ||c||^2 is a cheap VPU reduction per centroid tile; ||x||^2 is constant
per point so it cannot change the argmin and is *omitted inside the kernel*
(ops.py adds it back when true distances are requested).  This turns a
bandwidth-bound per-point loop into a compute-dense tile loop — the TPU
version of the paper's "avoid unnecessary memory operations" advice
(CL_MEM_USE_HOST_PTR / pinned buffers): the running (min, argmin) pair for a
point-tile lives in the output VMEM block across all centroid tiles and is
written to HBM exactly once.

Layout notes:
- block shapes are multiples of (8, 128) (VPU lanes) and feed the MXU with
  d padded to a multiple of 128;
- the grid is (points-tiles, centroid-tiles) with the centroid dimension
  marked "arbitrary" (sequential) because it carries the running min;
- outputs are (n, 1)-shaped so Mosaic keeps them as [8,128]-tileable 2D refs;
  ops.py squeezes them.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK_N = 512   # points per tile
DEFAULT_BLOCK_K = 128   # centroids per tile

_BIG = 3.4e38  # +inf stand-in that survives arithmetic (python float: kernels
# must not capture traced constants)


def _assign_kernel(x_ref, c_ref, val_ref, idx_ref, *, block_k: int):
    """One (point-tile, centroid-tile) grid step.

    x_ref:   (bn, d)  VMEM — point tile
    c_ref:   (bk, d)  VMEM — centroid tile
    val_ref: (bn, 1)  VMEM — running min of (||c||^2 - 2 x·c)  (persistent)
    idx_ref: (bn, 1)  VMEM — running argmin (persistent)
    """
    j = pl.program_id(1)

    # init the running pair on the first centroid tile
    @pl.when(j == 0)
    def _init():
        val_ref[...] = jnp.full(val_ref.shape, _BIG, val_ref.dtype)
        idx_ref[...] = jnp.zeros_like(idx_ref)

    x = x_ref[...].astype(jnp.float32)
    c = c_ref[...].astype(jnp.float32)

    # MXU: cross term.  (bn, d) @ (d, bk) -> (bn, bk), fp32 accumulation.
    cross = jax.lax.dot_general(
        x, c,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    cnorm = jnp.sum(c * c, axis=1)  # (bk,)
    # score = ||c||^2 - 2 x·c  (+||x||^2 omitted: constant per row)
    score = cnorm[None, :] - 2.0 * cross  # (bn, bk)

    # tile-local (min, first-argmin)
    tile_min = jnp.min(score, axis=1, keepdims=True)  # (bn, 1)
    col = jax.lax.broadcasted_iota(jnp.int32, score.shape, 1)
    tile_idx = jnp.min(
        jnp.where(score == tile_min, col, jnp.int32(block_k)), axis=1, keepdims=True
    ) + j * block_k  # global centroid index, first occurrence within tile

    # combine with the running pair; strict < keeps the first (lowest-j) winner
    run_val = val_ref[...]
    better = tile_min < run_val
    val_ref[...] = jnp.where(better, tile_min, run_val)
    idx_ref[...] = jnp.where(better, tile_idx, idx_ref[...])


@functools.partial(
    jax.jit, static_argnames=("block_n", "block_k", "interpret")
)
def assign_clusters_kernel(
    x: jnp.ndarray,
    c: jnp.ndarray,
    *,
    block_n: int = DEFAULT_BLOCK_N,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Raw kernel entry.  Requires pre-padded shapes:

    x: (n, d) with n % block_n == 0, d % 128 == 0
    c: (k, d) with k % block_k == 0; padding centroid rows must be _BIG-normed
       (ops.py pads with 1e19 so they never win the argmin).

    Returns (score_min (n,1) f32, argmin (n,1) i32) where score omits ||x||^2.
    """
    n, d = x.shape
    k, dc = c.shape
    assert d == dc, (d, dc)
    assert n % block_n == 0 and k % block_k == 0 and d % 128 == 0

    grid = (n // block_n, k // block_k)
    return pl.pallas_call(
        functools.partial(_assign_kernel, block_k=block_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_k, d), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.int32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(x, c)
