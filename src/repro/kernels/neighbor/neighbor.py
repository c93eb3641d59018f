"""Pallas TPU kernels: DBSCAN epsilon-neighborhood queries.

The paper uses two OpenCL kernels with "almost the same purpose": one decides
core-point reachability in the main loop, one expands clusters.  Both reduce
to rows of the epsilon-adjacency matrix A = [ d2(i,j) <= eps^2 ].  On the Mali
GPU each work-item scans its row; on TPU we tile the n x n matrix into
(bn, bm) VMEM blocks, build each tile from the MXU decomposition

    d2 = ||x_i||^2 - 2 x_i . x_j + ||x_j||^2

and reduce tiles on the fly so A is **never materialized in HBM** (the
quadratic object exists only one VMEM tile at a time — the TPU analogue of
the paper's pinned zero-copy buffers).

Kernel 1 — degree:   deg[i]     = sum_j A[i, j]            (VPU row reduce)
Kernel 2 — expand:   reach[i]   = sum_j A[i, j] * front[j]  (MXU mat-vec)

Layout: grid (row-tiles, col-tiles), col dimension sequential ("arbitrary")
because it carries the running accumulator in the output VMEM block.
eps^2 arrives as a (1, 1) SMEM-style operand rather than a captured constant
so eps sweeps do not retrace.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK_I = 512
DEFAULT_BLOCK_J = 512


def _tile_d2(xi, xj):
    """Squared-distance tile via the MXU decomposition, fp32."""
    xi = xi.astype(jnp.float32)
    xj = xj.astype(jnp.float32)
    cross = jax.lax.dot_general(
        xi, xj,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    ni = jnp.sum(xi * xi, axis=1)  # (bi,)
    nj = jnp.sum(xj * xj, axis=1)  # (bj,)
    return ni[:, None] - 2.0 * cross + nj[None, :]


def _degree_kernel(eps2_ref, xi_ref, xj_ref, deg_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        deg_ref[...] = jnp.zeros_like(deg_ref)

    d2 = _tile_d2(xi_ref[...], xj_ref[...])
    adj = (d2 <= eps2_ref[0, 0]).astype(jnp.int32)
    deg_ref[...] += jnp.sum(adj, axis=1, keepdims=True)


def _expand_kernel(eps2_ref, xi_ref, xj_ref, front_ref, out_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    d2 = _tile_d2(xi_ref[...], xj_ref[...])
    adj = (d2 <= eps2_ref[0, 0]).astype(jnp.float32)
    # (bi, bj) @ (bj, 1) on the MXU: count of frontier neighbors in this tile
    out_ref[...] += jax.lax.dot_general(
        adj, front_ref[...].astype(jnp.float32),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


@functools.partial(jax.jit, static_argnames=("block_i", "block_j", "interpret"))
def degree_kernel(
    x: jnp.ndarray,
    eps2: jnp.ndarray,
    *,
    block_i: int = DEFAULT_BLOCK_I,
    block_j: int = DEFAULT_BLOCK_J,
    interpret: bool = False,
) -> jnp.ndarray:
    """Pre-padded entry: x (n, d), n % block == 0, d % 128 == 0 -> (n, 1) i32."""
    n, d = x.shape
    assert n % block_i == 0 and n % block_j == 0 and d % 128 == 0
    grid = (n // block_i, n // block_j)
    return pl.pallas_call(
        _degree_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((block_i, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_j, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_i, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1), jnp.int32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(eps2.reshape(1, 1), x, x)


@functools.partial(jax.jit, static_argnames=("block_i", "block_j", "interpret"))
def expand_kernel(
    x: jnp.ndarray,
    frontier: jnp.ndarray,
    eps2: jnp.ndarray,
    *,
    block_i: int = DEFAULT_BLOCK_I,
    block_j: int = DEFAULT_BLOCK_J,
    interpret: bool = False,
) -> jnp.ndarray:
    """Pre-padded entry: frontier (n, 1) f32 in {0,1} -> neighbor counts (n, 1) f32."""
    n, d = x.shape
    assert frontier.shape == (n, 1)
    assert n % block_i == 0 and n % block_j == 0 and d % 128 == 0
    grid = (n // block_i, n // block_j)
    return pl.pallas_call(
        _expand_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((block_i, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_j, d), lambda i, j: (j, 0)),
            pl.BlockSpec((block_j, 1), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_i, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(eps2.reshape(1, 1), x, x, frontier)
