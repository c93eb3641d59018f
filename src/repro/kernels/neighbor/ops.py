"""Jit'd public wrappers for the DBSCAN neighborhood kernels.

Padding contract: points are padded with a large coordinate (1e10) in the
first feature column, which puts padding at squared distance >= ~1e20 from
every real point — outside any realistic eps — without overflowing fp32 in
the norm decomposition.  Padding frontier entries are zero so they can never
spread reachability.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.neighbor.neighbor import (
    DEFAULT_BLOCK_I,
    DEFAULT_BLOCK_J,
    degree_kernel,
    expand_kernel,
)
from repro.runtime.backend import pallas_interpret

_PAD_COORD = 1e10


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _blocks(n: int, block_i: Optional[int], block_j: Optional[int]):
    return (block_i or min(DEFAULT_BLOCK_I, _round_up(n, 8)),
            block_j or min(DEFAULT_BLOCK_J, _round_up(n, 8)))


def pad_points(x: jnp.ndarray, *, block_i: Optional[int] = None,
               block_j: Optional[int] = None) -> jnp.ndarray:
    """``x`` as the neighbourhood kernels read it: rows padded to a
    multiple of both blocks, features to 128 lanes."""
    n, d = x.shape
    bi, bj = _blocks(n, block_i, block_j)
    n_pad = _round_up(n, max(bi, bj))
    d_pad = _round_up(d, 128)
    xp = jnp.zeros((n_pad, d_pad), x.dtype).at[:, 0].set(_PAD_COORD)
    xp = xp.at[:n, :d].set(x)
    return xp.at[:n, d:].set(0.0)


@functools.partial(jax.jit, static_argnames=("block_i", "block_j", "interpret"))
def epsilon_degree(
    x: jnp.ndarray,
    eps: jnp.ndarray | float,
    *,
    block_i: Optional[int] = None,
    block_j: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """|N_eps(p)| for every point (self included), int32 (n,)."""
    if interpret is None:
        interpret = pallas_interpret()
    n, _ = x.shape
    bi, bj = _blocks(n, block_i, block_j)
    xp = pad_points(x, block_i=bi, block_j=bj)
    eps2 = jnp.asarray(eps, jnp.float32) ** 2
    deg = degree_kernel(xp, eps2, block_i=bi, block_j=bj, interpret=interpret)
    return deg[:n, 0]


def expand_padded(
    xp: jnp.ndarray,
    frontier: jnp.ndarray,
    eps: jnp.ndarray | float,
    *,
    block_i: Optional[int] = None,
    block_j: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """:func:`expand_frontier` on points already padded by
    :func:`pad_points` (same blocks): a loop that expands many frontiers
    of one point set pads it once."""
    if interpret is None:
        interpret = pallas_interpret()
    n = frontier.shape[0]
    bi, bj = _blocks(n, block_i, block_j)
    fp = jnp.zeros((xp.shape[0], 1), jnp.float32).at[:n, 0].set(
        frontier.astype(jnp.float32)
    )
    eps2 = jnp.asarray(eps, jnp.float32) ** 2
    counts = expand_kernel(xp, fp, eps2, block_i=bi, block_j=bj,
                           interpret=interpret)
    return counts[:n, 0] > 0.5


@functools.partial(jax.jit, static_argnames=("block_i", "block_j", "interpret"))
def expand_frontier(
    x: jnp.ndarray,
    frontier: jnp.ndarray,
    eps: jnp.ndarray | float,
    *,
    block_i: Optional[int] = None,
    block_j: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Bool (n,): within eps of some frontier point (the expansion kernel)."""
    xp = pad_points(x, block_i=block_i, block_j=block_j)
    return expand_padded(xp, frontier, eps, block_i=block_i,
                         block_j=block_j, interpret=interpret)
