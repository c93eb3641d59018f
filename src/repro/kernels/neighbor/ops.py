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


def _pad_points(x: jnp.ndarray, block: int):
    n, d = x.shape
    n_pad = _round_up(n, block)
    d_pad = _round_up(d, 128)
    xp = jnp.zeros((n_pad, d_pad), x.dtype).at[:, 0].set(_PAD_COORD)
    xp = xp.at[:n, :d].set(x)
    xp = xp.at[:n, d:].set(0.0)
    return xp, n_pad, d_pad


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
    bi = block_i or min(DEFAULT_BLOCK_I, _round_up(n, 8))
    bj = block_j or min(DEFAULT_BLOCK_J, _round_up(n, 8))
    b = max(bi, bj)
    xp, _, _ = _pad_points(x, b)
    eps2 = jnp.asarray(eps, jnp.float32) ** 2
    deg = degree_kernel(xp, eps2, block_i=bi, block_j=bj, interpret=interpret)
    return deg[:n, 0]


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
    if interpret is None:
        interpret = pallas_interpret()
    n, _ = x.shape
    bi = block_i or min(DEFAULT_BLOCK_I, _round_up(n, 8))
    bj = block_j or min(DEFAULT_BLOCK_J, _round_up(n, 8))
    b = max(bi, bj)
    xp, n_pad, _ = _pad_points(x, b)
    fp = jnp.zeros((n_pad, 1), jnp.float32).at[:n, 0].set(
        frontier.astype(jnp.float32)
    )
    eps2 = jnp.asarray(eps, jnp.float32) ** 2
    counts = expand_kernel(xp, fp, eps2, block_i=bi, block_j=bj,
                           interpret=interpret)
    return counts[:n, 0] > 0.5
