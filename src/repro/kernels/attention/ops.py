"""Public wrapper for flash attention: layout + GQA + padding handling."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.attention.attention import (
    DEFAULT_BLOCK_K,
    DEFAULT_BLOCK_Q,
    flash_attention_kernel,
)
from repro.runtime.backend import pallas_interpret


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret")
)
def flash_attention(
    q: jnp.ndarray,   # (B, Sq, H, D)
    k: jnp.ndarray,   # (B, Sk, KV, D)
    v: jnp.ndarray,
    *,
    causal: bool = True,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Returns (B, Sq, H, D).  H % KV == 0 (GQA: kv repeated)."""
    if interpret is None:
        interpret = pallas_interpret()
    b, sq, h, d = q.shape
    _, sk, kv, _ = k.shape
    assert h % kv == 0
    group = h // kv
    if group > 1:
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)

    bq = block_q or min(DEFAULT_BLOCK_Q, _round_up(sq, 8))
    bk = block_k or min(DEFAULT_BLOCK_K, _round_up(sk, 8))
    sq_pad = _round_up(sq, bq)
    sk_pad = _round_up(sk, bk)

    def to_bh(x, s_pad):
        x = jnp.pad(x, ((0, 0), (0, s_pad - x.shape[1]), (0, 0), (0, 0)))
        return x.transpose(0, 2, 1, 3).reshape(b * h, s_pad, d)

    qf = to_bh(q, sq_pad)
    kf = to_bh(k, sk_pad)
    vf = to_bh(v, sk_pad)
    out = flash_attention_kernel(
        qf, kf, vf, jnp.int32(sk),
        block_q=bq, block_k=bk, causal=causal, interpret=interpret,
    )
    out = out.reshape(b, h, sq_pad, d).transpose(0, 2, 1, 3)
    return out[:, :sq]
