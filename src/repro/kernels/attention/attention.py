"""Pallas TPU kernel: causal flash attention (forward), online softmax.

The serving-path hot spot (32k prefill).  Grid: (batch*heads, q-blocks,
k-blocks), k-dimension sequential ("arbitrary") because it carries the
online-softmax running state in VMEM scratch:

    m (bq, 1)  running row max        — VPU reduce per tile
    l (bq, 1)  running normalizer
    acc (bq, d) unnormalized output   — accumulated in fp32 in VMEM

MXU feeds: the (bq, d) x (d, bk) score tile and the (bq, bk) x (bk, d)
value tile.  Block sizes default (256, 512) so the working set
(q + k + v + scores + acc ~ (bq+2bk)*d*4 + bq*bk*4) stays well inside the
16 MB/core VMEM at d=128.

Causal handling: whole k-blocks strictly above the diagonal are skipped
(pl.when on block indices — Mosaic elides the compute); the diagonal block
applies an element mask.  Padded key positions (seq not divisible by the
block) are masked via the kv_len scalar operand.

Forward-only by design: training attention goes through XLA (DESIGN.md §4)
— the dry-run cost model must see the attention FLOPs, and a custom-call
would hide them; serving uses this kernel on real hardware.
"""

from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 512


def _flash_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *,
                  block_q: int, block_k: int, scale: float, causal: bool):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, -1e30, m_scr.dtype)
        l_scr[...] = jnp.zeros(l_scr.shape, l_scr.dtype)
        acc_scr[...] = jnp.zeros(acc_scr.shape, acc_scr.dtype)

    q_start = qi * block_q
    k_start = ki * block_k
    # causal: skip k-blocks entirely above the diagonal
    live = (k_start <= q_start + block_q - 1) if causal else (k_start >= 0)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale          # (bq, d)
        k = k_ref[0].astype(jnp.float32)                  # (bk, d)
        s = jax.lax.dot_general(
            q, k, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                 # (bq, bk)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < len_ref[0, 0]                       # padded keys
        if causal:
            mask = mask & (kpos <= qpos)
        s = jnp.where(mask, s, -1e30)

        m_prev = m_scr[...]                               # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                            # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)                   # (bq, 1)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = alpha * acc_scr[...] + jax.lax.dot_general(
            p, v_ref[0].astype(jnp.float32),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_q", "block_k", "causal", "interpret"),
)
def flash_attention_kernel(
    q: jnp.ndarray,      # (BH, Sq, D) pre-padded
    k: jnp.ndarray,      # (BH, Sk, D)
    v: jnp.ndarray,
    kv_len: jnp.ndarray,  # () int32: true (unpadded) key length
    *,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    causal: bool = True,
    interpret: bool = False,
) -> jnp.ndarray:
    bh, sq, d = q.shape
    _, sk, _ = k.shape
    assert sq % block_q == 0 and sk % block_k == 0, (sq, sk)
    scale = 1.0 / math.sqrt(d)
    grid = (bh, sq // block_q, sk // block_k)

    scratch = [
        pltpu.VMEM((block_q, 1), jnp.float32),
        pltpu.VMEM((block_q, 1), jnp.float32),
        pltpu.VMEM((block_q, d), jnp.float32),
    ]

    return pl.pallas_call(
        functools.partial(
            _flash_kernel, block_q=block_q, block_k=block_k,
            scale=scale, causal=causal,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda b, i, j: (0, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(kv_len.reshape(1, 1), q, k, v)
