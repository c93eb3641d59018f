"""Pipeline parallelism: GPipe schedule over shard_map + collective_permute.

Optional parallelism axis for depth-dominated models (the mandated
production mesh is (pod, data, model); a PP deployment reshapes to
(pod, data, model, pipe) — the sharding-rules table makes that a config
change, not a code change).

Design: the layer stack is split into `P` contiguous stages.  Under
shard_map over the 'pipe' axis every device holds its stage's parameters;
microbatches stream through the ring with `lax.ppermute`.  The schedule is
the classic GPipe fill-drain loop of length M + P - 1; each device computes
every tick (idle ticks compute on garbage and are masked — on TPU the
predictable dataflow beats divergent control flow).

The loop is `lax.fori_loop`-free on purpose: a Python loop of M + P - 1
ticks unrolls into a static HLO pipeline XLA can overlap (ppermute of tick
t+1 against compute of tick t — the latency-hiding scheduler sees
independent ops).  Autodiff works through ppermute (its transpose is the
reverse permute), so `jax.grad` of a pipelined loss is pipeline-parallel
backward for free.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(
    mesh: Mesh,
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    axis: str = "pipe",
):
    """Build a pipelined apply: (stage_params, x_microbatches) -> y.

    stage_params: pytree whose leaves have a leading 'pipe'-sharded stage
    dim (one slice per device).  x_microbatches: (M, mb, ...) replicated.
    Returns (M, mb, ...) outputs (replicated).
    """
    n_stages = mesh.shape[axis]

    def local(params, xs):
        # params: stage slice (leading dim 1); xs: (M, mb, ...)
        params = jax.tree.map(lambda p: p[0], params)
        stage = jax.lax.axis_index(axis)
        m = xs.shape[0]
        ticks = m + n_stages - 1
        buf = jnp.zeros_like(xs[0])          # inter-stage buffer
        outs = jnp.zeros_like(xs)

        fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        for t in range(ticks):
            mb = t - stage                   # microbatch index at my stage
            active = (mb >= 0) & (mb < m)
            # stage 0 reads from the input stream, others from the ring
            x_in = jnp.where(
                stage == 0,
                xs[jnp.clip(t, 0, m - 1)],
                buf,
            )
            y = stage_fn(params, x_in)
            y = jnp.where(active, y, jnp.zeros_like(y))
            # last stage emits; use dynamic index, masked
            emit = active & (stage == n_stages - 1)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs,
                jnp.where(emit, y, outs[jnp.clip(mb, 0, m - 1)]),
                jnp.clip(mb, 0, m - 1),
                axis=0,
            )
            buf = jax.lax.ppermute(y, axis, fwd)
        # replicate results (only the last stage holds them)
        outs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs)),
            axis,
        )
        return outs

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )


def split_stages(tree: Any, n_stages: int) -> Any:
    """Reshape stacked layer params (L, ...) -> (n_stages, L/n_stages, ...)."""

    def f(p):
        l = p.shape[0]
        assert l % n_stages == 0, (l, n_stages)
        return p.reshape(n_stages, l // n_stages, *p.shape[1:])

    return jax.tree.map(f, tree)
