"""Distribution-layer tests: flash kernel, sharding resolution, pipeline PP,
optimizer, compression, token pipeline."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypo_shim import HealthCheck, given, settings, strategies as st

from repro.data.tokens import synthetic_token_batch, synthetic_token_batches
from repro.kernels.attention.ops import flash_attention
from repro.kernels.attention.ref import attention_ref
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update
from repro.optim.compress import (
    int8_decode,
    int8_encode,
    topk_decode,
    topk_encode,
)

_HYPO = dict(deadline=None, max_examples=8,
             suppress_health_check=[HealthCheck.too_slow])


# -- flash attention kernel -------------------------------------------------------


@pytest.mark.parametrize(
    "b,sq,h,kv,d",
    [(1, 64, 2, 2, 32), (2, 100, 4, 2, 16), (1, 33, 2, 1, 8),
     (1, 128, 8, 2, 64)],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(b, sq, h, kv, d, dtype):
    key = jax.random.PRNGKey(sq * h + d)
    kq, kk, kvk = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, sq, h, d), jnp.float32).astype(dtype)
    k = jax.random.normal(kk, (b, sq, kv, d), jnp.float32).astype(dtype)
    v = jax.random.normal(kvk, (b, sq, kv, d), jnp.float32).astype(dtype)
    out = flash_attention(q, k, v, block_q=32, block_k=32)
    ref = attention_ref(q, k, v)
    tol = 2e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=tol, atol=tol,
    )


def test_flash_attention_block_sweep():
    key = jax.random.PRNGKey(7)
    q = jax.random.normal(key, (1, 96, 2, 16), jnp.float32)
    ref = attention_ref(q, q, q)
    for bq in (8, 32, 96):
        for bk in (16, 48):
            out = flash_attention(q, q, q, block_q=bq, block_k=bk)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=3e-4, atol=3e-4), (bq, bk)


@given(sq=st.integers(4, 80), h=st.sampled_from([1, 2, 4]),
       d=st.sampled_from([8, 16]), seed=st.integers(0, 2**31 - 1))
@settings(**_HYPO)
def test_flash_attention_property(sq, h, d, seed):
    key = jax.random.PRNGKey(seed)
    q = jax.random.normal(key, (1, sq, h, d), jnp.float32)
    out = flash_attention(q, q, q, block_q=16, block_k=16)
    ref = attention_ref(q, q, q)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=3e-4,
                               atol=3e-4)


def test_flash_attention_causality():
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (1, 64, 2, 16), jnp.float32)
    k2 = q.at[:, 40:].set(0.0)
    a = flash_attention(q, q, q, block_q=16, block_k=16)
    b = flash_attention(q, k2, k2, block_q=16, block_k=16)
    # outputs before position 40 must be identical (causal)
    np.testing.assert_allclose(np.asarray(a[:, :40]), np.asarray(b[:, :40]),
                               rtol=1e-5, atol=1e-5)


# -- AdamW ----------------------------------------------------------------


def test_adamw_bf16_master_weights():
    params = {"w": jnp.ones((64,), jnp.bfloat16)}
    state = adamw_init(params)
    assert "master" in state and state["master"]["w"].dtype == jnp.float32
    grads = {"w": jnp.full((64,), 0.1, jnp.bfloat16)}
    cfg = AdamWConfig(lr=1e-2, weight_decay=0.0)
    p2, s2, m = adamw_update(cfg, params, grads, state)
    assert p2["w"].dtype == jnp.bfloat16
    assert float(m["grad_norm"]) > 0
    # master moved against the gradient
    assert float(s2["master"]["w"][0]) < 1.0


def test_adamw_grad_clip():
    params = {"w": jnp.zeros((4,), jnp.float32)}
    state = adamw_init(params)
    huge = {"w": jnp.full((4,), 1e6, jnp.float32)}
    cfg = AdamWConfig(lr=1.0, grad_clip=1.0, weight_decay=0.0)
    p2, s2, m = adamw_update(cfg, params, huge, state)
    assert np.isfinite(np.asarray(p2["w"])).all()
    # clipped: first-step Adam update is bounded by lr
    assert np.abs(np.asarray(p2["w"])).max() <= 1.0 + 1e-5


def test_adamw_decreases_quadratic():
    params = {"w": jnp.full((8,), 5.0)}
    state = adamw_init(params)
    cfg = AdamWConfig(lr=0.5, weight_decay=0.0)
    for _ in range(50):
        grads = {"w": 2 * params["w"]}
        params, state, _ = adamw_update(cfg, params, grads, state)
    assert np.abs(np.asarray(params["w"])).max() < 1.0


# -- gradient compression codecs ----------------------------------------------


def test_int8_codec_roundtrip_error():
    key = jax.random.PRNGKey(0)
    g = jax.random.normal(key, (1024,), jnp.float32)
    q, scale = int8_encode(g, jax.random.PRNGKey(1))
    rec = int8_decode(q, scale)
    # quantization error bounded by scale/2 + stochastic noise
    assert float(jnp.max(jnp.abs(rec - g))) <= float(scale) * 1.5
    assert q.dtype == jnp.int8


def test_topk_codec_keeps_largest():
    g = jnp.asarray(np.array([0.1, -5.0, 0.2, 3.0, -0.05], np.float32))
    vals, idx, residual = topk_encode(g, frac=0.4)  # k=2
    rec = topk_decode(vals, idx, g.shape)
    assert float(rec[1]) == -5.0 and float(rec[3]) == 3.0
    assert float(rec[0]) == 0.0
    # error feedback residual holds the rest
    np.testing.assert_allclose(np.asarray(rec + residual), np.asarray(g))


# -- token pipeline ------------------------------------------------------------


def test_token_batches_replayable():
    key = jax.random.PRNGKey(0)
    a = list(zip(range(3), synthetic_token_batches(
        key, batch=2, seq=16, vocab=100)))
    b = list(zip(range(3), synthetic_token_batches(
        key, batch=2, seq=16, vocab=100)))
    for (_, x), (_, y) in zip(a, b):
        assert (np.asarray(x.tokens) == np.asarray(y.tokens)).all()
    # resume mid-stream: start_step=2 reproduces batch 2
    c = next(iter(synthetic_token_batches(key, batch=2, seq=16, vocab=100,
                                          start_step=2)))
    assert (np.asarray(c.tokens) == np.asarray(a[2][1].tokens)).all()


def test_token_batch_is_zipfian():
    tb = synthetic_token_batch(jax.random.PRNGKey(0), batch=8, seq=512,
                               vocab=1000)
    ids = np.asarray(tb.tokens).ravel()
    assert (ids >= 0).all() and (ids < 1000).all()
    # heavy head: token 0 much more frequent than median token
    counts = np.bincount(ids, minlength=1000)
    assert counts[0] > 10 * max(1, int(np.median(counts)))


# -- sharding resolution + pipeline (multi-device subprocesses) ---------------

_SHARDING_SCRIPT = r"""
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import sys; sys.path.insert(0, {src!r})
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.parallel.sharding import DEFAULT_RULES, spec_for_shape
from repro.parallel.resolve import spec_for_decl

mesh = jax.make_mesh((2, 4), ('data', 'model'),
                     (jax.sharding.AxisType.Auto,) * 2)
# divisible: heads 8 on model=4
s = spec_for_shape(DEFAULT_RULES, ('embed', 'heads', 'head_dim'), mesh,
                   (64, 8, 16))
assert s == P(None, 'model'), s
# non-divisible heads 6 -> dropped, fan-in fallback puts model on embed
s = spec_for_decl(DEFAULT_RULES, ('embed', 'heads', 'head_dim'),
                  (64, 6, 16), mesh)
assert s == P('model'), s
# batch over (pod, data): pod absent -> data only
s = spec_for_shape(DEFAULT_RULES, ('batch', 'seq'), mesh, (16, 128))
assert s == P('data'), s
# batch=1: unshardable -> replicated
s = spec_for_shape(DEFAULT_RULES, ('batch', 'seq'), mesh, (1, 128))
assert s == P(), s
print('SHARDING_OK')
"""

_PIPELINE_SCRIPT = r"""
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import sys; sys.path.insert(0, {src!r})
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.parallel.pipeline import pipeline_apply, split_stages

mesh = jax.make_mesh((4,), ('pipe',),
                     (jax.sharding.AxisType.Auto,))
L, D, M, MB = 8, 16, 6, 4
key = jax.random.PRNGKey(0)
ws = jax.random.normal(key, (L, D, D), jnp.float32) * 0.3

def stage_fn(params, x):  # params: (L/4, D, D)
    def body(x, w):
        return jnp.tanh(x @ w), None
    y, _ = jax.lax.scan(body, x, params)
    return y

xs = jax.random.normal(jax.random.fold_in(key, 1), (M, MB, D), jnp.float32)
# sequential reference
ref = xs
for i in range(L):
    ref = jnp.tanh(ref @ ws[i])

staged = split_stages(ws, 4)
staged = jax.device_put(staged, NamedSharding(mesh, P('pipe')))
pipe = jax.jit(pipeline_apply(mesh, stage_fn))
out = pipe(staged, xs)
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5,
                           atol=2e-5)

# gradients flow through the pipeline (backward is pipelined too)
def loss(staged, xs):
    return jnp.sum(pipeline_apply(mesh, stage_fn)(staged, xs) ** 2)

g = jax.jit(jax.grad(loss))(staged, xs)
def ref_loss(ws, xs):
    y = xs
    for i in range(L):
        y = jnp.tanh(y @ ws[i])
    return jnp.sum(y ** 2)
g_ref = jax.grad(ref_loss)(ws, xs)
np.testing.assert_allclose(np.asarray(g).reshape(L, D, D),
                           np.asarray(g_ref), rtol=1e-4, atol=1e-4)
print('PIPELINE_OK')
"""

_COMPRESS_SCRIPT = r"""
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import sys; sys.path.insert(0, {src!r})
import jax, jax.numpy as jnp, numpy as np
from repro.optim.compress import compressed_psum_int8

mesh = jax.make_mesh((8,), ('data',),
                     (jax.sharding.AxisType.Auto,))
grads = {{'w': jnp.linspace(-1, 1, 256, dtype=jnp.float32)}}
out = compressed_psum_int8(mesh, grads, jax.random.PRNGKey(0), ('data',))
# mean over 8 identical replicas == the input, up to int8 quantization
np.testing.assert_allclose(np.asarray(out['w']), np.asarray(grads['w']),
                           atol=2.0 / 127.0)
print('COMPRESS_OK')
"""


def _run_sub(script: str, marker: str):
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    out = subprocess.run(
        [sys.executable, "-c", script.format(src=src)],
        capture_output=True, text=True, timeout=600,
    )
    assert marker in out.stdout, out.stderr[-3000:]


@pytest.mark.slow
def test_sharding_resolution_subprocess():
    _run_sub(_SHARDING_SCRIPT, "SHARDING_OK")


@pytest.mark.slow
def test_pipeline_parallel_subprocess():
    _run_sub(_PIPELINE_SCRIPT, "PIPELINE_OK")


@pytest.mark.slow
def test_compressed_psum_subprocess():
    _run_sub(_COMPRESS_SCRIPT, "COMPRESS_OK")
