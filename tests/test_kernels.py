"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(7)


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


TOL = {jnp.float32: dict(rtol=2e-4, atol=2e-4), jnp.bfloat16: dict(rtol=6e-2, atol=6e-2)}


@pytest.mark.parametrize("m,k,n", [(64, 64, 64), (200, 300, 150), (128, 512, 256), (33, 65, 17)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gemm(m, k, n, dtype):
    a = _rand(jax.random.fold_in(KEY, m), (m, k), dtype)
    b = _rand(jax.random.fold_in(KEY, n), (k, n), dtype)
    y = ops.gemm(a, b, bm=64, bn=64, bk=128, interpret=True)
    yr = ref.gemm_ref(a, b)
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(yr, np.float32), **TOL[dtype]
    )


@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("r,s", [(1, 1), (3, 3), (5, 5)])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_conv2d(stride, r, s, dtype):
    x = _rand(jax.random.fold_in(KEY, r), (2, 12, 12, 8), dtype)
    w = _rand(jax.random.fold_in(KEY, s), (r, s, 8, 24), dtype)
    y = ops.conv2d_im2col(x, w, stride=stride, bk=16, interpret=True)
    yr = ref.conv2d_ref(x, w, stride=stride)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("h,kvh", [(4, 4), (4, 2), (8, 1)])
@pytest.mark.parametrize("s", [64, 128])
def test_flash_attention(causal, h, kvh, s):
    d = 32
    q = _rand(jax.random.fold_in(KEY, h), (2, h, s, d), jnp.float32)
    k = _rand(jax.random.fold_in(KEY, kvh), (2, kvh, s, d), jnp.float32)
    v = _rand(jax.random.fold_in(KEY, s), (2, kvh, s, d), jnp.float32)
    y = ops.flash_attention(q, k, v, causal=causal, bq=32, bk=32, interpret=True)
    yr = ref.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("chunk", [16, 32])
@pytest.mark.parametrize("h,p,n", [(2, 16, 8), (3, 8, 16)])
def test_ssd_scan(chunk, h, p, n):
    b, l = 2, 128
    ks = jax.random.split(KEY, 5)
    x = _rand(ks[0], (b, l, h, p), jnp.float32)
    dt = jax.nn.softplus(_rand(ks[1], (b, l, h), jnp.float32))
    A = -jnp.exp(_rand(ks[2], (h,), jnp.float32) * 0.5)
    B = _rand(ks[3], (b, l, n), jnp.float32)
    C = _rand(ks[4], (b, l, n), jnp.float32)
    y = ops.ssd_scan(x, dt, A, B, C, chunk=chunk, interpret=True)
    yr = ref.ssd_ref(x, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=2e-3, atol=2e-3)


def test_blockwise_sdpa_matches_full():
    """The jnp blockwise attention (model path) equals exact attention."""
    import dataclasses

    from repro.configs import get_smoke
    from repro.models.blocks import _sdpa

    cfg = dataclasses.replace(get_smoke("granite-3-2b"), attn_q_block=16)
    b, s, h, kvh, d = 2, 64, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(KEY, 3)
    q = _rand(ks[0], (b, s, h, d), jnp.float32)
    k = _rand(ks[1], (b, s, kvh, d), jnp.float32)
    v = _rand(ks[2], (b, s, kvh, d), jnp.float32)
    y = _sdpa(cfg, q, k, v, causal=True)
    yr = ref.attention_ref(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3), causal=True
    ).transpose(0, 2, 1, 3).reshape(b, s, h * d)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=2e-4, atol=2e-4)
