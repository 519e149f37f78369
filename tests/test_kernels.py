"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles
(interpret mode executes the kernel body in Python on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd_scan

ATTN_SHAPES = [
    # (B, S, H, hd, block_q, block_k)
    (1, 128, 1, 64, 64, 64),
    (2, 256, 4, 64, 128, 128),
    (1, 256, 2, 128, 64, 128),
    (2, 128, 3, 32, 32, 64),
    (1, 512, 2, 64, 128, 64),
]


@pytest.mark.parametrize("B,S,H,hd,bq,bk", ATTN_SHAPES)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_vs_ref(B, S, H, hd, bq, bk, dtype, causal):
    key = jax.random.PRNGKey(hash((B, S, H, hd)) % 2**31)
    dt = jnp.dtype(dtype)
    q, k, v = (jax.random.normal(kk, (B, S, H, hd), dt)
               for kk in jax.random.split(key, 3))
    out = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk,
                          interpret=True)
    expect = ref.attention_ref(q, k, v, causal=causal)
    tol = 2e-6 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               atol=tol, rtol=tol)


SSD_SHAPES = [
    # (b, s, h, p, n, chunk)
    (1, 64, 2, 8, 16, 16),
    (2, 128, 4, 16, 32, 32),
    (1, 128, 8, 32, 64, 64),
    (2, 96, 2, 16, 16, 32),   # s not multiple of chunk -> clamp path
]


@pytest.mark.parametrize("b,s,h,p,n,chunk", SSD_SHAPES)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_ssd_scan_vs_ref(b, s, h, p, n, chunk, dtype):
    if s % chunk != 0:
        chunk = s // 2 if s % (s // 2) == 0 else s
    key = jax.random.PRNGKey(hash((b, s, h, p, n)) % 2**31)
    ks = jax.random.split(key, 5)
    dt_ = jnp.dtype(dtype)
    x = jax.random.normal(ks[0], (b, s, h, p), dt_)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h))).astype(jnp.float32)
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = jax.random.normal(ks[3], (b, s, n), jnp.float32) * 0.5
    C = jax.random.normal(ks[4], (b, s, n), jnp.float32) * 0.5
    y, st = ssd_scan(x, dt, A, B, C, chunk=chunk, interpret=True)
    y_ref, st_ref = ref.ssd_ref(x, dt, A, B, C)
    tol = 2e-3 if dtype == "float32" else 5e-2
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32),
                               atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(st), np.asarray(st_ref),
                               atol=tol, rtol=tol)


def test_ssd_kernel_matches_production_path():
    """Pallas kernel == models/ssm.ssd_chunked (the pjit production path)."""
    from repro.models.ssm import ssd_chunked
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 5)
    b, s, h, p, n, chunk = 2, 128, 4, 16, 32, 32
    x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = jax.random.normal(ks[3], (b, s, n)) * 0.5
    C = jax.random.normal(ks[4], (b, s, n)) * 0.5
    y1, st1 = ssd_scan(x, dt, A, B, C, chunk=chunk, interpret=True)
    y2, st2 = ssd_chunked(x, dt, A, B, C, chunk)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-4,
                               rtol=2e-4)
    np.testing.assert_allclose(np.asarray(st1), np.asarray(st2), atol=2e-4,
                               rtol=2e-4)


def test_flash_kernel_matches_production_chunked():
    from repro.models.attention import chunked_attention
    key = jax.random.PRNGKey(3)
    B, S, H, hd = 1, 256, 2, 64
    q, k, v = (jax.random.normal(kk, (B, S, H, hd), jnp.float32)
               for kk in jax.random.split(key, 3))
    a = flash_attention(q, k, v, causal=True, interpret=True)
    b_ = chunked_attention(q, k, v, chunk=64, causal=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=2e-5,
                               rtol=2e-5)


def test_ops_dispatch():
    from repro.kernels import ops
    key = jax.random.PRNGKey(1)
    q = k = v = jax.random.normal(key, (1, 64, 2, 32), jnp.float32)
    o_jnp = ops.attention(q, k, v, impl="jnp")
    o_int = ops.attention(q, k, v, impl="interpret", block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(o_jnp), np.asarray(o_int),
                               atol=2e-5, rtol=2e-5)


def test_ops_ssd_dispatch():
    from repro.kernels import ops
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    b, s, h, p, n = 1, 64, 2, 8, 16
    x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = jax.random.normal(ks[3], (b, s, n)) * 0.5
    C = jax.random.normal(ks[4], (b, s, n)) * 0.5
    y_jnp, st_jnp = ops.ssd(x, dt, A, B, C, chunk=32, impl="jnp")
    y_int, st_int = ops.ssd(x, dt, A, B, C, chunk=32, impl="interpret")
    np.testing.assert_allclose(np.asarray(y_jnp), np.asarray(y_int),
                               atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(st_jnp), np.asarray(st_int),
                               atol=2e-4, rtol=2e-4)
