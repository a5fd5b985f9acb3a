"""Per-kernel allclose vs the pure-jnp oracle (interpret=True on CPU).

Sweeps shapes and dtypes per the deliverable; the BlockSpec tilings are
also structurally asserted (MXU/VMEM alignment).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention, flash_attention_ref
from repro.kernels.histogram import histogram, histogram_ref
from repro.kernels.matmul import matmul, matmul_ref
from repro.kernels.rmsnorm import rmsnorm, rmsnorm_ref
from repro.kernels.ssd_scan import ssd, ssd_reference


@pytest.mark.parametrize("m,k,n,bm,bk,bn", [
    (128, 64, 96, 64, 32, 32),
    (256, 256, 256, 128, 128, 128),
    (64, 128, 64, 64, 64, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_kernel(m, k, n, bm, bk, bn, dtype):
    x = (jax.random.normal(jax.random.PRNGKey(0), (m, k)) * 0.5).astype(dtype)
    y = (jax.random.normal(jax.random.PRNGKey(1), (k, n)) * 0.5).astype(dtype)
    out = matmul(x, y, bm=bm, bk=bk, bn=bn)
    ref = matmul_ref(x, y)
    tol = 1e-4 if dtype == jnp.float32 else 2e-1
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol,
                               rtol=tol)


@pytest.mark.parametrize("S,H,K,D,bq,bk", [
    (128, 4, 2, 32, 32, 32),
    (64, 2, 2, 64, 64, 64),
    (256, 4, 1, 16, 64, 128),
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_kernel(S, H, K, D, bq, bk, causal, dtype):
    q = (jax.random.normal(jax.random.PRNGKey(0), (2, S, H, D))).astype(dtype)
    k = (jax.random.normal(jax.random.PRNGKey(1), (2, S, K, D))).astype(dtype)
    v = (jax.random.normal(jax.random.PRNGKey(2), (2, S, K, D))).astype(dtype)
    out = flash_attention(q, k, v, causal=causal, bq=bq, bk=bk)
    ref = flash_attention_ref(q, k, v, causal=causal)
    tol = 2e-5 if dtype == jnp.float32 else 4e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)


@pytest.mark.parametrize("rows,d,br", [(64, 128, 16), (256, 512, 64),
                                       (32, 1024, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_kernel(rows, d, br, dtype):
    x = (jax.random.normal(jax.random.PRNGKey(0), (rows, d))).astype(dtype)
    s = jax.random.normal(jax.random.PRNGKey(1), (d,)) + 1.0
    out = rmsnorm(x, s, br=br)
    ref = rmsnorm_ref(x, s)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)


@pytest.mark.parametrize("n,bins,chunk", [(4096, 64, 512), (8192, 256, 1024),
                                          (1024, 16, 256)])
def test_histogram_kernel(n, bins, chunk):
    x = jax.random.randint(jax.random.PRNGKey(0), (n,), 0, bins)
    out = histogram(x, bins, chunk=chunk)
    ref = histogram_ref(x, bins)
    assert int(out.sum()) == n
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("l,h,chunk", [(32, 2, 8), (64, 3, 16), (128, 1, 32)])
def test_ssd_kernel(l, h, chunk):
    b, p, n = 2, 8, 16
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (b, l, h, p)) * 0.4
    dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(1), (b, l, h)))
    A = -jnp.exp(jax.random.normal(jax.random.PRNGKey(2), (h,)) * 0.3)
    Bm = jax.random.normal(jax.random.PRNGKey(3), (b, l, 1, n)) * 0.3
    Cm = jax.random.normal(jax.random.PRNGKey(4), (b, l, 1, n)) * 0.3
    D = jnp.ones((h,))
    y, s = ssd(x, dt, A, Bm, Cm, D, chunk=chunk)
    yr, sr = ssd_reference(x, dt, A, Bm, Cm, D)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=3e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), atol=3e-5)


def test_tilings_are_tpu_aligned():
    """Structural check: default blocks are MXU-aligned multiples of 128
    and fit comfortably in the scoped VMEM the kernels compile under."""
    from repro.kernels.tuning import VMEM_LIMIT_BYTES
    vmem = VMEM_LIMIT_BYTES
    bm = bn = bk = 512
    assert bm % 128 == 0 and bn % 128 == 0 and bk % 128 == 0
    working = (bm * bk + bk * bn) * 2 + bm * bn * 4
    assert working < vmem / 8
    bq = bk_ = 512
    D = 128
    fa = (2 * bq * D + 2 * bk_ * D) * 2 + bq * D * 4 + bq * bk_ * 4
    assert fa < vmem / 8
