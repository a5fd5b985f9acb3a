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
from repro.kernels.ssd_scan.kernel import plan, ssd_scan
from repro.models.layers import _ssd_chunked_xla


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


def _ssd_scan_inputs(b, l, h, p, n, with_init):
    """Inputs as the mixer makes them: x, B and C in bfloat16, dt float32
    over the published initial range (log-uniform in [0.001, 0.1]), A in
    the published [-16, -1]; an initial state or zeros."""
    ks = jax.random.split(jax.random.PRNGKey(b * l + h + n), 8)
    x = jax.random.normal(ks[0], (b, l, h, p)).astype(jnp.bfloat16)
    dt = jnp.exp(jax.random.uniform(ks[1], (b, l, h), minval=np.log(1e-3),
                                    maxval=np.log(0.1)))
    A = -jax.random.uniform(ks[2], (h,), minval=1.0, maxval=16.0)
    Bm = (jax.random.normal(ks[3], (b, l, 1, n)) * 0.3).astype(jnp.bfloat16)
    Cm = (jax.random.normal(ks[4], (b, l, 1, n)) * 0.3).astype(jnp.bfloat16)
    D = jax.random.normal(ks[5], (h,))
    h0 = (jax.random.normal(ks[6], (b, h, p, n)) * 0.5 if with_init
          else jnp.zeros((b, h, p, n)))
    dy = jax.random.normal(ks[7], (b, l, h, p))
    return (x, dt, A, Bm, Cm, D, h0), dy


def _vjp(fn, args, dy):
    """(y, final state, and the gradients of every input) of ``fn`` under
    the cotangents dy and a constant one on the final state."""
    (y, s), back = jax.vjp(fn, *args)
    return (y, s) + back((dy.astype(y.dtype), jnp.ones_like(s)))


@pytest.mark.parametrize("l,h,p,n,chunk", [
    (32, 4, 8, 16, 8),        # four heads in one lane group
    (48, 2, 16, 128, 16),
    (64, 4, 64, 16, 32),      # two lane groups of two heads of 64
    (64, 2, 128, 128, 32),    # a head per lane group
])
@pytest.mark.parametrize("with_init", [False, True])
def test_ssd_scan_kernel_matches_xla_body(l, h, p, n, chunk, with_init):
    """The fused forward and backward kernels (interpret mode) against
    ``ssd_chunked``'s XLA body on the same inputs: y, the final state and
    the gradients of x, dt, A, B, C, D and the initial state; and y and
    the final state against the sequential recurrence."""
    assert plan(h, p, n, chunk) is not None
    args, dy = _ssd_scan_inputs(2, l, h, p, n, with_init)
    kernel = jax.jit(lambda *a: _vjp(
        lambda *a: ssd_scan(*a, chunk, True), a[:-1], a[-1]))(*args, dy)
    body = jax.jit(lambda *a: _vjp(
        lambda *a: _ssd_chunked_xla(*a, chunk=chunk), a[:-1], a[-1]))(
            *args, dy)
    names = ("y", "state", "dx", "ddt", "dA", "dB", "dC", "dD", "dh0")
    for name, got, want in zip(names, kernel, body):
        assert got.dtype == want.dtype, name
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        # bf16 outputs (y, dx, dB, dC) differ by a rounding at most
        tol = 1e-2 if name in ("y", "dx", "dB", "dC") else 1e-4
        scale = max(1.0, float(np.abs(want).max()))
        np.testing.assert_allclose(got, want, atol=tol * scale, rtol=tol,
                                   err_msg=name)
    x, dt, A, Bm, Cm, D, h0 = args
    yr, sr = ssd_reference(x, dt, A, Bm, Cm, D, init_state=h0)
    np.testing.assert_allclose(np.asarray(kernel[0], np.float32),
                               np.asarray(yr, np.float32), atol=3e-2)
    np.testing.assert_allclose(np.asarray(kernel[1]), np.asarray(sr),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("h,p,n,chunk,ok", [
    (48, 64, 128, 128, True), (256, 64, 16, 128, True),
    (256, 64, 128, 128, False), (3, 64, 128, 128, False),
    (4, 48, 128, 128, False), (4, 64, 128, 12, False),
    (4, 256, 16, 8, True)])
def test_ssd_scan_plan_blocks_whole_lane_groups(h, p, n, chunk, ok):
    """A grid step holds whole lane groups (128 lanes, or a head of a
    multiple of 128), divides the heads and fits the scoped VMEM, with
    as many heads as fit; else no plan (256 heads of 64 with a state of
    128: the resident states alone pass the limit)."""
    from repro.kernels.ssd_scan.kernel import vmem_bytes
    from repro.kernels.tuning import VMEM_LIMIT_BYTES
    got = plan(h, p, n, chunk)
    assert (got is not None) == ok
    if got:
        assert h % got.hb == 0 and got.gw % p == 0
        assert got.hb * p % got.gw == 0 and got.hb * p % 128 == 0
        assert vmem_bytes(chunk, got.hb, h, p, n) <= VMEM_LIMIT_BYTES
        bigger = [hb for hb in range(got.hb + 1, h + 1) if h % hb == 0
                  and hb * p % 128 == 0]
        assert all(vmem_bytes(chunk, hb, h, p, n) > VMEM_LIMIT_BYTES
                   for hb in bigger)


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
