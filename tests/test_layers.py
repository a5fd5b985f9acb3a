"""Layer-level correctness: flash-vs-naive, SSD, MoE, conv, loss."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.models import layers as L


@pytest.mark.parametrize("S,H,K,D,cq,ck", [
    (64, 4, 4, 16, 16, 16),
    (128, 4, 2, 32, 32, 64),
    (96, 6, 2, 16, 32, 32),
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_naive(S, H, K, D, cq, ck, causal):
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (2, S, H, D))
    k = jax.random.normal(jax.random.PRNGKey(1), (2, S, K, D))
    v = jax.random.normal(jax.random.PRNGKey(2), (2, S, K, D))
    ref = L.naive_attention(q, k, v, causal=causal)
    out = L.flash_attention_xla(q, k, v, causal=causal, chunk_q=cq,
                                chunk_k=ck)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_grads_match_naive():
    q = jax.random.normal(jax.random.PRNGKey(0), (2, 64, 4, 16))
    k = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 2, 16))
    v = jax.random.normal(jax.random.PRNGKey(2), (2, 64, 2, 16))

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    gf = jax.grad(loss(lambda q, k, v: L.flash_attention_xla(
        q, k, v, chunk_q=16, chunk_k=16)), argnums=(0, 1, 2))(q, k, v)
    gn = jax.grad(loss(lambda q, k, v: L.naive_attention(q, k, v)),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gn):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def _decode_attention_repeated(q, k_cache, v_cache, cache_len):
    """Decode attention over the caches repeated to H heads: the form
    ``decode_attention`` had before it grouped the query heads."""
    B, _, H, D = q.shape
    k_cache, v_cache = jax.lax.optimization_barrier((k_cache, v_cache))
    kr, vr = L.repeat_kv(k_cache, H), L.repeat_kv(v_cache, H)
    qs = q.astype(kr.dtype) * jnp.asarray(1.0 / np.sqrt(D), kr.dtype)
    s = jnp.einsum("bqhd,bshd->bhqs", qs, kr,
                   preferred_element_type=jnp.float32)
    mask = jnp.arange(kr.shape[1]) < cache_len[:, None, None, None]
    s = jnp.where(mask, s, -jnp.inf)
    m = jnp.max(s, axis=-1)
    m_safe = jnp.where(jnp.isneginf(m), 0.0, m)
    p = jnp.exp(s - m_safe[..., None]).astype(vr.dtype)
    p = jnp.where(jnp.isneginf(s), 0.0, p)
    l = jnp.sum(p.astype(jnp.float32), axis=-1)
    pv = jnp.einsum("bhqs,bshd->bhqd", p, vr,
                    preferred_element_type=jnp.float32)
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o = jnp.transpose(pv / l_safe[..., None], (0, 2, 1, 3))
    return o.astype(q.dtype)


@pytest.mark.parametrize("H,K", [(16, 8), (8, 1), (4, 4)])
def test_decode_attention_grouped_equals_repeated(H, K):
    """Reading each kv head's bf16 cache once for its H // K query heads
    gives the repeated form's output bit for bit (ragged prefixes, the
    shortest and the full one among them)."""
    S, D = 64, 32
    ks = jax.random.split(jax.random.PRNGKey(H * 10 + K), 3)
    q = jax.random.normal(ks[0], (4, 1, H, D), jnp.bfloat16)
    k_cache = jax.random.normal(ks[1], (4, S, K, D), jnp.bfloat16)
    v_cache = jax.random.normal(ks[2], (4, S, K, D), jnp.bfloat16)
    cache_len = jnp.asarray([1, 17, S - 1, S], jnp.int32)
    out = jax.jit(L.decode_attention)(q, k_cache, v_cache, cache_len)
    ref = jax.jit(_decode_attention_repeated)(q, k_cache, v_cache, cache_len)
    assert out.dtype == ref.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(ref, np.float32))


@given(st.integers(1, 4), st.integers(8, 48), st.integers(1, 3))
@settings(max_examples=8, deadline=None)
def test_ssd_chunked_equals_reference(b, l, h):
    """SSD duality: chunked == sequential recurrence (property)."""
    p, n = 8, 8
    key = jax.random.PRNGKey(l * 7 + b)
    x = jax.random.normal(key, (b, l, h, p)) * 0.4
    dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(1),
                                           (b, l, h)))
    A = -jnp.exp(jax.random.normal(jax.random.PRNGKey(2), (h,)) * 0.3)
    Bm = jax.random.normal(jax.random.PRNGKey(3), (b, l, 1, n)) * 0.3
    Cm = jax.random.normal(jax.random.PRNGKey(4), (b, l, 1, n)) * 0.3
    D = jnp.ones((h,))
    y1, s1 = L.ssd_reference(x, dt, A, Bm, Cm, D)
    y2, s2 = L.ssd_chunked(x, dt, A, Bm, Cm, D, chunk=16)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=3e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=3e-5)


def _ssd_inputs(chunk, dt_kind, b=1, h=4, p=8, n=16):
    """Two chunks of inputs with A in [-16, -1] (the published A_init_range)
    and dt either over the published initial range, log-uniform in
    [0.001, 0.1], or softplus(0) (a dt_bias of zero)."""
    l = 2 * chunk
    ks = jax.random.split(jax.random.PRNGKey(chunk), 5)
    x = jax.random.normal(ks[0], (b, l, h, p))
    A = -jax.random.uniform(ks[1], (h,), minval=1.0, maxval=16.0)
    if dt_kind == "softplus0":
        dt = jnp.full((b, l, h), jax.nn.softplus(0.0))
    else:
        dt = jnp.exp(jax.random.uniform(ks[2], (b, l, h),
                                        minval=np.log(1e-3),
                                        maxval=np.log(0.1)))
    Bm = jax.random.normal(ks[3], (b, l, 1, n)) * 0.3
    Cm = jax.random.normal(ks[4], (b, l, 1, n)) * 0.3
    return x, dt, A, Bm, Cm, jnp.ones((h,))


@pytest.mark.parametrize("chunk", [128, 256])
@pytest.mark.parametrize("dt_kind", ["published", "softplus0"])
def test_ssd_chunked_gradients_are_finite(chunk, dt_kind):
    """Above the diagonal the intra-chunk segment sums are large and
    positive; masked after exp they overflowed to inf and the backward
    pass read inf * 0 = NaN."""
    x, dt, A, Bm, Cm, D = _ssd_inputs(chunk, dt_kind)

    def f(x, dt, A, Bm, Cm):
        y, s = L.ssd_chunked(x, dt, A, Bm, Cm, D, chunk=chunk)
        return jnp.sum(y ** 2) + jnp.sum(s ** 2)

    grads = jax.jit(jax.grad(f, argnums=(0, 1, 2, 3, 4)))(x, dt, A, Bm, Cm)
    for g in grads:
        assert np.all(np.isfinite(np.asarray(g)))


@pytest.mark.parametrize("chunk", [128, 256])
def test_ssd_chunked_forward_matches_recurrence_at_published_dt(chunk):
    x, dt, A, Bm, Cm, D = _ssd_inputs(chunk, "published")
    y1, s1 = L.ssd_reference(x, dt, A, Bm, Cm, D)
    y2, s2 = L.ssd_chunked(x, dt, A, Bm, Cm, D, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y1), atol=1e-6,
                               rtol=0)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s1), atol=1e-6,
                               rtol=0)


@pytest.fixture
def ssd_kernel_path(monkeypatch):
    """``ssd_chunked`` as lowered for a TPU, its kernels in interpret mode:
    the platform choice takes the ``tpu`` branch, whose kernel runs
    interpreted on the CPU."""
    from repro.kernels.ssd_scan import kernel

    scan = kernel.ssd_scan
    monkeypatch.setattr(kernel, "ssd_scan",
                        lambda *a, chunk: scan(*a, chunk, True))
    monkeypatch.setattr(L.lax, "platform_dependent",
                        lambda *a, default, tpu: tpu(*a))


@pytest.mark.parametrize("chunk,n,cut", [(8, 16, 3), (16, 128, 5),
                                         (32, 16, 0), (32, 128, 11)])
@pytest.mark.parametrize("with_init", [False, True])
def test_ssd_chunked_kernel_path_matches_xla_body(ssd_kernel_path, chunk, n,
                                                  cut, with_init):
    """The kernel path of ``ssd_chunked`` (dt as published; ``cut``
    positions short of two chunks, so that the padding runs) against its
    XLA body and the recurrence: y, the final state, and the gradients of
    x, dt, A, B, C, D and ``init_state``."""
    x, dt, A, Bm, Cm, D = _ssd_inputs(chunk, "published", b=2, n=n)
    l = x.shape[1] - cut
    x, dt, Bm, Cm = x[:, :l], dt[:, :l], Bm[:, :l], Cm[:, :l]
    h0 = (jax.random.normal(jax.random.PRNGKey(9), x.shape[:1] + x.shape[2:]
                            + (n,)) * 0.5 if with_init else None)
    dy = jax.random.normal(jax.random.PRNGKey(8), x.shape)

    def run(x, dt, A, Bm, Cm, D, h0):
        (y, s), back = jax.vjp(
            lambda *a: L.ssd_chunked(*a[:6], chunk=chunk, init_state=a[6]),
            x, dt, A, Bm, Cm, D, h0)
        return (y, s) + back((dy, jnp.ones_like(s)))

    args = (x, dt, A, Bm, Cm, D, h0)
    assert "ssd_bwd" in str(jax.make_jaxpr(run)(*args))
    kernel = jax.jit(run)(*args)
    with pytest.MonkeyPatch.context() as undo:
        undo.setattr(L, "_on_one_device", lambda: False)
        body = jax.jit(run)(*args)
    names = ("y", "state", "dx", "ddt", "dA", "dB", "dC", "dD", "dh0")
    for name, got, want in zip(names, kernel, body):
        if want is None:
            continue
        scale = max(1.0, float(np.max(np.abs(np.asarray(want)))))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5 * scale, rtol=1e-5,
                                   err_msg=name)
    yr, sr = L.ssd_reference(x, dt, A, Bm, Cm, D, init_state=h0)
    np.testing.assert_allclose(np.asarray(kernel[0]), np.asarray(yr),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(kernel[1]), np.asarray(sr),
                               atol=1e-5)


def test_ssd_chunked_runs_the_xla_body_off_the_tpu():
    """Lowered for the CPU, ``ssd_chunked`` holds no Pallas kernel, even
    where the kernel's conditions hold."""
    x, dt, A, Bm, Cm, D = _ssd_inputs(16, "published")
    text = jax.jit(lambda *a: L.ssd_chunked(*a, chunk=16)).lower(
        x, dt, A, Bm, Cm, D).as_text()
    assert "ssd_fwd" not in text and "tpu_custom_call" not in text


def test_mamba2_init_follows_the_published_module():
    """A = exp(A_log) uniform in [1, 16]; softplus(dt_bias) log-uniform in
    [0.001, 0.1]; D one; a bias on the conv over x, B and C within
    ±1/sqrt(d_conv)."""
    from repro.models import get_config
    cfg = get_config("mamba2-780m")
    p = L.init_mamba2(jax.random.PRNGKey(3), cfg)
    H = cfg.ssm_heads
    A = np.exp(np.asarray(p["A_log"]))
    dt = np.asarray(jax.nn.softplus(p["dt_bias"]))
    assert A.shape == dt.shape == p["D"].shape == (H,)
    assert A.min() >= 1.0 and A.max() <= 16.0 and A.std() > 2.0
    assert dt.min() >= 1e-3 * (1 - 1e-5) and dt.max() <= 0.1 * (1 + 1e-5)
    log_dt = np.log(dt)
    assert log_dt.max() - log_dt.min() > 0.6 * np.log(100.0)
    np.testing.assert_array_equal(np.asarray(p["D"]), np.ones(H))
    bound = 1 / np.sqrt(cfg.ssm_conv)
    for name, width in (("conv_x_bias", cfg.ssm_d_inner),
                        ("conv_B_bias", cfg.ssm_state),
                        ("conv_C_bias", cfg.ssm_state)):
        b = np.asarray(p[name])
        assert b.shape == (width,)
        assert np.abs(b).max() <= bound and np.abs(b).max() > 0.4 * bound


def test_moe_scatter_equals_einsum():
    p = L.init_moe(jax.random.PRNGKey(0), 32, 8, 64, 1)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))
    ys, auxs = L.moe_scatter(p, x, top_k=2, capacity_factor=8.0, n_shared=1)
    ye, auxe = L.moe_einsum(p, x, top_k=2, capacity_factor=8.0, n_shared=1)
    np.testing.assert_allclose(np.asarray(ys), np.asarray(ye), atol=1e-5)
    assert abs(float(auxs - auxe)) < 1e-6


def test_moe_capacity_drops_tokens():
    """With tiny capacity, outputs differ from infinite capacity (drops)."""
    p = L.init_moe(jax.random.PRNGKey(0), 16, 4, 32, 0)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 64, 16))
    y_small, _ = L.moe_scatter(p, x, top_k=2, capacity_factor=0.25)
    y_big, _ = L.moe_scatter(p, x, top_k=2, capacity_factor=8.0)
    assert np.abs(np.asarray(y_small - y_big)).max() > 1e-4


@given(st.integers(1, 512), st.integers(1, 64), st.integers(1, 8),
       st.floats(0.5, 4.0))
@settings(max_examples=40, deadline=None)
def test_moe_capacity_invariants(T, E, k, cf):
    C = L.moe_capacity(T, E, k, cf)
    assert C >= 8 and C % 8 == 0
    assert C >= min(8, int(np.ceil(T * k / E * cf)))


def test_causal_conv_matches_decode_path():
    """Streaming conv (decode) == full conv applied position-wise, bias
    included."""
    k, C = 4, 6
    w = jax.random.normal(jax.random.PRNGKey(0), (k, C)) * 0.3
    bias = jax.random.uniform(jax.random.PRNGKey(2), (C,), minval=-0.5,
                              maxval=0.5)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 10, C))
    full = L.causal_conv1d(w, bias, x)
    tail = jnp.zeros((2, k - 1, C))
    outs = []
    for t in range(10):
        out, tail = L._conv_decode(w, bias, tail, x[:, t:t + 1])
        outs.append(out)
    stream = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(full), np.asarray(stream),
                               atol=1e-5)
    no_bias = L.causal_conv1d(w, jnp.zeros((C,)), x)
    assert np.abs(np.asarray(full - no_bias)).max() > 1e-2


def test_chunked_loss_matches_unchunked():
    table = jax.random.normal(jax.random.PRNGKey(0), (64, 16)) * 0.1
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 16))
    labels = jax.random.randint(jax.random.PRNGKey(2), (2, 32), 0, 64)
    full = L.chunked_loss(table, x, labels, 0, jnp.float32)
    chunked = L.chunked_loss(table, x, labels, 8, jnp.float32)
    np.testing.assert_allclose(float(full), float(chunked), rtol=1e-6)


def test_rope_relative_property():
    """RoPE: scores depend only on relative positions."""
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 4, 1, 32))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 4, 1, 32))
    def scores(offset):
        pos = jnp.arange(4)[None] + offset
        qr = L.apply_rope(q, pos, 1e4)
        kr = L.apply_rope(k, pos, 1e4)
        return jnp.einsum("bqhd,bshd->bqs", qr, kr)
    np.testing.assert_allclose(np.asarray(scores(0)),
                               np.asarray(scores(100)), atol=1e-3)


def test_pick_chunk_divides():
    for S in (1500, 4096, 51865, 7):
        c = L.pick_chunk(S, 512)
        assert S % c == 0 and c <= 512
