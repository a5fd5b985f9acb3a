"""HLO analyzer: flops/collectives/trip counts on known programs."""
import jax
import jax.numpy as jnp

from repro.roofline.analysis import model_flops, roofline_terms
from repro.roofline.hlo import analyze_hlo, cpu_widening_artifact_bytes


def test_scan_flops_exact():
    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), ()
        c, _ = jax.lax.scan(body, x, None, length=12)
        return c
    compiled = jax.jit(f).lower(
        jax.ShapeDtypeStruct((128, 256), jnp.float32),
        jax.ShapeDtypeStruct((256, 256), jnp.float32)).compile()
    st = analyze_hlo(compiled.as_text())
    assert st.flops == 12 * 2 * 128 * 256 * 256


def test_nested_scan_flops_exact():
    def f(x, w):
        def outer(c, _):
            def inner(c2, _):
                return jnp.tanh(c2 @ w), ()
            c2, _ = jax.lax.scan(inner, c, None, length=5)
            return c2, ()
        c, _ = jax.lax.scan(outer, x, None, length=3)
        return c
    compiled = jax.jit(f).lower(
        jax.ShapeDtypeStruct((64, 128), jnp.float32),
        jax.ShapeDtypeStruct((128, 128), jnp.float32)).compile()
    st = analyze_hlo(compiled.as_text())
    assert st.flops == 15 * 2 * 64 * 128 * 128


def test_dominant_term_selection():
    # per-device terms: peak 197e12 F/s, 819e9 B/s HBM, 50e9 B/s link
    t = roofline_terms("a", "s", "m", 256, flops=1e13, bytes_accessed=1e9,
                       coll_bytes=1e8, mflops=5e14)
    assert t.dominant == "compute"
    t2 = roofline_terms("a", "s", "m", 256, flops=1e10,
                        bytes_accessed=1e13, coll_bytes=1e9, mflops=1e12)
    assert t2.dominant == "memory"


def test_model_flops_moe_uses_active():
    from repro.models import get_config
    dense = get_config("llama3.2-1b")
    moe = get_config("deepseek-moe-16b")
    assert model_flops(moe, 1000) < 6 * moe.num_params() * 1000
    assert model_flops(dense, 1000) == 6 * dense.num_params() * 1000


def test_cpu_widening_artifact_detection():
    text = """
ENTRY %main (p: f32[4]) -> f32[4] {
  %w = (s32[], bf16[8,64], f32[8,64], f32[4]) while(%t), condition=%c, body=%b
}
"""
    assert cpu_widening_artifact_bytes(text) == 8 * 64 * 4


def test_device_peaks_are_looked_up_by_device_kind():
    """A measured time is divided only by the peaks of the device it ran
    on: the CPU has none, and an unknown chip is an error, not a v5e."""
    from types import SimpleNamespace

    import pytest

    from repro.core.sysinfo import DEVICE_PEAKS, device_peaks
    v5e = device_peaks(SimpleNamespace(platform="tpu",
                                       device_kind="TPU v5 lite"))
    assert v5e["peak_bf16_flops"] == 197e12
    assert v5e["hbm_bandwidth"] == 819e9
    assert device_peaks(jax.devices()[0]) is None         # the CPU
    with pytest.raises(ValueError, match="TPU v9"):
        device_peaks(SimpleNamespace(platform="tpu", device_kind="TPU v9"))
    assert set(DEVICE_PEAKS) == {"TPU v5 lite"}
