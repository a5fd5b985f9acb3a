"""The Pallas kernels compile for a TPU v5e at main-path widths.

Each test compiles one kernel, at the blocks its public wrapper resolves
(tuned artifact, shape clamp, VMEM validation), for one chip of a
described ``v5e:2x2`` topology: the TPU compiler is installed even where
no chip is attached.  A kernel that only interpret mode accepts (a block
not aligned to the TPU tiling, more scoped VMEM than the limit) fails
here, with no chip time spent.  Nothing runs; the compiled program must
contain the Mosaic kernel (``tpu_custom_call``).  The serve engine's
decode step is compiled the same way, to read what XLA makes of its
attention over the KV cache.
"""
import functools
import math
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import ops as flash_ops
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.histogram.kernel import histogram_pallas
from repro.kernels.matmul import ops as matmul_ops
from repro.kernels.matmul.kernel import matmul_pallas
from repro.kernels.rmsnorm import ops as rmsnorm_ops
from repro.kernels.rmsnorm.kernel import rmsnorm_pallas
from repro.kernels.ssd_scan import ops as ssd_ops
from repro.kernels.ssd_scan.kernel import ssd_forward, ssd_scan
from repro.models import build, get_config, layers, transformer


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    # the TPU library logs under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "can't here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile written to the persistent cache cannot be read back
    # without a chip; keep these compiles out of it
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def test_matmul_compiles(one_chip):
    x = jax.ShapeDtypeStruct((4096, 4096), jnp.bfloat16, sharding=one_chip)
    eff = matmul_ops.blocks(x, x)
    text = _compile_text(functools.partial(matmul_pallas, **eff), x, x)
    assert "tpu_custom_call" in text


def test_flash_attention_compiles(one_chip):
    q = jax.ShapeDtypeStruct((1, 2048, 16, 128), jnp.bfloat16,
                             sharding=one_chip)
    eff = flash_ops.blocks(q, q)
    text = _compile_text(functools.partial(flash_attention_pallas, **eff),
                         q, q, q)
    assert "tpu_custom_call" in text


def test_rmsnorm_compiles(one_chip):
    x = jax.ShapeDtypeStruct((8192, 2048), jnp.bfloat16, sharding=one_chip)
    s = jax.ShapeDtypeStruct((2048,), jnp.float32, sharding=one_chip)
    eff = rmsnorm_ops.blocks(x, s)
    text = _compile_text(functools.partial(rmsnorm_pallas, **eff), x, s)
    assert "tpu_custom_call" in text


def test_rmsnorm_block_over_the_vmem_limit_fails_validation(one_chip):
    """br=2048 at d=2048 bf16 needs more scoped VMEM than the kernels are
    compiled under: the chip's compiler refuses it, and the wrapper
    refuses it first, with the readable message."""
    x = jax.ShapeDtypeStruct((8192, 2048), jnp.bfloat16, sharding=one_chip)
    s = jax.ShapeDtypeStruct((2048,), jnp.float32, sharding=one_chip)
    with pytest.raises(ValueError, match="scoped-VMEM limit"):
        rmsnorm_ops.blocks(x, s, br=2048)
    with pytest.raises(jax.errors.JaxRuntimeError, match="vmem"):
        _compile_text(functools.partial(rmsnorm_pallas, br=2048), x, s)


def _ssd_args(sds, b, l, h, p, n, dtype):
    """x, dt, A, B, C, D and an initial state as shapes."""
    return (sds((b, l, h, p), dtype), sds((b, l, h), jnp.float32),
            sds((h,), jnp.float32), sds((b, l, 1, n), dtype),
            sds((b, l, 1, n), dtype), sds((h,), jnp.float32),
            sds((b, h, p, n), jnp.float32))


def test_ssd_scan_compiles_at_mamba2_780m_widths(one_chip):
    # mamba2-780m: 48 heads of 64, state 128, one 512-token sequence; the
    # forward kernel with its residual and the backward kernel
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    args = _ssd_args(sds, 1, 512, 48, 64, 128, jnp.bfloat16)
    chunk = ssd_ops.blocks(args[0], args[3])["chunk"]
    text = _compile_text(functools.partial(ssd_forward, chunk=chunk,
                                           save=True), *args)
    assert "tpu_custom_call" in text

    def loss(*a):
        y, s = ssd_scan(*a, chunk)
        return jnp.sum(y.astype(jnp.float32)) + jnp.sum(s)
    text = _compile_text(jax.grad(loss, argnums=tuple(range(7))), *args)
    assert text.count('custom_call_target="tpu_custom_call"') == 2


def _bench_scope_of():
    """``bench/ssm_scopes.scope_of``: the map the benchmark's ``ssd`` and
    ``mixer`` metrics read a compiled step through."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from bench.ssm_scopes import scope_of
    return scope_of


def test_mamba2_block_gradient_runs_the_ssd_kernels(one_chip):
    """jax.grad through ``mamba2_block`` under ``jax.checkpoint`` at
    mamba2-780m's widths and the train cell's 2 x 8,192 positions, lowered
    for one v5e: the SSD scan's forward and backward Mosaic kernels are
    both in the program, each under the ``ssd`` scope that
    ``ssd_ms.train8k`` reads (a backward without it would leave the
    scan's time out of the metric), and the scan's compiled temporaries
    are below those of the XLA body at the same shapes."""
    scope_of = _bench_scope_of()
    cfg = get_config("mamba2-780m")
    b, l = 2, 8192

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    params = jax.tree.map(lambda a: sds(a.shape, a.dtype), jax.eval_shape(
        lambda: layers.init_mamba2(jax.random.PRNGKey(0), cfg)))
    block = jax.checkpoint(lambda p, x: layers.mamba2_block(p, x, cfg))
    text = _compile_text(jax.grad(
        lambda p, x: jnp.sum(block(p, x).astype(jnp.float32)),
        argnums=(0, 1)), params, sds((b, l, cfg.d_model), jnp.bfloat16))
    kernels = {}
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            name = re.match(r"\s*(?:ROOT\s+)?%([\w-]+)", line).group(1)
            kernels[name] = scope_of(re.search(r'op_name="([^"]*)"',
                                               line).group(1))
    assert sorted(kernels) == ["ssd_bwd", "ssd_fwd"], kernels
    assert set(kernels.values()) == {"ssd"}, kernels

    args = _ssd_args(sds, b, l, cfg.ssm_heads, cfg.ssm_head_dim,
                     cfg.ssm_state, jnp.bfloat16)

    def temp_bytes(scan):
        def loss(*a):
            y, _ = jax.checkpoint(scan)(*a)
            return jnp.sum(y.astype(jnp.float32))
        return jax.jit(jax.grad(loss, argnums=tuple(range(7)))).lower(
            *args).compile().memory_analysis().temp_size_in_bytes
    chunk = cfg.ssm_chunk
    kernel = temp_bytes(lambda *a: layers.ssd_chunked(
        *a[:6], chunk=chunk, init_state=a[6]))
    xla = temp_bytes(functools.partial(layers._ssd_chunked_xla, chunk=chunk))
    assert kernel < xla, (kernel, xla)


def test_histogram_compiles(one_chip):
    x = jax.ShapeDtypeStruct((1 << 20,), jnp.int32, sharding=one_chip)
    text = _compile_text(functools.partial(histogram_pallas, nbins=256), x)
    assert "tpu_custom_call" in text


def _flash_gqa(f32):
    q, kv = f32((2, 128, 2, 32)), f32((2, 128, 1, 32))
    return (functools.partial(flash_attention_pallas, causal=True,
                              **flash_ops.blocks(q, kv)), q, kv, kv)


def _ssd_small(f32):
    x, B = f32((2, 512, 4, 64)), f32((2, 512, 1, 64))
    return (functools.partial(ssd_forward, **ssd_ops.blocks(x, B)),
            x, f32((2, 512, 4)), f32((4,)), B, B, f32((4,)),
            f32((2, 4, 64, 64)))


# The shapes the builtin scopes run the kernels at (`repro run` on a
# chip), with the blocks the wrappers resolve for them.
RUN_STAGE_CASES = {
    "mxu/matmul": lambda f32: (
        functools.partial(matmul_pallas, **matmul_ops.blocks(
            f32((256, 256)), f32((256, 256)))),
        f32((256, 256)), f32((256, 256))),
    "linalg/matmul_rect": lambda f32: (
        functools.partial(matmul_pallas, **matmul_ops.blocks(
            f32((512, 256)), f32((256, 256)))),
        f32((512, 256)), f32((256, 256))),
    "nn/rmsnorm": lambda f32: (
        functools.partial(rmsnorm_pallas, **rmsnorm_ops.blocks(
            f32((1024, 1024)), f32((1024,)))),
        f32((1024, 1024)), f32((1024,))),
    "nn/flash_attention_pallas": _flash_gqa,
    "nn/ssd_scan_pallas": _ssd_small,
}


@pytest.mark.parametrize("family", sorted(RUN_STAGE_CASES))
def test_run_stage_kernel_shapes_compile(one_chip, family):
    def f32(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    fn, *args = RUN_STAGE_CASES[family](f32)
    assert "tpu_custom_call" in _compile_text(fn, *args)


_HLO_ARRAY = re.compile(r"^\s*(?:ROOT\s+)?%\S+ = (\w+)\[([\d,]*)\]\S* ([a-z][\w-]*)\(")


_SLOTS, _MAX_LEN = 16, 2048


def _served_decode_args(one_chip):
    """internlm2-1.8b at its served widths, 2 layers: (cfg, (params,
    tokens, cache)) as shapes on one v5e chip, the cache as the serve
    engine holds it (bf16, a position per slot)."""
    cfg = get_config("internlm2-1.8b").override(num_layers=2)
    api = build(cfg)

    def shaped(a, dtype=None):
        return jax.ShapeDtypeStruct(a.shape, dtype or a.dtype,
                                    sharding=one_chip)

    params = jax.tree.map(lambda a: shaped(a, jnp.bfloat16),
                          jax.eval_shape(api.init, jax.random.PRNGKey(0)))
    cache = jax.tree.map(shaped, jax.eval_shape(
        lambda: api.init_cache(_SLOTS, _MAX_LEN, jnp.bfloat16)))
    cache["pos"] = jax.ShapeDtypeStruct((_SLOTS,), jnp.int32,
                                        sharding=one_chip)
    tokens = jax.ShapeDtypeStruct((_SLOTS, 1), jnp.int32, sharding=one_chip)
    return cfg, (params, tokens, cache)


def test_decode_step_reads_the_kv_cache_as_stored(one_chip):
    """internlm2-1.8b's ragged decode step at its served widths (16 query
    heads over 8 kv heads, 16 slots x 2,048 positions): no cache-sized
    float32 array (a widened cache) and no cache-sized broadcast (a cache
    repeated to the query heads) in the compiled program, whose attention
    runs under the ``gqa_grouped`` scope.  A bf16 copy of a layer's cache
    is allowed: XLA transposes it to [B,K,S,D] for the dot batched over
    (batch, kv head)."""
    cfg, args = _served_decode_args(one_chip)
    text = _compile_text(
        lambda p, t, c: transformer.decode_step(cfg, p, t, c), *args)
    layer_cache = _SLOTS * _MAX_LEN * cfg.num_kv_heads * cfg.hd
    offending = []
    for line in text.splitlines():
        m = _HLO_ARRAY.match(line)
        if not m:
            continue
        dtype, dims, op = m.groups()
        if math.prod(int(d) for d in dims.split(",") if d) < layer_cache:
            continue
        if dtype == "f32" or op == "broadcast":
            offending.append(line.strip()[:160])
    assert not offending, offending
    assert "gqa_grouped" in text


def test_decode_step_updates_the_kv_cache_in_place(one_chip):
    """The ragged decode step with its cache donated, as the serve engine
    compiles it: the output cache aliases the input, and no operation the
    size of the stacked cache copies it or writes a layer's slab back into
    it (a scan over the cache as its sliced input and stacked output
    does); the new tokens' keys and values are scattered in place."""
    cfg, args = _served_decode_args(one_chip)
    compiled = jax.jit(
        lambda p, t, c: transformer.decode_step(cfg, p, t, c),
        donate_argnums=(2,)).lower(*args).compile()
    stacked = cfg.num_layers * _SLOTS * _MAX_LEN * cfg.num_kv_heads * cfg.hd
    kv_bytes = 2 * stacked * jnp.dtype(jnp.bfloat16).itemsize
    assert compiled.memory_analysis().alias_size_in_bytes >= kv_bytes
    ops = []
    for line in compiled.as_text().splitlines():
        m = _HLO_ARRAY.match(line)
        if m and math.prod(int(d) for d in m.group(2).split(",") if d) \
                >= stacked:
            ops.append((m.group(3), line.split("=")[0].strip()))
    kinds = {op for op, _ in ops}
    assert "scatter" in kinds
    offending = [(op, name) for op, name in ops
                 if op in ("copy", "dynamic-update-slice")
                 or "dynamic-update-slice" in name]
    assert not offending, offending
