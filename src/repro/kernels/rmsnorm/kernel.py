"""Fused RMSNorm Pallas kernel: one HBM round-trip per row block.

Grid (nrows/br,): each step loads a [br, d] tile + the [d] scale into VMEM,
computes mean-of-squares in fp32 and writes the normalized tile — XLA's
unfused version reads x twice (square-reduce, then scale).  d up to 8192 at
br=256 → 256·8192·2B ≈ 4 MiB tiles, plus the fp32 working copy.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tuning import VMEM_LIMIT_BYTES


def _rmsnorm_kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps)
                  * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def rmsnorm_pallas(x: jax.Array, scale: jax.Array, *, eps: float = 1e-6,
                   br: int = 256, interpret: bool = False) -> jax.Array:
    orig_shape = x.shape
    d = x.shape[-1]
    xr = x.reshape(-1, d)
    n = xr.shape[0]
    br_ = min(br, n)
    assert n % br_ == 0, (n, br_)
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(n // br_,),
        in_specs=[pl.BlockSpec((br_, d), lambda i: (i, 0)),
                  pl.BlockSpec((d,), lambda i: (0,))],
        out_specs=pl.BlockSpec((br_, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(xr, scale)
    return out.reshape(orig_shape)
