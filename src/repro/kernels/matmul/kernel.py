"""MXU-tiled matmul — the TCU|Scope analogue body.

Grid (M/bm, N/bn, K/bk); K is the innermost (sequential) grid dim so the
fp32 VMEM accumulator carries across K steps and spills to HBM exactly once
per (i, j) tile.  Block sizes default to MXU-aligned 512×512×512 (bf16
working set = 2·512·512·2B + acc 512·512·4B ≈ 2.1 MiB — far under the
scoped-VMEM limit, ``repro.kernels.tuning.VMEM_LIMIT_BYTES``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tuning import VMEM_LIMIT_BYTES


def _matmul_kernel(x_ref, y_ref, o_ref, acc_ref, *, nk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], y_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matmul_pallas(x: jax.Array, y: jax.Array, *,
                  bm: int = 512, bn: int = 512, bk: int = 512,
                  out_dtype=None, interpret: bool = False) -> jax.Array:
    """x [M,K] @ y [K,N] with explicit VMEM tiling."""
    M, K = x.shape
    K2, N = y.shape
    assert K == K2, (x.shape, y.shape)
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, \
        f"{(M, N, K)} not divisible by {(bm, bn, bk)}"
    nk = K // bk
    out_dtype = out_dtype or x.dtype
    return pl.pallas_call(
        functools.partial(_matmul_kernel, nk=nk),
        grid=(M // bm, N // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(x, y)
