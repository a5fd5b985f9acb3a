"""Mamba2 SSD chunk kernel: intra-chunk output + chunk state, per grid step.

The SSD decomposition splits work into (a) quadratic-in-chunk local terms
and (b) a short inter-chunk recurrence.  This kernel computes (a) plus the
per-chunk states entirely in VMEM — grid (B, H, nc), blocks of one
(batch, head, chunk) each: x [Q,P], dt [Q,1], B/C [Q,N].  The tiny
inter-chunk scan and the final C·h_in combination stay in XLA (ops.py) —
they are O(nc·P·N) and memory-bound either way.

Layout: the TPU tiles the last two dims of every block by (8, 128) unless a
block spans the whole dim, so a block holding one head of a [b,l,h,p]
array is refused.  The wrapper therefore hands the kernel head-major
copies (x [b,h,l,p], dt [b,h,l,1]) and A whole in SMEM; per-head columns
become (Q, P) and (Q, 1) tiles, and Q only needs to be a multiple of 8.
The in-chunk cumulative sums are masked reductions over the [Q,Q] tile,
which the decay matrix needs anyway.

VMEM at Q=256, P=64, N=128: decay [Q,Q] fp32 + state [P,N] + tiles ≈ 0.6 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tuning import VMEM_LIMIT_BYTES


def _ssd_chunk_kernel(a_ref, x_ref, dt_ref, b_ref, c_ref,
                      y_ref, state_ref, ecs_ref, *, Q: int):
    A = a_ref[pl.program_id(1)]                     # scalar (SMEM)
    x = x_ref[0, 0].astype(jnp.float32)             # [Q, P]
    dt_col = dt_ref[0, 0].astype(jnp.float32)       # [Q, 1]
    Bm = b_ref[0].astype(jnp.float32)               # [Q, N]
    Cm = c_ref[0].astype(jnp.float32)               # [Q, N]

    qi = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    ki = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    causal = ki <= qi
    # row copies of the per-step columns (diagonal pick), then inclusive
    # cumsums of a = dt·A (negative) in both orientations
    dt_row = jnp.sum(jnp.where(qi == ki, dt_col, 0.0), axis=0,
                     keepdims=True)                 # [1, Q]
    a_col, a_row = dt_col * A, dt_row * A
    a_cs_col = jnp.sum(jnp.where(causal, a_row, 0.0), axis=1,
                       keepdims=True)               # [Q, 1]
    a_cs_row = jnp.sum(jnp.where(qi <= ki, a_col, 0.0), axis=0,
                       keepdims=True)               # [1, Q]
    a_tot = jnp.sum(a_col, axis=0, keepdims=True)   # [1, 1]
    # intra-chunk: y_q = sum_{k<=q} exp(a_cs_q - a_cs_k) (C_q·B_k) dt_k x_k
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [Q,Q]
    decay = jnp.exp(jnp.where(causal, a_cs_col - a_cs_row, -jnp.inf))
    w = cb * decay * dt_row
    y = jax.lax.dot_general(w, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # [Q,P]
    # chunk state: S = sum_k exp(a_tot - a_cs_k) dt_k x_k ⊗ B_k   [P,N]
    edecay = jnp.exp(a_tot - a_cs_col) * dt_col                   # [Q,1]
    state = jax.lax.dot_general(x * edecay, Bm,
                                (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    y_ref[0, 0] = y.astype(y_ref.dtype)
    state_ref[0, 0, 0] = state
    ecs_ref[0, 0] = jnp.exp(a_cs_col)


def ssd_chunk_pallas(x, dt, A, B, C, *, chunk: int = 128,
                     interpret: bool = False):
    """x [b,l,h,p]; dt [b,l,h]; A [h]; B/C [b,l,n] (group dim folded).

    Returns (y_intra [b,l,h,p] fp32-accurate in x.dtype,
             states [b,nc,h,p,n] fp32, exp_a_cs [b,l,h] fp32).
    """
    b, l, h, p = x.shape
    n = B.shape[-1]
    Q = min(chunk, l)
    assert l % Q == 0
    nc = l // Q
    xh = jnp.swapaxes(x, 1, 2)                      # [b,h,l,p]
    dth = jnp.swapaxes(dt, 1, 2)[..., None]         # [b,h,l,1]
    y, states, ecs = pl.pallas_call(
        functools.partial(_ssd_chunk_kernel, Q=Q),
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, Q, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, Q, 1), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, Q, n), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, Q, n), lambda bi, hi, ci: (bi, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Q, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, 1, p, n),
                         lambda bi, hi, ci: (bi, ci, hi, 0, 0)),
            pl.BlockSpec((1, 1, Q, 1), lambda bi, hi, ci: (bi, hi, ci, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, l, p), x.dtype),
            jax.ShapeDtypeStruct((b, nc, h, p, n), jnp.float32),
            jax.ShapeDtypeStruct((b, h, l, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(A.astype(jnp.float32), xh, dth, B, C)
    return jnp.swapaxes(y, 1, 2), states, jnp.swapaxes(ecs[..., 0], 1, 2)
