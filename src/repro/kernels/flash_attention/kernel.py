"""Flash attention (causal/GQA) Pallas kernel — cuDNN|Scope-style NN hot-spot.

Grid (B·H, nq, nk): nk innermost so the online-softmax state (m, l, acc)
lives in VMEM scratch across k-steps and the output tile is written once.
Causal tiles above the diagonal are skipped with ``pl.when`` (the TPU grid
is sequential, so skipped steps cost only the (cheap) predicate).

Tiling: q/o tiles (bq, D), k/v tiles (bk, D).  With bq=bk=512, D=128:
working set ≈ (2·512·128·2 + 512·128·4 + 512·512·4) ≈ 1.6 MiB « VMEM.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tuning import VMEM_LIMIT_BYTES

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  causal: bool, bq: int, bk: int, nk: int, scale: float):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    run = (j * bk <= i * bq + bq - 1) if causal else True

    @pl.when(run if causal else j >= 0)
    def _step():
        q = q_ref[0].astype(jnp.float32)              # [bq, D]
        k = k_ref[0].astype(jnp.float32)              # [bk, D]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(s <= NEG_INF, 0.0, p)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...], l_ref[...] = m_new, l_new

    @pl.when(j == nk - 1)
    def _flush():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, bq: int = 512, bk: int = 512,
                           interpret: bool = False) -> jax.Array:
    """q [B,Sq,H,D]; k/v [B,Sk,K,D] (GQA repeats folded here).

    Layout inside the kernel is [BH, S, D] (head-major) so each grid row
    streams contiguous S×D tiles.
    """
    B, Sq, H, D = q.shape
    Sk, K = k.shape[1], k.shape[2]
    if K != H:
        k = jnp.repeat(k, H // K, axis=2)
        v = jnp.repeat(v, H // K, axis=2)
    qt = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    kt = k.transpose(0, 2, 1, 3).reshape(B * H, Sk, D)
    vt = v.transpose(0, 2, 1, 3).reshape(B * H, Sk, D)
    bq_, bk_ = min(bq, Sq), min(bk, Sk)
    assert Sq % bq_ == 0 and Sk % bk_ == 0
    nq, nk = Sq // bq_, Sk // bk_
    scale = 1.0 / math.sqrt(D)

    kernel = functools.partial(_flash_kernel, causal=causal, bq=bq_, bk=bk_,
                               nk=nk, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq_, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk_, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk_, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq_, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq_, 1), jnp.float32),
                        pltpu.VMEM((bq_, 1), jnp.float32),
                        pltpu.VMEM((bq_, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(qt, kt, vt)
    return out.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)
