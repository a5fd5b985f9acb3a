"""Mamba-2 SSD scan as two fused Pallas kernels, forward and backward.

The chunked SSD (state-space duality) splits a sequence into chunks of Q
positions.  Within a chunk the output is attention-like, quadratic in Q;
from chunk to chunk a state [p, n] per head carries the rest.  Per chunk c
and head h, with a = dt·A (negative), s its inclusive cumulative sum over
the chunk and s_tot its last entry:

    y_q   = sum_{k<=q} (C_q·B_k) exp(s_q - s_k) dt_k x_k        (intra)
          + exp(s_q) h_in C_q + D x_q                            (inter, skip)
    h_out = exp(s_tot) h_in + sum_k exp(s_tot - s_k) dt_k x_k ⊗ B_k

Both kernels walk the chunks in order (the forward) or in reverse (the
backward) along a sequential grid axis and carry the state, or its
gradient, in float32 VMEM scratch; the [Q, Q] score and decay tiles, the
float32 working copies of x and y and every per-head cumulative sum live
in VMEM only.  HBM holds the inputs as the mixer makes them (x [b,l,h,p]
and B, C [b,l,n] in their own dtype, dt [b,l,h] float32), y in x's dtype,
the final state, and the one residual of the backward: the state entering
each chunk, float32 [b, nc, h/hb, n, hb·p], written by the forward
(b·l·h·p·n·4/Q bytes: 201 MB a layer at 2 x 8,192 positions of
mamba2-780m).

Grid (batch, chunk, head block).  A grid step holds hb heads: x, y and
their gradients as a [Q, hb·p] block of the [b, l, h·p] view (a free
reshape), so that no head-major copy is made.  The TPU tiles the last two
dims of a block by (8, 128), so the heads of a block are handled in
groups of gw lanes (two heads of 64): a group's matrix products run on a
lane-aligned [Q, gw] tile, and a head's own result is kept by a lane mask.
C·Bᵀ is made once per chunk, at the first head block, and shared by every
head.  dt arrives as a [Q, h] block; a head's column is picked by a lane
mask, and its row and cumulative sums by masked reductions over the
[Q, Q] tile, exact in float32.  The state is stored transposed, [n, p]
per head, so that the heads of a group sit side by side in lanes.

Precision.  Segment sums, cumulative sums, exp, the state carry and
every accumulation are float32.  The matrix products of data take
float32 operands at ``MXU_PRECISION`` (``Precision.DEFAULT``), as the XLA
path's einsums do, and accumulate in float32.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tuning import VMEM_LIMIT_BYTES

#: Precision of every matrix product of data (operands float32).
MXU_PRECISION = lax.Precision.DEFAULT

_F32 = jnp.float32


class Plan(NamedTuple):
    """How the heads are blocked: ``hb`` heads a grid step, in groups of
    ``gw`` lanes."""
    hb: int
    gw: int


def vmem_bytes(chunk: int, hb: int, h: int, p: int, n: int) -> int:
    """The backward kernel's working set a grid step (the forward's is
    smaller), counted in float32: double-buffered x, dy and dx blocks
    [Q, hb·p] and the entering state [n, hb·p]; the resident final-state
    blocks and the state carry, [h, p, n] each; the [Q, Q] tiles (C·Bᵀ,
    its gradient, a head's decay and scores); dt [Q, h]; B, C and their
    gradients [Q, n]."""
    Q, lanes = chunk, hb * p
    return 4 * (2 * (3 * Q * lanes + n * lanes) + 5 * h * p * n
                + 6 * Q * Q + 2 * Q * h + 4 * Q * n)


def plan(h: int, p: int, n: int, chunk: int) -> Optional[Plan]:
    """The head blocking for ``h`` heads of ``p`` and a state of ``n``:
    the most heads a grid step whose working set fits the scoped VMEM
    (fewer grid steps, and C·Bᵀ's tile shared by more heads).  None where
    the kernels cannot run: a chunk not a multiple of 8, a head size that
    neither divides 128 nor is a multiple of it, heads that do not fill
    whole lane groups, or a state too large for VMEM."""
    if chunk % 8:
        return None
    if p % 128 == 0:
        gw = p
    elif 128 % p == 0:
        gw = min(128, h * p)
    else:
        return None
    if h % (gw // p):
        return None
    best = None
    for hb in range(gw // p, h + 1, gw // p):
        if h % hb == 0 and (hb * p % 128 == 0 or hb == h) \
                and vmem_bytes(chunk, hb, h, p, n) <= VMEM_LIMIT_BYTES:
            best = hb
    return None if best is None else Plan(best, gw)


def _dot(a, b, ca: int, cb: int):
    """a·b contracting a's dim ``ca`` with b's dim ``cb``, float32."""
    return lax.dot_general(a, b, (((ca,), (cb,)), ((), ())),
                           precision=MXU_PRECISION,
                           preferred_element_type=_F32)


class _Chunk:
    """Iotas and masks of one grid step, and each head's cumulative sums
    over the chunk."""

    def __init__(self, Q: int, p: int, gw: int, dt, a_ref, head0):
        self.Q, self.gw = Q, gw
        self.dt, self.a_ref, self.head0 = dt, a_ref, head0
        self.iq = lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
        self.ik = lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
        self.causal = self.ik <= self.iq
        self.eye = self.ik == self.iq
        self.lane_h = lax.broadcasted_iota(jnp.int32, dt.shape, 1)
        self.last = lax.broadcasted_iota(jnp.int32, (Q, 1), 0) == Q - 1
        lane_g = lax.broadcasted_iota(jnp.int32, (1, gw), 1)
        # [1, gw]: the lanes of a group's i-th head
        self.masks = [(lane_g >= i * p) & (lane_g < (i + 1) * p)
                      for i in range(gw // p)]

    def group(self, g):
        """The lanes of lane group ``g`` of the block: a loop's groups
        are whole 128-lane tiles (``plan``)."""
        if isinstance(g, int):
            return pl.ds(g * self.gw, self.gw)
        return pl.ds(pl.multiple_of(g * self.gw, 128), self.gw)

    def row(self, col):
        """[Q, 1] → [1, Q], exact."""
        return jnp.sum(jnp.where(self.eye, col, 0.0), axis=0, keepdims=True)

    def col(self, row):
        """[1, Q] → [Q, 1], exact."""
        return jnp.sum(jnp.where(self.eye, row, 0.0), axis=1, keepdims=True)

    def head(self, j):
        """Head ``j`` of the block: its A, dt and the inclusive cumulative
        sum s of a = dt·A, as columns [Q, 1] and rows [1, Q]."""
        A = self.a_ref[self.head0 + j]
        dt_col = jnp.sum(jnp.where(self.lane_h == self.head0 + j, self.dt,
                                   0.0), axis=1, keepdims=True)
        dt_row = self.row(dt_col)
        s_col = jnp.sum(jnp.where(self.causal, dt_row * A, 0.0), axis=1,
                        keepdims=True)
        s_row = self.row(s_col)
        s_tot = jnp.sum(jnp.where(self.last, s_col, 0.0), axis=0,
                        keepdims=True)                        # [1, 1]
        return dict(A=A, dt_col=dt_col, dt_row=dt_row, s_col=s_col,
                    s_row=s_row, s_tot=s_tot)

    def decay(self, hd):
        """exp(s_q - s_k) on and below the diagonal, 0 above: the segment
        sums are masked to -inf before exp, never after."""
        return jnp.exp(jnp.where(self.causal, hd["s_col"] - hd["s_row"],
                                 -jnp.inf))

    def keep(self, i: int, v):
        """v [R, gw] on the lanes of the group's i-th head, 0 elsewhere."""
        return v if len(self.masks) == 1 else jnp.where(self.masks[i], v, 0.0)

    def lanes(self, heads, key: str):
        """A per-head column (or [1, 1]) spread over each head's lanes of
        the group: [Q, gw] (or [1, gw])."""
        if len(heads) == 1:
            v = heads[0][key]
            return jnp.broadcast_to(v, (v.shape[0], self.gw))
        return sum(jnp.where(m, hd[key], 0.0)
                   for m, hd in zip(self.masks, heads))

    def per_head(self, v, i: int):
        """Sum over the lanes of the group's i-th head of [R, gw] → [R, 1]."""
        return jnp.sum(self.keep(i, v), axis=1, keepdims=True)


def _groups(n: int, body, init):
    """``body(g, carry)`` over the lane groups of a block: in a loop, so
    that a block of many heads traces and compiles one group's code."""
    return body(0, init) if n == 1 else lax.fori_loop(0, n, body, init)


def _fwd_kernel(a_ref, dt_ref, x_ref, b_ref, c_ref, d_ref, h0_ref,
                y_ref, hfin_ref, *rest, Q: int, p: int, hb: int, gw: int,
                save: bool):
    hin_ref, g_scr, st_scr = rest if save else (None, *rest)
    ci, hbi = pl.program_id(1), pl.program_id(2)
    Bm = b_ref[0].astype(_F32)                       # [Q, n]
    Cm = c_ref[0].astype(_F32)

    @pl.when(hbi == 0)
    def _():
        g_scr[...] = _dot(Cm, Bm, 1, 1)              # C·Bᵀ, all heads

    @pl.when(ci == 0)
    def _():
        st_scr[hbi] = h0_ref[0, hbi]

    G = g_scr[...]
    ch = _Chunk(Q, p, gw, dt_ref[0].astype(_F32), a_ref, hbi * hb)
    hpg = gw // p

    def group(g, carry):
        sl = ch.group(g)
        xg = x_ref[0, :, sl].astype(_F32)            # [Q, gw]
        heads = [ch.head(g * hpg + i) for i in range(hpg)]
        y = 0.0
        for i, hd in enumerate(heads):
            w = G * ch.decay(hd) * hd["dt_row"]
            y = y + ch.keep(i, _dot(w, xg, 1, 0))
        s_g = ch.lanes(heads, "s_col")
        dt_g = ch.lanes(heads, "dt_col")
        tot_g = ch.lanes(heads, "s_tot")             # [1, gw]
        hT = st_scr[hbi, :, sl]                      # [n, gw] entering
        y = y + jnp.exp(s_g) * _dot(Cm, hT, 1, 0) + d_ref[:, sl] * xg
        y_ref[0, :, sl] = y.astype(y_ref.dtype)
        if save:
            hin_ref[0, 0, 0, :, sl] = hT
        xe = xg * (jnp.exp(tot_g - s_g) * dt_g)
        st_scr[hbi, :, sl] = jnp.exp(tot_g) * hT + _dot(Bm, xe, 0, 0)
        return carry

    _groups(hb // hpg, group, 0)

    @pl.when(ci == pl.num_programs(1) - 1)
    def _():
        hfin_ref[0, hbi] = st_scr[hbi]


def _bwd_kernel(a_ref, dt_ref, x_ref, b_ref, c_ref, d_ref, hin_ref, dy_ref,
                dfin_ref,
                dx_ref, ddt_ref, db_ref, dc_ref, da_ref, dd_ref, dh0_ref,
                g_scr, dg_scr, db_scr, dc_scr, ds_scr,
                *, Q: int, p: int, hb: int, gw: int):
    ci, hbi = pl.program_id(1), pl.program_id(2)
    nhb = pl.num_programs(2)
    Bm = b_ref[0].astype(_F32)                       # [Q, n]
    Cm = c_ref[0].astype(_F32)

    @pl.when(hbi == 0)
    def _():
        g_scr[...] = _dot(Cm, Bm, 1, 1)
        dg_scr[...] = jnp.zeros_like(dg_scr)
        db_scr[...] = jnp.zeros_like(db_scr)
        dc_scr[...] = jnp.zeros_like(dc_scr)
        ddt_ref[...] = jnp.zeros_like(ddt_ref)

    @pl.when(ci == 0)                                # the last chunk
    def _():
        ds_scr[hbi] = dfin_ref[0, hbi]

    @pl.when((ci == 0) & (hbi == 0))
    def _():
        da_ref[...] = jnp.zeros_like(da_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    G = g_scr[...]
    ch = _Chunk(Q, p, gw, dt_ref[0].astype(_F32), a_ref, hbi * hb)
    hpg = gw // p
    lane_b = lax.broadcasted_iota(jnp.int32, (1, hb), 1)

    def group(g, carry):
        dG, dB, dC, ddt, da = carry                  # this step's heads
        sl = ch.group(g)
        xg = x_ref[0, :, sl].astype(_F32)            # [Q, gw]
        dyg = dy_ref[0, :, sl].astype(_F32)
        hT = hin_ref[0, 0, 0, :, sl]                 # [n, gw] entering
        dH = ds_scr[hbi, :, sl]                      # d(state leaving)
        heads = [ch.head(g * hpg + i) for i in range(hpg)]
        s_g = ch.lanes(heads, "s_col")
        dt_g = ch.lanes(heads, "dt_col")
        tot_g = ch.lanes(heads, "s_tot")
        r_g = jnp.exp(s_g)
        ed_g = jnp.exp(tot_g - s_g) * dt_g
        # inter-chunk read-out and the state: their gradients
        y_inter = r_g * _dot(Cm, hT, 1, 0)
        dyr = dyg * r_g
        dC = dC + _dot(dyr, hT, 1, 1)
        Z = _dot(Bm, dH, 1, 0)                       # [Q, gw]
        dB = dB + _dot(xg * ed_g, dH, 1, 1)
        ds_scr[hbi, :, sl] = jnp.exp(tot_g) * dH + _dot(Cm, dyr, 0, 0)
        dx = ed_g * Z + d_ref[:, sl] * dyg
        dd_ref[0, hbi, :, sl] += jnp.sum(dyg * xg, axis=0, keepdims=True)
        dyy, xz, dhh = dyg * y_inter, xg * Z, dH * hT
        for i, hd in enumerate(heads):
            j = g * hpg + i
            L = ch.decay(hd)
            Ld = L * hd["dt_row"]
            P = _dot(ch.keep(i, dyg), xg, 1, 1)      # [Q, Q]
            dx = dx + ch.keep(i, _dot(G * Ld, dyg, 0, 0))
            dG = dG + P * Ld
            M = P * G * L
            ddt_intra = jnp.sum(M, axis=0, keepdims=True)            # [1, Q]
            R = M * hd["dt_row"]
            ddt_state = (jnp.exp(hd["s_tot"] - hd["s_col"])
                         * ch.per_head(xz, i))                       # [Q, 1]
            dstate_s = hd["dt_col"] * ddt_state
            h_dot = jnp.sum(ch.per_head(dhh, i), axis=0, keepdims=True)
            out_dot = (jnp.exp(hd["s_tot"]) * h_dot
                       + jnp.sum(dstate_s, axis=0, keepdims=True))
            ds_col = (jnp.sum(R, axis=1, keepdims=True)
                      + ch.per_head(dyy, i) - dstate_s
                      + jnp.where(ch.last, out_dot, 0.0))
            ds_row = ch.row(ds_col) - hd["dt_row"] * ddt_intra
            # s is an inclusive cumulative sum: da_j = sum_{q >= j} ds_q
            da_col = jnp.sum(jnp.where(ch.ik >= ch.iq, ds_row, 0.0),
                             axis=1, keepdims=True)
            ddt_col = ch.col(ddt_intra) + ddt_state + hd["A"] * da_col
            ddt = ddt + jnp.where(ch.lane_h == hbi * hb + j, ddt_col, 0.0)
            da = da + jnp.where(lane_b == j, jnp.sum(
                da_col * hd["dt_col"], axis=0, keepdims=True), 0.0)
        dx_ref[0, :, sl] = dx.astype(dx_ref.dtype)
        return dG, dB, dC, ddt, da

    n = Bm.shape[1]
    dG, dB, dC, ddt, da = _groups(hb // hpg, group, (
        jnp.zeros((Q, Q), _F32), jnp.zeros((Q, n), _F32),
        jnp.zeros((Q, n), _F32), jnp.zeros(ddt_ref.shape[1:], _F32),
        jnp.zeros(da_ref.shape[2:], _F32)))
    ddt_ref[0] += ddt
    da_ref[0, hbi] += da
    dg_scr[...] += dG
    db_scr[...] += dB
    dc_scr[...] += dC

    @pl.when(hbi == nhb - 1)
    def _():
        dG = dg_scr[...]
        dc_ref[0] = (dc_scr[...] + _dot(dG, Bm, 1, 0)).astype(dc_ref.dtype)
        db_ref[0] = (db_scr[...] + _dot(dG, Cm, 0, 0)).astype(db_ref.dtype)

    @pl.when(ci == pl.num_programs(1) - 1)           # the first chunk
    def _():
        dh0_ref[0, hbi] = ds_scr[hbi]


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _shapes(x, B, chunk):
    b, l, h, p = x.shape
    n = B.shape[-1]
    pl_ = plan(h, p, n, chunk)
    if pl_ is None or l % chunk:
        raise ValueError(f"the SSD kernels cannot run {h} heads of {p} "
                         f"and a state of {n} at chunk {chunk} over {l} "
                         f"positions")
    return b, l, h, p, n, l // chunk, pl_.hb, pl_.gw, h // pl_.hb


def _state_t(s, nhb: int):
    """[b, h, p, n] → the kernels' [b, h/hb, n, hb·p]."""
    b, h, p, n = s.shape
    return jnp.swapaxes(s.reshape(b, nhb, h // nhb * p, n), 2, 3)


def _state(s, h: int):
    """The kernels' [b, h/hb, n, hb·p] → [b, h, p, n]."""
    b, nhb, n, lanes = s.shape
    return jnp.swapaxes(s, 2, 3).reshape(b, h, lanes * nhb // h, n)


def ssd_forward(x, dt, A, B, C, D, h0, *, chunk: int, save: bool = False,
                interpret: bool = False):
    """The forward kernel.  x [b,l,h,p]; dt [b,l,h]; A, D [h];
    B, C [b,l,1,n]; h0 [b,h,p,n] float32.  Returns (y [b,l,h,p] in
    x.dtype, final state [b,h,p,n] float32, and with ``save`` the states
    entering each chunk, [b, nc, h/hb, n, hb·p] float32)."""
    b, l, h, p, n, nc, hb, gw, nhb = _shapes(x, B, chunk)
    Q, lanes = chunk, hb * p
    blk = lambda *s: pl.BlockSpec(s[:-1], s[-1])      # noqa: E731
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),
        blk(1, Q, h, lambda bi, ci, hi: (bi, ci, 0)),
        blk(1, Q, lanes, lambda bi, ci, hi: (bi, ci, hi)),
        blk(1, Q, n, lambda bi, ci, hi: (bi, ci, 0)),
        blk(1, Q, n, lambda bi, ci, hi: (bi, ci, 0)),
        blk(1, lanes, lambda bi, ci, hi: (0, hi)),
        blk(1, nhb, n, lanes, lambda bi, ci, hi: (bi, 0, 0, 0)),
    ]
    out_specs = [
        blk(1, Q, lanes, lambda bi, ci, hi: (bi, ci, hi)),
        blk(1, nhb, n, lanes, lambda bi, ci, hi: (bi, 0, 0, 0)),
    ]
    out_shape = [jax.ShapeDtypeStruct((b, l, h * p), x.dtype),
                 jax.ShapeDtypeStruct((b, nhb, n, lanes), _F32)]
    if save:
        out_specs.append(blk(1, 1, 1, n, lanes,
                             lambda bi, ci, hi: (bi, ci, hi, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((b, nc, nhb, n, lanes), _F32))
    outs = pl.pallas_call(
        functools.partial(_fwd_kernel, Q=Q, p=p, hb=hb, gw=gw, save=save),
        grid=(b, nc, nhb),
        in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((Q, Q), _F32),
                        pltpu.VMEM((nhb, n, lanes), _F32)],
        compiler_params=_params(), interpret=interpret, name="ssd_fwd",
    )(A.astype(_F32), dt.astype(_F32), x.reshape(b, l, h * p),
      B.reshape(b, l, n), C.reshape(b, l, n),
      jnp.repeat(D.astype(_F32), p)[None], _state_t(h0, nhb))
    y, hfin = outs[0].reshape(x.shape), _state(outs[1], h)
    return (y, hfin, outs[2]) if save else (y, hfin)


def ssd_backward(x, dt, A, B, C, D, h_in, dy, dfin, *, chunk: int,
                 interpret: bool = False):
    """The backward kernel: from the inputs, the forward's entering
    states and the gradients of y and of the final state, the gradients
    (dx, ddt, dA, dB, dC, dD, d h0) in the inputs' dtypes."""
    b, l, h, p, n, nc, hb, gw, nhb = _shapes(x, B, chunk)
    Q, lanes = chunk, hb * p
    rev = lambda bi, ci, hi: (bi, nc - 1 - ci)         # noqa: E731
    blk = lambda *s: pl.BlockSpec(s[:-1], s[-1])      # noqa: E731
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),
        blk(1, Q, h, lambda *g: (*rev(*g), 0)),
        blk(1, Q, lanes, lambda *g: (*rev(*g), g[2])),
        blk(1, Q, n, lambda *g: (*rev(*g), 0)),
        blk(1, Q, n, lambda *g: (*rev(*g), 0)),
        blk(1, lanes, lambda bi, ci, hi: (0, hi)),
        blk(1, 1, 1, n, lanes, lambda *g: (*rev(*g), g[2], 0, 0)),
        blk(1, Q, lanes, lambda *g: (*rev(*g), g[2])),
        blk(1, nhb, n, lanes, lambda bi, ci, hi: (bi, 0, 0, 0)),
    ]
    out_specs = [
        blk(1, Q, lanes, lambda *g: (*rev(*g), g[2])),
        blk(1, Q, h, lambda *g: (*rev(*g), 0)),
        blk(1, Q, n, lambda *g: (*rev(*g), 0)),
        blk(1, Q, n, lambda *g: (*rev(*g), 0)),
        blk(1, nhb, 1, hb, lambda bi, ci, hi: (bi, 0, 0, 0)),
        blk(1, nhb, 1, lanes, lambda bi, ci, hi: (bi, 0, 0, 0)),
        blk(1, nhb, n, lanes, lambda bi, ci, hi: (bi, 0, 0, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((b, l, h * p), x.dtype),
        jax.ShapeDtypeStruct((b, l, h), _F32),
        jax.ShapeDtypeStruct((b, l, n), B.dtype),
        jax.ShapeDtypeStruct((b, l, n), C.dtype),
        jax.ShapeDtypeStruct((b, nhb, 1, hb), _F32),
        jax.ShapeDtypeStruct((b, nhb, 1, lanes), _F32),
        jax.ShapeDtypeStruct((b, nhb, n, lanes), _F32),
    ]
    dx, ddt, dB, dC, dA, dD, dh0 = pl.pallas_call(
        functools.partial(_bwd_kernel, Q=Q, p=p, hb=hb, gw=gw),
        grid=(b, nc, nhb),
        in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((Q, Q), _F32), pltpu.VMEM((Q, Q), _F32),
                        pltpu.VMEM((Q, n), _F32), pltpu.VMEM((Q, n), _F32),
                        pltpu.VMEM((nhb, n, lanes), _F32)],
        compiler_params=_params(), interpret=interpret, name="ssd_bwd",
    )(A.astype(_F32), dt.astype(_F32), x.reshape(b, l, h * p),
      B.reshape(b, l, n), C.reshape(b, l, n),
      jnp.repeat(D.astype(_F32), p)[None], h_in,
      dy.astype(x.dtype).reshape(b, l, h * p),
      _state_t(dfin.astype(_F32), nhb))
    return (dx.reshape(x.shape), ddt.astype(dt.dtype),
            dA.sum(axis=(0, 2)).reshape(h).astype(A.dtype),
            dB.reshape(B.shape), dC.reshape(C.shape),
            dD.sum(axis=(0, 2)).reshape(h, p).sum(-1).astype(D.dtype),
            _state(dh0, h))


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def ssd_scan(x, dt, A, B, C, D, h0, chunk: int, interpret: bool = False):
    """The SSD scan through the two kernels: ``ssd_chunked``'s contract
    with the initial state ``h0`` [b,h,p,n] float32 given (zeros for
    none) and the length a multiple of ``chunk``.  Returns (y, final
    state)."""
    return ssd_forward(x, dt, A, B, C, D, h0, chunk=chunk,
                       interpret=interpret)


def _scan_fwd(x, dt, A, B, C, D, h0, chunk, interpret):
    y, hfin, h_in = ssd_forward(x, dt, A, B, C, D, h0, chunk=chunk,
                                save=True, interpret=interpret)
    return (y, hfin), (x, dt, A, B, C, D, h_in)


def _scan_bwd(chunk, interpret, res, cts):
    return ssd_backward(*res, *cts, chunk=chunk, interpret=interpret)


ssd_scan.defvjp(_scan_fwd, _scan_bwd)
