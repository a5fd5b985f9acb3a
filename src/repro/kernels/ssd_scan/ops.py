"""SSD via the Pallas chunk kernel + XLA inter-chunk recurrence.

``chunk`` resolves through :mod:`repro.kernels.tuning` outside the jit
boundary (kwarg > env > tuned.json > builtin).
"""
import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels import tuning

from .kernel import ssd_chunk_pallas


@functools.partial(jax.jit, static_argnames=("chunk",))
def _ssd(x, dt, A, B, C, D, chunk: int, init_state=None):
    b, l, h, p = x.shape
    interpret = jax.default_backend() != "tpu"
    y_intra, states, ecs = ssd_chunk_pallas(
        x, dt, A, B[:, :, 0], C[:, :, 0], chunk=chunk, interpret=interpret)
    nc = states.shape[1]
    Q = l // nc
    # decay across a whole chunk = exp(a_tot) = ecs at the chunk's last row
    etot = ecs.reshape(b, nc, Q, h)[:, :, -1]            # [b,nc,h]

    h0 = (jnp.zeros((b, h, p, float_n := states.shape[-1]), jnp.float32)
          if init_state is None else init_state.astype(jnp.float32))

    def carry(prev, inp):
        s_c, e_c = inp
        new = prev * e_c[:, :, None, None] + s_c
        return new, prev                                  # emit entering state

    hfin, h_in = lax.scan(carry, h0, (jnp.moveaxis(states, 1, 0),
                                      jnp.moveaxis(etot, 1, 0)))
    h_in = jnp.moveaxis(h_in, 0, 1)                       # [b,nc,h,p,n]
    Cc = C[:, :, 0].astype(jnp.float32).reshape(b, nc, Q, -1)
    ecs_c = ecs.reshape(b, nc, Q, h)
    y_inter = jnp.einsum("bcqn,bcqh,bchpn->bcqhp", Cc, ecs_c, h_in)
    y = y_intra.astype(jnp.float32) + y_inter.reshape(b, l, h, p)
    y = y + x.astype(jnp.float32) * D[None, None, :, None]
    return y.astype(x.dtype), hfin


def blocks(x, B, *, chunk: Optional[int] = None) -> Dict[str, int]:
    """The validated, shape-clamped chunk :func:`ssd` runs with
    (kwarg > tuned configuration)."""
    cfg = tuning.resolve("ssd_scan", chunk=chunk)
    _, l, _, p = x.shape
    n = B.shape[-1]
    eff = {"chunk": min(cfg["chunk"], l)}
    Q = eff["chunk"]
    # per grid step: x/y chunks, B/C chunks, dt + cumsum rows, the state
    # tile and the three Q x Q decay matrices (all fp32 in-kernel);
    # x2 for the pipeline's double buffer
    vmem = 2 * 4 * (2 * Q * p + 2 * Q * n + 2 * Q + p * n + 3 * Q * Q)
    tuning.validate_blocks("ssd_scan", eff, dims={"chunk": l},
                           vmem_bytes=vmem)
    return eff


def ssd(x, dt, A, B, C, D, *, chunk: Optional[int] = None,
        init_state=None):
    """Same contract as repro.models.layers.ssd_chunked (g=1 folded).

    x [b,l,h,p]; dt [b,l,h]; A [h]; B,C [b,l,g,n]; D [h].
    Returns (y [b,l,h,p], final_state [b,h,p,n]).  ``chunk`` defaults to
    the tuned intra-chunk length.
    """
    return _ssd(x, dt, A, B, C, D, blocks(x, B, chunk=chunk)["chunk"],
                init_state=init_state)
