"""The SSD scan through the fused Pallas kernels (kernel.py).

``chunk`` resolves through :mod:`repro.kernels.tuning` outside the jit
boundary (kwarg > env > tuned.json > builtin).  The model path
(``repro.models.layers.ssd_chunked``) passes its own chunk instead.
"""
import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.kernels import tuning

from .kernel import plan, ssd_scan


@functools.partial(jax.jit, static_argnames=("chunk",))
def _ssd(x, dt, A, B, C, D, chunk: int, init_state=None):
    b, _, h, p = x.shape
    h0 = (jnp.zeros((b, h, p, B.shape[-1]), jnp.float32)
          if init_state is None else init_state.astype(jnp.float32))
    return ssd_scan(x, dt, A, B, C, D, h0, chunk,
                    jax.default_backend() != "tpu")


def blocks(x, B, *, chunk: Optional[int] = None) -> Dict[str, int]:
    """The validated, shape-clamped chunk :func:`ssd` runs with
    (kwarg > tuned configuration); the head blocking is ``plan``'s."""
    cfg = tuning.resolve("ssd_scan", chunk=chunk)
    _, l, h, p = x.shape
    n = B.shape[-1]
    eff = {"chunk": min(cfg["chunk"], l)}
    tuning.validate_blocks("ssd_scan", eff, dims={"chunk": l})
    if plan(h, p, n, eff["chunk"]) is None:
        raise ValueError(f"the SSD kernels cannot run {h} heads of {p} and "
                         f"a state of {n} at chunk {eff['chunk']}")
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
