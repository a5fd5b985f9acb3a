"""Public wrapper for the RMSNorm kernel.

``br`` (rows per grid step) resolves through :mod:`repro.kernels.tuning`
outside the jit boundary (kwarg > env > tuned.json > builtin).
"""
import functools
from typing import Dict, Optional

import jax

from repro.kernels import tuning

from .kernel import rmsnorm_pallas


@functools.partial(jax.jit, static_argnames=("eps", "br"))
def _rmsnorm(x, scale, eps: float, br: int):
    return rmsnorm_pallas(x, scale, eps=eps, br=br,
                          interpret=jax.default_backend() != "tpu")


def blocks(x, scale, *, br: Optional[int] = None) -> Dict[str, int]:
    """The validated, shape-clamped block :func:`rmsnorm` runs with
    (kwarg > tuned configuration)."""
    cfg = tuning.resolve("rmsnorm", br=br)
    n, d = x.shape
    eff = {"br": min(cfg["br"], n)}
    # x block + fp32 working copy + output block + the scale row;
    # x2 for the pipeline's double buffer
    vmem = 2 * (eff["br"] * d * (2 * x.dtype.itemsize + 4)
                + d * scale.dtype.itemsize)
    tuning.validate_blocks("rmsnorm", eff, dims={"br": n}, vmem_bytes=vmem)
    return eff


def rmsnorm(x, scale, *, eps: float = 1e-6, br: Optional[int] = None):
    """Row-blocked RMSNorm; ``br`` defaults to the tuned block size."""
    return _rmsnorm(x, scale, eps, blocks(x, scale, br=br)["br"])
