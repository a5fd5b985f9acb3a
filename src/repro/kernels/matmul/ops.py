"""Public wrapper: Pallas on TPU, interpret-mode elsewhere.

Block knobs resolve through :mod:`repro.kernels.tuning` (kwarg > env >
tuned.json > builtin) *before* the jit boundary, so a new tuned artifact
or a tune-trial override is honoured on the next call rather than being
frozen into a cached trace keyed on the default.
"""
import functools
from typing import Dict, Optional

import jax

from repro.kernels import tuning

from .kernel import matmul_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk"))
def _matmul(x, y, bm: int, bn: int, bk: int):
    return matmul_pallas(x, y, bm=bm, bn=bn, bk=bk,
                         interpret=not _on_tpu())


def blocks(x, y, *, bm: Optional[int] = None, bn: Optional[int] = None,
           bk: Optional[int] = None) -> Dict[str, int]:
    """The validated, shape-clamped blocks :func:`matmul` runs ``x @ y``
    with (kwarg > tuned configuration)."""
    cfg = tuning.resolve("matmul", bm=bm, bn=bn, bk=bk)
    M, K = x.shape
    N = y.shape[1]
    eff = {"bm": min(cfg["bm"], M), "bn": min(cfg["bn"], N),
           "bk": min(cfg["bk"], K)}
    # one grid step holds an x block, a y block, the fp32 accumulator
    # scratch and the output block; x2 for the pipeline's double buffer
    vmem = 2 * (eff["bm"] * eff["bk"] * x.dtype.itemsize
                + eff["bk"] * eff["bn"] * y.dtype.itemsize
                + eff["bm"] * eff["bn"] * (4 + x.dtype.itemsize))
    tuning.validate_blocks("matmul", eff, dims={"bm": M, "bn": N, "bk": K},
                           vmem_bytes=vmem)
    return eff


def matmul(x, y, *, bm: Optional[int] = None, bn: Optional[int] = None,
           bk: Optional[int] = None):
    """Tiled ``x @ y``; block sizes default to the tuned configuration."""
    return _matmul(x, y, **blocks(x, y, bm=bm, bn=bn, bk=bk))
