"""Public wrapper for the flash-attention kernel.

``bq``/``bk`` resolve through :mod:`repro.kernels.tuning` outside the
jit boundary (kwarg > env > tuned.json > builtin) so tuned defaults and
tune-trial overrides take effect without retracing stale configs.
"""
import functools
from typing import Dict, Optional

import jax

from repro.kernels import tuning

from .kernel import flash_attention_pallas


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk"))
def _flash_attention(q, k, v, causal: bool, bq: int, bk: int):
    return flash_attention_pallas(
        q, k, v, causal=causal, bq=bq, bk=bk,
        interpret=jax.default_backend() != "tpu")


def blocks(q, k, *, bq: Optional[int] = None,
           bk: Optional[int] = None) -> Dict[str, int]:
    """The validated, shape-clamped blocks :func:`flash_attention` runs
    with (kwarg > tuned configuration)."""
    cfg = tuning.resolve("flash_attention", bq=bq, bk=bk)
    Sq, Sk = q.shape[1], k.shape[1]             # q [B,Sq,H,D], k [B,Sk,K,D]
    D = q.shape[-1]
    eff = {"bq": min(cfg["bq"], Sq), "bk": min(cfg["bk"], Sk)}
    # q block + k/v blocks + the bq x bk scores tile + fp32 acc and the
    # m/l running stats + the output block; x2 for double buffering
    vmem = 2 * ((eff["bq"] + 2 * eff["bk"]) * D * q.dtype.itemsize
                + eff["bq"] * eff["bk"] * 4
                + eff["bq"] * (D + 2) * 4
                + eff["bq"] * D * q.dtype.itemsize)
    tuning.validate_blocks("flash_attention", eff,
                           dims={"bq": Sq, "bk": Sk}, vmem_bytes=vmem)
    return eff


def flash_attention(q, k, v, *, causal: bool = True,
                    bq: Optional[int] = None, bk: Optional[int] = None):
    """Online-softmax attention; ``bq``/``bk`` default to tuned blocks."""
    return _flash_attention(q, k, v, causal, **blocks(q, k, bq=bq, bk=bk))
