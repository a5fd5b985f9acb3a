"""Exact percentiles, as the program's ``repro.core.quantile.percentile``
computes them (numpy's linear method), copied so the benchmark's tail
arithmetic cannot change with the program."""
from __future__ import annotations

from typing import Sequence


def percentile(samples: Sequence[float], q: float) -> float:
    """Exact quantile ``q`` in [0, 1] with linear interpolation."""
    if not 0.0 <= q <= 1.0:
        raise ValueError(f"quantile must be in [0, 1] (got {q!r})")
    if not samples:
        raise ValueError("percentile of an empty sample set")
    xs = sorted(float(v) for v in samples)
    h = (len(xs) - 1) * q
    lo = int(h)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (h - lo)

