"""What several readers share."""
from __future__ import annotations

import importlib
from typing import Dict, Optional


def idle_share_pct(ctx: Dict) -> Optional[float]:
    """Percent of the traced window in which no operation ran on the
    device, mean over the cell's devices."""
    share = ctx["trace"].idle_share()
    return None if share is None else 100.0 * share


def counts(ctx: Dict):
    """The operation and byte counts of the configuration's family."""
    return importlib.import_module(f"bench.counts.{ctx['model']['family']}")
