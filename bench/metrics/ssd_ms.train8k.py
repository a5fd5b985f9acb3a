"""Device time of a train step in the SSD scan (ms a step): the chunked
state-space scan of every Mamba-2 layer, forward, recompute and backward.
The leaf operations of ``jit_train_step`` whose HLO ``op_name`` carries
the program's ``ssd`` scope, over the traced steps (bench/ssm_scopes.py)."""
from __future__ import annotations

from typing import Dict, Optional

from bench.ssm_scopes import SSD, scope_ms


def read(ctx: Dict) -> Optional[float]:
    return scope_ms(ctx, SSD)
