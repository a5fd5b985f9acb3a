"""Device time of a train step in the attention sublayer (ms a step):
projections, rope and flash attention, forward, recompute and backward.
The leaf operations of ``jit_train_step`` whose HLO ``op_name`` carries
the program's ``attention`` scope, over the traced steps
(bench/scopes.py)."""
from __future__ import annotations

from typing import Dict, Optional

from bench.scopes import train_scope_ms


def read(ctx: Dict) -> Optional[float]:
    return train_scope_ms(ctx, "attention")
