"""Device time of a train step in the Mamba-2 mixer outside its SSD scan
(ms a step): the input projections, the causal convolution, dt, the
gated norm and the output projection, forward, recompute and backward.
The leaf operations of ``jit_train_step`` whose HLO ``op_name`` carries
the program's ``mixer`` scope and not its ``ssd`` scope, over the traced
steps (bench/ssm_scopes.py)."""
from __future__ import annotations

from typing import Dict, Optional

from bench.ssm_scopes import MIXER, scope_ms


def read(ctx: Dict) -> Optional[float]:
    return scope_ms(ctx, MIXER)
