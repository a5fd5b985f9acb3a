"""Device time of one decode step of the serve engine (ms): the device
time of the program ``jit_decode_step`` in the trace over its runs."""
from __future__ import annotations

from typing import Dict, Optional

#: The engine's decode program's name in the trace.
PROGRAM = "jit_decode_step"


def read(ctx: Dict) -> Optional[float]:
    seconds, runs = ctx["trace"].module_time(PROGRAM)
    if not runs or seconds <= 0:
        return None
    return 1e3 * seconds / runs
