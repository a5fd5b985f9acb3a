"""Share of the bf16 peak that prefill reached (percent): the operations
the prompts admitted in the traced window need, counted on their real
tokens and not the bucket's padding, over the device time of the prefill
programs in the trace."""
from __future__ import annotations

from typing import Dict, Optional

from bench.metrics.common import counts

#: The prefill program's name in the trace.
PROGRAM = "jit_one_row_prefill"


def read(ctx: Dict) -> Optional[float]:
    prompts = ctx["traced_prefill"]
    seconds, runs = ctx["trace"].module_time(PROGRAM)
    if not prompts or not runs or seconds <= 0:
        return None
    c = counts(ctx)
    ops = sum(c.prefill_ops(ctx["model"], n) for n in prompts)
    # the trace can cut a prefill at either end: take the mean of each
    per_run = seconds / runs
    return 100.0 * (ops / len(prompts)) / per_run / ctx["peaks"]["bf16_flops"]
