"""Model FLOP/s utilization of training (percent): the operations the
forward and backward passes need per token (recomputation not counted),
times the untraced steps' tokens per second, over chips times the
device's bf16 peak."""
from __future__ import annotations

from typing import Dict, Optional

from bench.metrics.common import counts


def read(ctx: Dict) -> Optional[float]:
    tok_s = ctx["train_tok_s"]
    if not tok_s:
        return None
    ops = counts(ctx).train_ops_per_token(ctx["model"], ctx["seq_len"])
    return 100.0 * ops * tok_s / (ctx["chips"] * ctx["peaks"]["bf16_flops"])
