"""Share of its roofline that the SSD scan reached in a train step
(percent): the least time the chip could take for the scan's operations
and bytes (``counts/ssm.py``: forward and backward of the chunked
algorithm, at the program's chunk, nothing recomputed), the larger of
operations over the bf16 peak and bytes over the HBM bandwidth, over the
scan's device time a step (``ssd_ms.train8k``)."""
from __future__ import annotations

from typing import Dict, Optional

from bench.drivers.train import Run
from bench.metrics.common import counts
from bench.scopes import train_cell
from bench.ssm_scopes import SSD, scope_ms


def read(ctx: Dict) -> Optional[float]:
    ms = scope_ms(ctx, SSD)
    cell = train_cell(ctx)
    if ms is None or cell is None:
        return None
    chunk = Run(cell, 0, 0.0, []).program_config().ssm_chunk
    c, model, seq = counts(ctx), ctx["model"], ctx["seq_len"]
    rows = cell["global_batch"] / ctx["chips"]
    least = max(c.ssd_ops(model, seq, chunk) * rows
                / ctx["peaks"]["bf16_flops"],
                c.ssd_bytes(model, seq) * rows
                / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / (ms / 1e3)
