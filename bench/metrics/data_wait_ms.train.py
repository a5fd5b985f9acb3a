"""Mean time a train step waited for its batch from the data pipeline
(ms): the benchmark's span around ``next(pipeline)``."""
from __future__ import annotations

from typing import Dict, Optional


def read(ctx: Dict) -> Optional[float]:
    waits = ctx["data_wait_s"]
    return 1e3 * sum(waits) / len(waits) if waits else None
