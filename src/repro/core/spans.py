"""Host spans on the profiler's clock: the one way the program marks its
own phases.

``span(name, record, **meta)`` opens a ``jax.profiler.TraceAnnotation``,
so that when a profiler is tracing, the phase lands in its trace on the
same clock as the device's operations, with ``meta`` attached; given a
``record`` dict, it also adds the phase's host seconds to
``record[name]``.  It costs a TraceMe and two clock reads whether or not
a profiler runs, and writes nothing anywhere else.
"""
from __future__ import annotations

import time
from typing import Dict, Optional

import jax


class span:
    """Context manager for one phase; see the module's docstring."""

    __slots__ = ("name", "record", "_annotation", "_t0")

    def __init__(self, name: str, record: Optional[Dict[str, float]] = None,
                 **meta):
        self.name, self.record = name, record
        self._annotation = jax.profiler.TraceAnnotation(name, **meta)

    def __enter__(self) -> "span":
        self._t0 = time.perf_counter()
        self._annotation.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        self._annotation.__exit__(*exc)
        if self.record is not None:
            self.record[self.name] = (self.record.get(self.name, 0.0)
                                      + time.perf_counter() - self._t0)
