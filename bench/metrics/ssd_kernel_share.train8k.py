"""Share of the SSD scan's device time that its Pallas kernels took in a
train step (percent): of the leaf operations of the traced
``jit_train_step`` under the program's ``ssd`` scope (bench/ssm_scopes.py),
the time of those that are Mosaic kernels (``tpu_custom_call`` in the
compiled step).  None where no kernel ran in the scan, as in a program
whose scan is XLA's alone."""
from __future__ import annotations

import re
from typing import Dict, Optional, Set

from bench import scopes as base
from bench.ssm_scopes import SSD, train_scopes

_MOSAIC = 'custom_call_target="tpu_custom_call"'


def kernels(hlo_text: str) -> Set[str]:
    """The Mosaic kernels of a compiled program's text, named as the
    trace names them (``%ssd_fwd.1``)."""
    out = set()
    for line in hlo_text.splitlines():
        m = base._INSTRUCTION.match(line)
        if m and _MOSAIC in line:
            out.add(m.group(1))
    return out


def share(trace, smap: Dict[str, Optional[str]], mosaic: Set[str]
          ) -> Optional[float]:
    """Percent of the ``ssd`` leaf operations' device time in ``mosaic``;
    None where none of it is."""
    prefix = base.TRAIN_PROGRAM + "/"
    scan = kernel = 0
    for d in trace.devices:
        for name, a, b in d.ops:
            instr = name[len(prefix):]
            if name.startswith(prefix) and smap.get(instr) == SSD:
                scan += b - a
                kernel += (b - a) * (instr in mosaic)
    return 100.0 * kernel / scan if kernel else None


def read(ctx: Dict) -> Optional[float]:
    from bench.harness import devices

    smap = train_scopes(ctx)
    if not smap or SSD not in smap.values():
        return None
    # the step the run compiled, from the compilation cache: its
    # instructions and their names are those of the traced program
    text = base.train_step_lowered(
        base.train_cell(ctx), devices(ctx["chips"])).compile().as_text()
    mosaic = kernels(text)
    return share(ctx["trace"], smap, mosaic) if mosaic else None
