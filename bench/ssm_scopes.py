"""Device time of the Mamba-2 mixer and its SSD scan, by the program's
named scopes ``mixer`` and ``ssd`` (``models/layers.py``).

The same reduction as ``bench/scopes.py``, which maps each operation of
the traced train step to the first of its own scopes on the HLO
``op_name`` path: here an operation counts as ``ssd`` when that scope is
on its path, as ``mixer`` when ``mixer`` is and ``ssd`` is not (the
projections, convolution, gate, norm and output projection), and as
neither otherwise.  The step is rebuilt and compiled as there, afresh
when the cached executable lacks the names.
"""
from __future__ import annotations

import re
import sys
import time
from typing import Dict, Optional

from bench import scopes as base

MIXER, SSD = "mixer", "ssd"


def scope_of(op_name: str) -> str:
    """``ssd``, ``mixer`` or ``base.UNSCOPED`` for an ``op_name`` path,
    unwrapping ``jvp(...)`` and ``transpose(...)``."""
    names = {base._WRAPPER.sub("", part)
             for part in re.split(r"[/;]", op_name)}
    for scope in (SSD, MIXER):
        if scope in names:
            return scope
    return base.UNSCOPED


def scope_map(hlo_text: str) -> Dict[str, Optional[str]]:
    """Each instruction of a compiled program's text mapped to ``ssd``,
    ``mixer`` or unscoped; containers map to None (not counted)."""
    out: Dict[str, Optional[str]] = {}
    for line in hlo_text.splitlines():
        m = base._INSTRUCTION.match(line)
        if not m:
            continue
        if m.group(2) in base.CONTAINERS:
            out[m.group(1)] = None
            continue
        op = base._OP_NAME.search(line)
        out[m.group(1)] = scope_of(op.group(1)) if op else base.UNSCOPED
    return out


def compiled_scopes(lower) -> Optional[Dict[str, Optional[str]]]:
    """The map of a program's compiled text; ``lower`` gives a fresh
    ``Lowered`` of it.  None when its lowering has neither scope (a
    program without the marks)."""
    lowered = lower()
    # the name stacks among the locations, not files (as bench/scopes.py)
    paths = [name for name in re.findall(
        r'loc\("([^"]*)"', lowered.as_text(debug_info=True))
        if "/" in name and not name.startswith("/")]
    if not {scope_of(name) for name in paths} - {base.UNSCOPED}:
        return None
    smap = scope_map(lowered.compile().as_text())
    if MIXER not in smap.values():
        with base.compilation_cache_off():
            smap = scope_map(lower().compile().as_text())
    return smap


def train_scopes(ctx: Dict) -> Optional[Dict[str, Optional[str]]]:
    """The map of the train step the context's run traced, built once per
    context; None where the program has no such scopes, or there is no
    accelerator (a CPU test)."""
    if "ssm_scopes" in ctx:
        return ctx["ssm_scopes"]
    from bench.harness import NoDevice, devices

    ctx["ssm_scopes"] = None
    cell = base.train_cell(ctx)
    try:
        devs = devices(ctx["chips"])
    except NoDevice:
        return None
    if cell is not None:
        t = time.perf_counter()
        ctx["ssm_scopes"] = compiled_scopes(
            lambda: base.train_step_lowered(cell, devs))
        print(f"bench: the train step's mixer map built in "
              f"{time.perf_counter() - t:.1f} s", file=sys.stderr)
    return ctx["ssm_scopes"]


def scope_ms(ctx: Dict, scope: str) -> Optional[float]:
    """Device milliseconds a traced train step spent in ``scope``'s leaf
    operations; None where the program has no such scope."""
    if "ssm_scope_ms" not in ctx:
        ctx["ssm_scope_ms"], smap = {}, train_scopes(ctx)
        secs = smap and base.scope_seconds(ctx["trace"], base.TRAIN_PROGRAM,
                                           smap)
        _, runs = ctx["trace"].module_time(base.TRAIN_PROGRAM)
        if smap and secs is None:
            print("bench: the rebuilt train step's instructions are not "
                  "those of the traced program; its mixer metrics are left "
                  "out", file=sys.stderr)
        elif secs and runs:
            ctx["ssm_scope_ms"] = {k: 1e3 * v / runs
                                   for k, v in secs.items()}
    ms = ctx["ssm_scope_ms"].get(scope, 0.0)
    return ms if ms > 0.0 else None
