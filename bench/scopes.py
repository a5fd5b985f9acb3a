"""Device time by the program's named layer scopes.

The program marks its layers with ``jax.named_scope`` (``SCOPES``).  The
compiled HLO keeps the scope in the ``op_name`` of every instruction
made inside it, under remat (``.../rematted_computation/attention/...``)
and autodiff (``jvp(loss)``, ``transpose(jvp(loss))``) too, and a
fusion carries the ``op_name`` of its root.  A trace names an operation
by its program and HLO instruction (``jit_train_step/%fusion.458``), so
the program's compiled text (``jitted.lower(...).compile().as_text()``)
maps each operation of the trace to the first scope of ``SCOPES`` on its
``op_name`` path.

The trace lists a container (``while``, ``call``, ``conditional``) and
also the operations nested in it; a container would count its body's
time twice (the chunked loss's own scan carries ``loss`` in its
``op_name``), so only leaf operations are counted.
"""
from __future__ import annotations

import contextlib
import os
import re
import sys
import time
from collections import defaultdict
from typing import Any, Callable, Dict, Optional

#: The program's layer scopes (models/layers.py, models/transformer.py,
#: train/optimizer.py).
SCOPES = ("embed", "attention", "mlp", "unembed", "loss", "optimizer")
#: Leaf operations under none of ``SCOPES``: norms, residual adds, the
#: layer scans' slicing and bookkeeping.
UNSCOPED = "unscoped"
CONTAINERS = ("while", "call", "conditional")
#: The train step's name in the trace.
TRAIN_PROGRAM = "jit_train_step"

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?(%[\w.-]+) = .*? ([a-z][\w-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_WRAPPER = re.compile(r"^(?:[\w-]+\()+|\)+$")


def scope_of(op_name: str) -> str:
    """The first of ``SCOPES`` on an ``op_name`` path, unwrapping
    ``jvp(...)`` and ``transpose(...)``; else ``UNSCOPED``."""
    for part in re.split(r"[/;]", op_name):
        name = _WRAPPER.sub("", part)
        if name in SCOPES:
            return name
    return UNSCOPED


def scope_map(hlo_text: str) -> Dict[str, Optional[str]]:
    """Each instruction of a compiled program's text, named as the trace
    names it (``%fusion.458``), mapped to its scope; containers map to
    None (not counted)."""
    out: Dict[str, Optional[str]] = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        if m.group(2) in CONTAINERS:
            out[m.group(1)] = None
            continue
        op = _OP_NAME.search(line)
        out[m.group(1)] = scope_of(op.group(1)) if op else UNSCOPED
    return out


def scope_seconds(trace, program: str, smap: Dict[str, Optional[str]]
                  ) -> Optional[Dict[str, float]]:
    """Device seconds of ``program``'s leaf operations in the trace by
    scope, mean over devices; None when an operation is not in ``smap``
    (the map is of another program)."""
    prefix = program + "/"
    tot: Dict[str, float] = defaultdict(float)
    for d in trace.devices:
        for name, a, b in d.ops:
            if not name.startswith(prefix):
                continue
            instr = name[len(prefix):]
            if instr not in smap:
                return None
            if smap[instr] is not None:
                tot[smap[instr]] += (b - a) / 1e9
    k = max(len(trace.devices), 1)
    return {s: t / k for s, t in tot.items()}


def train_cell(ctx: Dict) -> Optional[Dict]:
    """The train cell of ``bench/workloads`` that the context's run was
    of, found by what the train driver's context gives: the
    configuration, the sequence length and the chips.  None where no cell
    matches (a test's cell); two that match raise, since the step rebuilt
    from either may not be the one that ran."""
    from bench.harness import BENCH, load_cell

    names = sorted(f[:-len(".json")] for f in os.listdir(
        os.path.join(BENCH, "workloads")) if f.endswith(".json"))
    cells = [c for c in map(load_cell, names)
             if c["kind"] == "train" and c["config_file"] == ctx["model"]
             and c["mix"]["seq_len"] == ctx["seq_len"]
             and c["chips"] == ctx["chips"]]
    if len(cells) > 1:
        raise ValueError(
            "bench/scopes.py cannot tell the traced train cell among "
            f"{[c['name'] for c in cells]}: pass the cell through the train "
            "driver's context()")
    return cells[0] if cells else None


def train_step_lowered(cell: Dict, devs):
    """The cell's train step, built as the train driver builds it and
    lowered from the state's and the batch's shapes."""
    import jax
    import jax.numpy as jnp

    from bench.drivers.train import Run
    from repro.distributed.logical import default_rules, logical_rules
    from repro.launch.mesh import make_mesh
    from repro.launch.train import sharded_train_fns
    from repro.train import AdamWConfig

    cfg = Run(cell, 0, 0.0, devs).program_config()
    shape = tuple(cell.get("mesh", (len(devs), 1)))
    mesh = make_mesh(shape, ("data", "model"), devices=devs)
    structs, _, _, step = sharded_train_fns(
        cfg, AdamWConfig(**cell["optimizer"]), mesh)
    batch = {k: jax.ShapeDtypeStruct(
        (cell["global_batch"], cell["mix"]["seq_len"]), jnp.int32)
        for k in ("tokens", "labels")}
    with mesh, logical_rules(default_rules(cfg, mesh)):
        return step.lower(structs, batch)


@contextlib.contextmanager
def compilation_cache_off():
    """Compile afresh: the persistent compilation cache off, then back."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def compiled_scopes(lower: Callable[[], Any]
                    ) -> Optional[Dict[str, Optional[str]]]:
    """The scope map of a program's compiled text; ``lower`` gives a
    fresh ``Lowered`` of it.  None when the program has no scopes.

    The persistent compilation cache leaves metadata out of its key, so
    it can hand back an executable compiled from another version of the
    source that differs only in metadata (a parent commit without the
    scopes).  Its instructions are the same, so a program whose lowering
    has scopes and whose cached executable has none is compiled afresh
    for their names' scopes."""
    lowered = lower()
    # the name stacks among the locations: not files, not function names
    # (a function may be called ``mlp`` without a scope of that name)
    paths = [name for name in re.findall(
        r'loc\("([^"]*)"', lowered.as_text(debug_info=True))
        if "/" in name and not name.startswith("/")]
    if not {scope_of(name) for name in paths} & set(SCOPES):
        return None
    smap = scope_map(lowered.compile().as_text())
    if not set(smap.values()) & set(SCOPES):
        with compilation_cache_off():
            smap = scope_map(lower().compile().as_text())
    return smap


def train_scopes(ctx: Dict) -> Optional[Dict[str, Optional[str]]]:
    """The scope map of the train step the context's run traced
    (``ctx["scopes"]``), built once per context from the cell's step
    rebuilt and compiled here; None where the program has no scopes or
    there is no accelerator (a CPU test)."""
    if "scopes" in ctx:
        return ctx["scopes"]
    from bench.harness import NoDevice, devices

    ctx["scopes"] = None
    cell = train_cell(ctx)
    try:
        devs = devices(ctx["chips"])
    except NoDevice:
        return None
    if cell is not None:
        t = time.perf_counter()
        ctx["scopes"] = compiled_scopes(lambda: train_step_lowered(cell, devs))
        print(f"bench: the train step's scope map built in "
              f"{time.perf_counter() - t:.1f} s", file=sys.stderr)
    return ctx["scopes"]


def train_scope_ms(ctx: Dict, scope: str) -> Optional[float]:
    """Device milliseconds a traced train step spent in ``scope``'s leaf
    operations; None where the program has no such scope."""
    if "scope_ms" not in ctx:
        ctx["scope_ms"], smap = {}, train_scopes(ctx)
        secs = smap and scope_seconds(ctx["trace"], TRAIN_PROGRAM, smap)
        _, runs = ctx["trace"].module_time(TRAIN_PROGRAM)
        if smap and secs is None:
            print("bench: the rebuilt train step's instructions are not "
                  "those of the traced program; its scope metrics are left "
                  "out", file=sys.stderr)
        elif secs and runs:
            ctx["scope_ms"] = {k: 1e3 * v / runs for k, v in secs.items()}
    ms = ctx["scope_ms"].get(scope, 0.0)
    return ms if ms > 0.0 else None
