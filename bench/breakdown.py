"""Where a cell's time goes, from one traced run: what PERF.md's section 5
is written from.  Run on the chip after a change that moves the time.

    python3 bench/breakdown.py --workload internlm2-serve-chat --seed 7 \
        --seconds 51

It sets the cell up and runs its window with the traced stretch, as
``bench/run.py --trace 1`` does, skips the reference check, and prints
one JSON line:

* ``end_to_end``, ``per_layer``: the cell's metrics, the per-layer ones
  by the same readers as the result line;
* ``busy_s``, ``window_s``: the device over the traced stretch;
* ``idle_gaps``: the longest gaps between operations on the first
  device, each named by the innermost span that covers more than half of
  it, among the benchmark's spans and the serve engine's (``engine.*``);
* ``scopes``: the device seconds of the cell's main program
  (``jit_decode_step``, ``jit_train_step``) by named scope, leaf
  operations only (bench/scopes.py), with the program's runs and each
  scope's longest operations;
* serve cells, ``steps``: the engine's step records over the whole
  window, by kind (a decode alone, or with admissions), inside and
  outside the traced stretch, with the admission stall and the admitted
  prompts' share of real tokens in their buckets.

The result line's ``bench/trace.py`` cannot yet read the engine's spans
and records: this runner stands in for it until the harness can.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import statistics
import sys
import tempfile
from typing import Dict, List, Sequence

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# libtpu logs to /tmp/tpu_logs unless told otherwise: write nothing there
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

#: The serve engine's spans (serve/engine.py): ``engine.admit`` holds the
#: next three, ``engine.decode`` the last four.
ENGINE_SPANS = ("engine.admit", "engine.prefill", "engine.first_token",
                "engine.splice", "engine.decode", "engine.upload",
                "engine.decode_wait", "engine.sample", "engine.retire")


def innermost(spans: Dict[str, Sequence], a: int, b: int) -> str:
    """The name of the innermost span that covers more than half of
    [a, b): of those, the one whose intervals there are shortest; else
    the span that covers most of it; else ``other``."""
    from bench.trace import covered

    inner, most = None, (0, "other")
    for name, ivs in spans.items():
        hit = [(x, y) for x, y in ivs if x < b and y > a]
        c = covered(hit, a, b)
        if 2 * c > b - a:
            extent = sum(y - x for x, y in hit)
            if inner is None or extent < inner[0]:
                inner = (extent, name)
        if c > most[0]:
            most = (c, name)
    return inner[1] if inner else most[1]


def device_lag(trace, program: str, span: str) -> float:
    """Seconds by which the device's clock in the trace runs behind the
    host's, bounded by causality: the host's fenced wait (``span``) for a
    run of ``program`` cannot end before the run does.  The least such
    margin over the runs: 0 where either is missing.  On a TPU v5e the
    device's events read up to a millisecond or so early."""
    if not trace.devices or span not in trace.spans:
        return 0.0
    waits = trace.spans[span]
    margins = []
    for name, a, b in trace.devices[0].modules:
        if name == program:
            ends = [y for x, y in waits if x < b and y > a]
            if ends:
                margins.append(min(ends, key=lambda y: abs(y - b)) - b)
    return min(margins) / 1e9 if margins else 0.0


def innermost_gaps(trace, n: int = 10, lag: float = 0.0) -> List[List]:
    """The ``n`` longest gaps between operations on the first device in
    the traced window, each named by :func:`innermost` once the device's
    times are moved ``lag`` seconds later (see :func:`device_lag`)."""
    if not trace.devices:
        return []
    lo, hi = trace.window
    busy = trace.devices[0].busy()
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = sorted(((min(b, hi) - max(a, lo), max(a, lo), min(b, hi))
                   for a, b in zip(edges[0::2], edges[1::2])
                   if min(b, hi) > max(a, lo)), reverse=True)
    shift = round(lag * 1e9)
    return [[innermost(trace.spans, a + shift, b + shift), length / 1e9]
            for length, a, b in gaps[:n]]


def traced_stretch(records, at: float, steps: int):
    """The serve driver's traced stretch on the host's clock, from the
    engine's step records: the driver starts the profiler on the
    window's first pass at or after ``at``, before that pass's step, and
    stops it between two steps, so the ``steps`` steps that the trace
    holds are those from the first that starts at or after ``at``.
    None where there are none."""
    recs = [r for r in records if r.start >= at][:steps]
    return (recs[0].start, recs[-1].end) if recs else None


def step_summary(records, t0: float, t_close: float, traced) -> Dict:
    """The engine's step records of the window: the admission stall (the
    mean step that decoded and admitted, less the median step that
    decoded alone), the admitted prompts' real tokens over the tokens
    their buckets padded them to, the median step of each kind inside
    and outside the traced stretch ``traced`` (host clock; None for no
    split), and each span's mean milliseconds by kind of step."""
    win = [r for r in records if r.live and t0 <= r.start and r.end <= t_close]
    kinds = {"decode": [r for r in win if not r.admitted],
             "admit": [r for r in win if r.admitted]}

    def on(r) -> bool:
        return traced is not None and traced[0] <= r.start \
            and r.end <= traced[1]

    out: Dict = {"steps": len(win),
                 "admissions": sum(len(r.admitted) for r in win)}
    padded = sum(r.padded_tokens for r in kinds["admit"])
    if padded:
        out["prompt_token_share"] = sum(
            r.prompt_tokens for r in kinds["admit"]) / padded
    for kind, recs in kinds.items():
        for where, sel in (("traced", [r for r in recs if on(r)]),
                           ("untraced", [r for r in recs if not on(r)])):
            if sel:
                out[f"{kind}_step_ms_median.{where}"] = 1e3 * statistics.median(
                    r.seconds for r in sel)
                out[f"{kind}_steps.{where}"] = len(sel)
        if recs:
            means = {name: 1e3 * sum(r.spans.get(name, 0.0) for r in recs)
                     / len(recs) for name in ENGINE_SPANS
                     if any(name in r.spans for r in recs)}
            means["step"] = 1e3 * statistics.mean(r.seconds for r in recs)
            out[f"{kind}_span_ms_mean"] = means
    if kinds["admit"] and kinds["decode"]:
        out["admit_stall_ms"] = 1e3 * (
            statistics.mean(r.seconds for r in kinds["admit"])
            - statistics.median(r.seconds for r in kinds["decode"]))
    return out


def breakdown(cell: Dict, seed: int, seconds: float, devs,
              log=sys.stderr) -> Dict:
    import importlib

    import jax

    from bench import harness, scopes
    from bench import trace as trace_mod

    _, layer_decl = harness.declared(cell["name"])
    run = importlib.import_module(f"bench.drivers.{cell['kind']}").Run(
        cell, seed, seconds, devs, log=log)
    run.setup()
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    run.window(seconds, trace_dir)
    out: Dict = {"workload": cell["name"], "seed": seed,
                 "end_to_end": run.end_to_end()}
    program, smap, records = scopes.TRAIN_PROGRAM, None, None
    if cell["kind"] == "serve":
        eng = run.engine
        records = list(eng.step_records)
        program = "jit_decode_step"
        toks = jax.numpy.zeros((eng.cfg.max_batch, 1), jax.numpy.int32)
        smap = scopes.compiled_scopes(
            lambda: eng._decode.lower(eng.params, toks, eng.cache))
        del eng
    run.release()
    gc.collect()
    tr = trace_mod.load(trace_dir, [d.id for d in devs],
                        trace_mod.HOST_SPANS + ENGINE_SPANS)
    shutil.rmtree(trace_dir, ignore_errors=True)
    if records is not None:
        traced = traced_stretch(records, run.t0 + cell["trace"]["start_s"],
                                len(tr.spans.get("engine.step", ())))
        out["steps"] = step_summary(records, run.t0, run.t_close, traced)
    ctx = run.context(tr)
    out["per_layer"] = {m["name"]: harness.reader(m["name"])(ctx)
                        for m in layer_decl}
    lag = device_lag(tr, program, "engine.decode_wait")
    out.update(busy_s=tr.busy_s(), window_s=tr.window_s, device_lag_s=lag,
               idle_gaps=innermost_gaps(tr, lag=lag))
    if cell["kind"] == "train":
        smap = scopes.train_scopes(ctx) or scopes.compiled_scopes(
            lambda: scopes.train_step_lowered(cell, devs))
    seconds, runs = tr.module_time(program)
    out["scopes"] = {"program": program, "runs": runs, "module_s": seconds,
                     "seconds": smap and scopes.scope_seconds(tr, program,
                                                              smap),
                     "top": smap and top_by_scope(tr, program, smap)}
    return out


def top_by_scope(trace, program: str, smap, n: int = 4) -> Dict:
    """The ``n`` leaf operations of ``program`` that took most device time
    in each scope, mean over devices."""
    prefix, tot = program + "/", {}
    for d in trace.devices:
        for name, a, b in d.ops:
            scope = smap.get(name[len(prefix):]) \
                if name.startswith(prefix) else None
            if scope is not None:
                tot.setdefault(scope, {}).setdefault(name, 0)
                tot[scope][name] += b - a
    k = max(len(trace.devices), 1)
    return {scope: [[name, t / k / 1e9] for name, t in sorted(
        ops.items(), key=lambda kv: -kv[1])[:n]] for scope, ops in tot.items()}


def main() -> int:
    from bench.harness import configure_jax, devices, load_cell

    ap = argparse.ArgumentParser(prog="bench/breakdown.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    cell = load_cell(args.workload)
    devs = devices(cell["chips"])
    configure_jax()
    print(json.dumps(breakdown(cell, args.seed, args.seconds, devs)),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
