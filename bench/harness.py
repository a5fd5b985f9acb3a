"""Run one cell once: find its files by name, check the devices, set up,
measure, check the result against the reference, print the result line.

Everything about a cell lives in files of its own, found by name:

  * ``bench/workloads/<cell>.json``   its configuration, traffic, kind,
    chips, the rate or batch it runs at, and its limits;
  * ``bench/configs/<config>.json``   the model's published sizes, as run;
  * ``bench/traffic/<traffic>.json``  the traffic mix's parameters;
  * ``bench/drivers/<kind>.py``       the driver of that kind of cell;
  * ``bench/metrics/<metric>.py``     the reader of one per-layer metric.

``BENCHMARK.json`` says which end-to-end and per-layer metrics a cell
reports; a driver measures, a reader reduces, and nothing here names a
cell.
"""
from __future__ import annotations

import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class NoDevice(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_json(*parts: str) -> Dict:
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def load_cell(name: str) -> Dict:
    """The workload file with its configuration and traffic mix inlined
    (under ``config_file`` and ``mix``)."""
    cell = load_json("workloads", f"{name}.json")
    cell["name"] = name
    cell["config_file"] = load_json("configs", f"{cell['config']}.json")
    cell["mix"] = load_json("traffic", f"{cell['traffic']}.json")
    return cell


def declared(cell: str, benchmark: Optional[Dict] = None):
    """(end-to-end, per-layer) metric entries of BENCHMARK.json that this
    cell reports."""
    if benchmark is None:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            benchmark = json.load(f)
    e2e = [m for m in benchmark["end_to_end"]
           if cell in m.get("workloads", [cell])]
    names = {m["name"] for m in e2e}
    layer = [m for m in benchmark["per_layer"]
             if (cell in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return e2e, layer


def reader(metric: str):
    """``bench/metrics/<metric>.py``'s ``read(ctx)``."""
    path = os.path.join(BENCH, "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench.metrics.{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def devices(chips: int):
    """The first ``chips`` accelerator devices; raises NoDevice."""
    import jax

    devs = jax.devices()
    if devs[0].platform not in ("tpu", "gpu"):
        raise NoDevice(f"JAX found no accelerator (platform "
                       f"{devs[0].platform!r})")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX found "
                       f"{len(devs)}")
    return devs[:chips]


def configure_jax() -> None:
    """Every compiled program, however quick to compile, goes to the
    persistent cache, so a second run in a checkout compiles nothing.
    The cache is where the program keeps it: ``JAX_COMPILATION_CACHE_DIR``
    if set, else ``<checkout>/.jax_cache``."""
    import jax

    import repro  # noqa: F401  (sets the cache directory)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts the programs compiled while it is on."""

    def __init__(self):
        import jax

        self.on, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, _secs: float, **_kw) -> None:
        if self.on and event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


class FullCollections:
    """Counts the full garbage collections while it is on, and the
    longest."""

    def __init__(self):
        self.on, self.count, self.longest, self._t = False, 0, 0.0, None
        gc.callbacks.append(self._event)

    def _event(self, phase: str, info: Dict) -> None:
        if not self.on or info["generation"] < 2:
            return
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.count += 1
            self.longest = max(self.longest, time.perf_counter() - self._t)


def peak_bytes(devs) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_cell(cell: Dict, seed: int, seconds: float, trace: bool, devs,
             t_start: float, benchmark: Optional[Dict] = None,
             log=sys.stderr) -> Dict:
    """Set up, measure and check one cell; returns the result object."""
    e2e_decl, layer_decl = declared(cell["name"], benchmark)
    driver = importlib.import_module(f"bench.drivers.{cell['kind']}")
    run = driver.Run(cell, seed, seconds, devs, log=log)
    compiles = CompileCounter()
    collections = FullCollections()
    run.setup()
    setup_s = time.perf_counter() - t_start

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    compiles.on = collections.on = True
    run.window(seconds, trace_dir)
    compiles.on = collections.on = False
    if compiles.count:
        print(f"bench: {compiles.count} program(s) compiled inside the "
              f"window", file=log)
    print(f"bench: {collections.count} full garbage collection(s) in the "
          f"window, the longest {1e3 * collections.longest:.1f} ms", file=log)
    peak = peak_bytes(devs)
    measured = run.end_to_end()
    measured["setup_s"] = setup_s
    run.release()
    gc.collect()

    t_check = time.perf_counter()
    checks = run.check()
    print(f"bench: set-up {setup_s:.1f} s, reference check "
          f"{time.perf_counter() - t_check:.1f} s", file=log)
    correct = bool(checks) and all(c["value"] <= c["limit"]
                                   for c in checks.values())

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": run.attempted,
           "failed": run.failed}
    breakdown = None
    if trace:
        from bench import trace as trace_mod

        tr = trace_mod.load(trace_dir, [d.id for d in devs])
        shutil.rmtree(trace_dir, ignore_errors=True)
        device.update(busy_s=tr.busy_s(), window_s=tr.window_s)
        ctx = run.context(tr)
        metrics = {}
        for m in layer_decl:
            value = reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = {"device_ops": tr.top_ops(10),
                     "idle_gaps": tr.idle_gaps(10)}
    else:
        metrics = {m["name"]: {"value": measured[m["name"]],
                               "unit": m["unit"]}
                   for m in e2e_decl if m["name"] in measured}
    out.update(metrics=metrics, device=device)
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def main(argv: Optional[List[str]] = None, t_start: Optional[float] = None
         ) -> int:
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(
        prog="bench/run.py",
        description="Run one benchmark cell once; the last line of "
                    "standard output is the result.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = load_cell(args.workload)
    try:
        devs = devices(cell["chips"])
    except NoDevice as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 2
    configure_jax()
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), devs,
                   t_start)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0
