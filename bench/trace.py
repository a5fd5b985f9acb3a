"""Reduce a profiler trace to what the per-layer metrics read.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes, read with
``jax.profiler.ProfileData``.  Device planes are those named
``/device:<platform>:<n>``; on each, the line ``XLA Ops`` holds one event
per operation run and ``XLA Modules`` one per program run.  The host's
spans are the benchmark's own ``TraceAnnotation`` events, found by name
on any host line.  All times are nanoseconds on the one clock of the
trace.

Everything is clipped to the traced window: the extent of the
benchmark's ``bench.window`` span.  A program is named as the trace names
its module, without the hash (``jit_one_row_prefill``); an operation by
its program and its HLO instruction (``jit__lambda/%while.3``).
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
#: The benchmark's host spans, which label the device's idle gaps.
HOST_SPANS = ("submit", "engine.step", "wait_arrival", "next_batch",
              "train_step", "loss_fetch")

_DEVICE = re.compile(r"^/device:(TPU|GPU):(\d+)$")

Interval = Tuple[int, int]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint cover of the intervals."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def covered(cover: Sequence[Interval], a: int, b: int) -> int:
    """Nanoseconds of [a, b) that the disjoint ``cover`` holds."""
    return sum(max(0, min(b, y) - max(a, x)) for x, y in cover)


@dataclass
class Device:
    name: str
    ops: List[Tuple[str, int, int]] = field(default_factory=list)
    modules: List[Tuple[str, int, int]] = field(default_factory=list)

    def busy(self) -> List[Interval]:
        return union((a, b) for _, a, b in self.ops)


@dataclass
class Trace:
    """One traced window: its extent, the devices' events within it, and
    the host's spans."""
    window: Interval
    devices: List[Device]
    spans: Dict[str, List[Interval]]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_s(self) -> float:
        """Seconds in which some operation ran, mean over devices."""
        if not self.devices:
            return 0.0
        return sum(covered(d.busy(), *self.window)
                   for d in self.devices) / len(self.devices) / 1e9

    def idle_share(self) -> Optional[float]:
        """Share of the window with no operation running, mean over
        devices; None when the trace holds no device."""
        if not self.devices or self.window_s <= 0:
            return None
        return 1.0 - self.busy_s() / self.window_s

    def module_time(self, pattern: str) -> Tuple[float, int]:
        """(seconds, runs) of the programs whose name contains
        ``pattern``, mean over devices."""
        if not self.devices:
            return 0.0, 0
        t, n = 0, 0
        for d in self.devices:
            for name, a, b in d.modules:
                if pattern in name:
                    t += b - a
                    n += 1
        k = len(self.devices)
        return t / k / 1e9, n // k

    def top_ops(self, n: int = 10) -> List[List]:
        """The operations that took most device time, mean over devices."""
        tot: Dict[str, int] = defaultdict(int)
        for d in self.devices:
            for name, a, b in d.ops:
                tot[name] += b - a
        k = max(len(self.devices), 1)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[name, t / k / 1e9] for name, t in top]

    def idle_gaps(self, n: int = 10) -> List[List]:
        """The longest gaps between operations on the first device, each
        named by the host span that covers most of it."""
        if not self.devices:
            return []
        lo, hi = self.window
        busy = self.devices[0].busy()
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps = []
        for a, b in zip(edges[0::2], edges[1::2]):
            a, b = max(a, lo), min(b, hi)
            if b > a:
                gaps.append((b - a, a, b))
        gaps.sort(reverse=True)
        out = []
        for length, a, b in gaps[:n]:
            best, label = 0, "other"
            for name, ivs in self.spans.items():
                c = covered(ivs, a, b)
                if c > best:
                    best, label = c, name
            out.append([label, length / 1e9])
        return out


def start(log_dir: str) -> None:
    """Start the profiler with the host's Python tracer off: it would
    record every Python call, slow the host that the trace measures and
    swell the file.  The benchmark's ``TraceAnnotation`` spans are kept."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def _module_at(modules: Sequence[Tuple[str, int, int]],
               starts: Sequence[int], t: int) -> str:
    """The program running at ``t`` on a device (``modules`` by start,
    ``starts`` their start times)."""
    i = bisect.bisect_right(starts, t) - 1
    return modules[i][0] if i >= 0 and t < modules[i][2] else "?"


def find_xplane(root: str) -> str:
    files = glob.glob(os.path.join(root, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"want one .xplane.pb under {root}, "
                                f"found {len(files)}")
    return files[0]


def load(path: str, device_ids: Optional[Sequence[int]] = None,
         span_names: Sequence[str] = HOST_SPANS) -> Trace:
    """Read a ``.xplane.pb`` (or the one under a directory), keeping the
    planes of the devices numbered ``device_ids`` (the cell's chips; all
    where None): a chip the cell does not use would count as idle."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    return reduce(ProfileData.from_file(path).planes, device_ids,
                  span_names, path)


def reduce(planes, device_ids: Optional[Sequence[int]] = None,
           span_names: Sequence[str] = HOST_SPANS,
           where: str = "the trace") -> Trace:
    """The :class:`Trace` of a profile's planes (each with a ``name`` and
    ``lines`` of ``events``, as ``ProfileData`` gives them)."""
    devices: List[Device] = []
    spans: Dict[str, List[Interval]] = defaultdict(list)
    window: Optional[Interval] = None
    wanted = set(span_names)
    for plane in planes:
        m = _DEVICE.match(plane.name)
        if m:
            if device_ids is not None and int(m.group(2)) not in device_ids:
                continue
            lines = {line.name: [(e.name, int(e.start_ns),
                                  int(e.start_ns + e.duration_ns))
                                 for e in line.events]
                     for line in plane.lines}
            modules = sorted(((n.split("(", 1)[0], a, b)
                              for n, a, b in lines.get("XLA Modules", ())),
                             key=lambda m: m[1])
            starts = [a for _, a, _ in modules]
            devices.append(Device(plane.name, [
                (f"{_module_at(modules, starts, a)}/{n.split(' = ', 1)[0]}",
                 a, b)
                for n, a, b in lines.get("XLA Ops", ())], modules))
            continue
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == WINDOW_SPAN:
                    window = (int(e.start_ns), int(e.start_ns + e.duration_ns))
                elif e.name in wanted:
                    spans[e.name].append((int(e.start_ns),
                                          int(e.start_ns + e.duration_ns)))
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span in {where}")
    devices.sort(key=lambda d: int(_DEVICE.match(d.name).group(2)))
    return Trace(window, devices,
                 {k: union(v) for k, v in spans.items()})
