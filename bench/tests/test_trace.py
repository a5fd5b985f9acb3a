"""The reduction from a profiler trace to the per-layer metrics: on
intervals worked by hand, and on a small trace recorded on a TPU v5e
(the tiny transformer served with the benchmark's spans)."""
import os
from types import SimpleNamespace

import pytest

from bench import trace
from bench.trace import Device, Trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_and_cover():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
    cover = [(0, 3), (5, 9)]
    assert trace.covered(cover, 2, 6) == 2
    assert trace.covered(cover, 3, 5) == 0


def _synthetic():
    ops = [("fusion.1", 10, 30), ("fusion.2", 20, 40), ("copy", 60, 70)]
    modules = [("jit_one_row_prefill", 10, 40), ("jit__lambda_", 60, 70)]
    return Trace(window=(0, 100),
                 devices=[Device("/device:TPU:0", ops, modules)],
                 spans={"engine.step": [(5, 45), (55, 75)],
                        "wait_arrival": [(75, 100)]})


def test_busy_idle_and_programs():
    t = _synthetic()
    assert t.window_s == pytest.approx(100e-9)
    assert t.busy_s() == pytest.approx(40e-9)       # 10-40 and 60-70
    assert t.idle_share() == pytest.approx(0.6)
    assert t.module_time("one_row_prefill") == (pytest.approx(30e-9), 1)
    assert t.top_ops(2) == [["fusion.1", pytest.approx(20e-9)],
                            ["fusion.2", pytest.approx(20e-9)]]


def test_idle_gaps_are_named_by_the_host_span():
    gaps = _synthetic().idle_gaps(3)
    assert gaps == [["wait_arrival", pytest.approx(30e-9)],
                    ["engine.step", pytest.approx(20e-9)],
                    ["engine.step", pytest.approx(10e-9)]]


def _plane(name, lines):
    ev = lambda n, a, b: SimpleNamespace(name=n, start_ns=a,  # noqa: E731
                                         duration_ns=b - a)
    return SimpleNamespace(name=name, lines=[
        SimpleNamespace(name=ln, events=[ev(*e) for e in events])
        for ln, events in lines.items()])


def test_only_the_cells_devices_count():
    """A host with a chip more than the cell uses: its idle plane is left
    out, so it neither halves the busy time nor doubles the idle share."""
    planes = [
        _plane("/host:CPU", {"python": [("bench.window", 0, 100),
                                        ("engine.step", 5, 45)]}),
        _plane("/device:TPU:0", {
            "XLA Modules": [("jit_one_row_prefill(123)", 10, 40)],
            "XLA Ops": [("fusion.1 = f32[] fusion()", 10, 30),
                        ("fusion.2", 20, 40)]}),
        _plane("/device:TPU:1", {"XLA Modules": [], "XLA Ops": []}),
    ]
    t = trace.reduce(planes, device_ids=[0])
    assert [d.name for d in t.devices] == ["/device:TPU:0"]
    assert t.busy_s() == pytest.approx(30e-9)
    assert t.idle_share() == pytest.approx(0.7)
    assert t.module_time("one_row_prefill") == (pytest.approx(30e-9), 1)
    assert t.top_ops(1) == [["jit_one_row_prefill/fusion.1",
                             pytest.approx(20e-9)]]
    both = trace.reduce(planes)
    assert len(both.devices) == 2
    assert both.idle_share() == pytest.approx(0.85)


def test_no_device_reads_nothing():
    t = Trace(window=(0, 10), devices=[], spans={})
    assert t.idle_share() is None and t.busy_s() == 0.0
    assert t.idle_gaps() == [] and t.top_ops() == []


RECORDED = os.path.join(DATA, "tiny-serve.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    """0.25 s of the tiny transformer's engine under open-loop traffic,
    traced on one TPU v5e with the benchmark's spans."""
    return trace.load(RECORDED, device_ids=[0])


def _busy_by_sweep(ops, lo, hi):
    """Busy nanoseconds in [lo, hi) by a sweep over +1/-1 edges: a second
    way to the union."""
    inside = [(max(a, lo), min(b, hi)) for _, a, b in ops
              if b > lo and a < hi]
    # at a shared instant an interval opens before another closes
    edges = sorted([(a, 1) for a, _ in inside] + [(b, -1) for _, b in inside],
                   key=lambda e: (e[0], -e[1]))
    busy, depth, since = 0, 0, None
    for t, step in edges:
        if depth == 0 and step == 1:
            since = t
        depth += step
        if depth == 0:
            busy += t - since
    return busy


def test_recorded_trace(recorded):
    t = recorded
    assert [d.name for d in t.devices] == ["/device:TPU:0"]
    assert t.window_s == pytest.approx(0.25, abs=0.01)
    ops = t.devices[0].ops
    assert len(ops) == 6622
    assert t.busy_s() * 1e9 == pytest.approx(
        _busy_by_sweep(ops, *t.window), abs=1)
    assert 0.9 < t.idle_share() < 1.0
    # every decode and prefill program the engine ran in the window
    assert t.module_time("jit__lambda")[1] == 24
    assert t.module_time("jit_one_row_prefill")[1] == 12
    assert len(t.spans["engine.step"]) == 24
    assert all(name.split("/")[0] != "?" for name, _ in t.top_ops(10))
    labels = {label for label, _ in t.idle_gaps(10)}
    assert labels <= {"engine.step", "wait_arrival", "submit", "other"}
