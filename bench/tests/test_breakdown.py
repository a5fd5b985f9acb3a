"""The breakdown tool's reductions (bench/breakdown.py): idle gaps named
by the innermost span, and the serve engine's step records summed up."""
from types import SimpleNamespace

import pytest

from bench.breakdown import (device_lag, innermost, innermost_gaps,
                             step_summary, top_by_scope, traced_stretch)
from bench.trace import Device, Trace

#: One engine step: admission 10-40 (prefill 10-30, splice 30-40), then
#: the decode 40-100 (upload 40-50, decode_wait 50-80, sample 80-100).
SPANS = {"engine.step": [(0, 100)], "engine.admit": [(10, 40)],
         "engine.prefill": [(10, 30)], "engine.splice": [(30, 40)],
         "engine.decode": [(40, 100)], "engine.upload": [(40, 50)],
         "engine.decode_wait": [(50, 80)], "engine.sample": [(80, 100)],
         "wait_arrival": [(100, 200)]}


@pytest.mark.parametrize("gap,label", [
    ((82, 98), "engine.sample"),          # inside the sample alone
    ((41, 49), "engine.upload"),
    ((32, 38), "engine.splice"),
    ((75, 85), "engine.decode"),          # half wait, half sample
    ((35, 45), "engine.step"),            # half admission, half decode
    ((90, 130), "wait_arrival"),          # mostly after the step
    ((300, 310), "other"),
])
def test_a_gap_takes_the_innermost_span_over_half_of_it(gap, label):
    assert innermost(SPANS, *gap) == label


def test_gaps_are_the_longest_first_and_named():
    ops = [("jit_decode_step/%f", 50, 80), ("jit_one_row_prefill/%g", 12, 28)]
    t = Trace(window=(0, 200), devices=[Device("/device:TPU:0", ops, [])],
              spans=SPANS)
    assert innermost_gaps(t, 3) == [
        ["wait_arrival", pytest.approx(120e-9)],
        ["engine.admit", pytest.approx(22e-9)],     # 28-40 of 28-50
        ["engine.step", pytest.approx(12e-9)]]      # 0-12
    assert innermost_gaps(Trace((0, 10), [], {})) == []


def test_the_device_lag_moves_the_gaps_onto_the_host_spans():
    """The device's clock reads 6 ns early: its decode ends at 74 where
    the host's fenced wait for it ends at 80."""
    ops = [("jit_decode_step/%f", 44, 74), ("jit__argmax/%g", 76, 78)]
    modules = [("jit_decode_step", 44, 74), ("jit__argmax", 76, 78)]
    t = Trace(window=(0, 100), devices=[Device("/device:TPU:0", ops,
                                               modules)], spans=SPANS)
    lag = device_lag(t, "jit_decode_step", "engine.decode_wait")
    assert lag == pytest.approx(6e-9)
    # gaps 0-44, 78-100 and 74-76: the last, read on the device's clock,
    # falls in the wait for the decode that had ended
    assert [g[0] for g in innermost_gaps(t, 3)] == [
        "engine.admit", "engine.sample", "engine.decode_wait"]
    assert [g[0] for g in innermost_gaps(t, 3, lag=lag)] == [
        "engine.admit", "engine.sample", "engine.sample"]
    assert device_lag(t, "jit_decode_step", "engine.nothing") == 0.0


def _rec(start, end, admitted=(), live=2, prompt=0, padded=0, **spans):
    """A step record as the serve engine keeps it."""
    return SimpleNamespace(start=start, end=end, seconds=end - start,
                           admitted=list(admitted), live=live,
                           prompt_tokens=prompt, padded_tokens=padded,
                           spans={k.replace("_", ".", 1): v
                                  for k, v in spans.items()})


def test_step_summary_parts_steps_by_kind_and_by_the_traced_stretch():
    recs = [_rec(0.0, 0.5, live=0),                     # idle: left out
            _rec(1.0, 1.1, engine_decode=0.09),
            _rec(1.2, 1.32, engine_decode=0.1),
            _rec(1.4, 1.6, [3], prompt=100, padded=128,
                 engine_admit=0.08, engine_decode=0.1),
            _rec(2.0, 2.1, engine_decode=0.09),
            _rec(2.2, 2.5, [4, 5], prompt=300, padded=384,
                 engine_admit=0.2, engine_decode=0.1),
            _rec(9.0, 9.1)]                             # after the window
    s = step_summary(recs, 0.0, 5.0, (1.9, 2.6))
    assert s["steps"] == 5 and s["admissions"] == 3
    assert s["decode_step_ms_median.untraced"] == pytest.approx(110)
    assert s["decode_step_ms_median.traced"] == pytest.approx(100)
    assert s["admit_steps.traced"] == 1 and s["admit_steps.untraced"] == 1
    assert s["admit_span_ms_mean"]["engine.admit"] == pytest.approx(140)
    assert s["admit_span_ms_mean"]["step"] == pytest.approx(250)
    # mean admitting step 250, less the median decode alone 100
    assert s["admit_stall_ms"] == pytest.approx(150)
    assert s["prompt_token_share"] == pytest.approx(400 / 512)
    s = step_summary(recs, 0.0, 5.0, None)
    assert s["decode_steps.untraced"] == 3 and "decode_steps.traced" not in s


def test_the_traced_stretch_is_the_traced_steps_from_the_first_after_its_start():
    """The profiler starts before the first step at or after its start
    and holds as many steps as the trace does."""
    recs = [_rec(0.0, 0.1), _rec(0.15, 0.3), _rec(0.5, 0.6), _rec(0.6, 0.7),
            _rec(0.8, 0.9), _rec(0.9, 1.0)]
    assert traced_stretch(recs, 0.2, 3) == (0.5, 0.9)
    assert traced_stretch(recs, 0.2, 0) is None
    assert traced_stretch(recs, 2.0, 3) is None


def test_top_operations_by_scope():
    ops = [("jit_p/%a", 0, 10), ("jit_p/%b", 10, 40), ("jit_p/%a", 50, 60),
           ("jit_p/%w", 0, 60), ("jit_q/%a", 60, 90)]
    t = Trace(window=(0, 100), devices=[Device("/device:TPU:0", ops, [])],
              spans={})
    smap = {"%a": "mlp", "%b": "mlp", "%w": None}
    assert top_by_scope(t, "jit_p", smap) == {"mlp": [
        ["jit_p/%b", pytest.approx(30e-9)], ["jit_p/%a", pytest.approx(20e-9)]]}
