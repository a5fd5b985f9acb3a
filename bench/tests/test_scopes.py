"""Device time by named layer scope (bench/scopes.py) and the per-layer
readers that read it or the serve engine's programs: on HLO text and
traces made by hand, on the tiny train step compiled here, and on the
trace recorded on a TPU v5e."""
import os

import jax
import pytest

from bench import harness, scopes
from bench.tests import cells
from bench.trace import Device, Trace

HLO = """\
HloModule jit_train_step, is_scheduled=true

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]{0}) parameter(0)
  %fusion.3 = f32[8]{0:T(128)} fusion(f32[8]{0} %gte), kind=kLoop, calls=%fc.3, metadata={op_name="jit(train_step)/jvp()/while/body/closed_call/checkpoint/rematted_computation/attention/dot_general"}
  ROOT %tuple = (s32[], f32[8]{0}) tuple(s32[] %i, f32[8]{0} %fusion.3)
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %while.7 = (s32[]{:T(128)}, f32[8]{0:T(128)S(1)}) while((s32[], f32[8]{0}) %t), condition=%cond, body=%body, metadata={op_name="jit(train_step)/jvp()/while"}
  %while.9 = (s32[], f32[]) while((s32[], f32[]) %u), condition=%c2, body=%b2, metadata={op_name="jit(train_step)/jvp(loss)/while"}
  %convolution.2 = f32[8]{0} convolution(f32[8]{0} %x, f32[8]{0} %y), metadata={op_name="jit(train_step)/transpose(jvp(loss))/dot_general;jit(train_step)/transpose(jvp(loss))/mul"}
  %copy.1 = f32[8]{0} copy(f32[8]{0} %z)
  %add.4 = f32[8]{0} add(f32[8]{0} %z, f32[8]{0} %z), metadata={op_name="jit(train_step)/transpose(jvp())/add_any"}
  ROOT %fusion.5 = f32[8]{0} fusion(f32[8]{0} %add.4), kind=kLoop, calls=%fc.5, metadata={op_name="jit(train_step)/optimizer/mul"}
}
"""


@pytest.mark.parametrize("op_name,scope", [
    ("jit(train_step)/jvp()/while/body/closed_call/attention/dot_general",
     "attention"),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/mlp/dot_general", "mlp"),
    ("jit(train_step)/jvp(loss)/while/body/unembed/dot_general", "loss"),
    ("jit(train_step)/transpose(jvp(loss))/reduce_sum", "loss"),
    ("jit(train_step)/transpose(jvp(embed))/scatter-add", "embed"),
    ("jit(decode_step)/while/body/attention/attention/exp", "attention"),
    ("jit(train_step)/transpose(jvp())/add_any;jit(train_step)/optimizer/x",
     "optimizer"),
    ("jit(train_step)/transpose(jvp())/while/body/rsqrt", scopes.UNSCOPED),
    ("jit(loss_chunks)/mlp_like/add", scopes.UNSCOPED),
])
def test_scope_of_unwraps_autodiff_and_remat(op_name, scope):
    assert scopes.scope_of(op_name) == scope


def test_scope_map_names_instructions_as_the_trace_does():
    smap = scopes.scope_map(HLO)
    assert smap == {
        "%p": scopes.UNSCOPED, "%fusion.3": "attention",
        "%tuple": scopes.UNSCOPED, "%while.7": None, "%while.9": None,
        "%convolution.2": "loss", "%copy.1": scopes.UNSCOPED,
        "%add.4": scopes.UNSCOPED, "%fusion.5": "optimizer"}


def _trace(ops, modules):
    return Trace(window=(0, 1000),
                 devices=[Device("/device:TPU:0", ops, modules)], spans={})


def _train_trace():
    """Two steps of the program above; each while loop listed beside the
    operations nested in it."""
    ops, modules = [], []
    for s in (0, 500):
        modules.append(("jit_train_step", s, s + 400))
        ops += [(f"jit_train_step/{n}", s + a, s + b) for n, a, b in (
            ("%while.7", 0, 100), ("%fusion.3", 10, 60),
            ("%while.9", 100, 300), ("%convolution.2", 110, 280),
            ("%copy.1", 300, 310), ("%fusion.5", 320, 400))]
    ops.append(("jit_other/%fusion.1", 450, 480))
    return _trace(ops, modules)


def test_scope_seconds_counts_leaf_operations_once():
    secs = scopes.scope_seconds(_train_trace(), "jit_train_step",
                                scopes.scope_map(HLO))
    assert secs == {"attention": pytest.approx(100e-9),
                    "loss": pytest.approx(340e-9),
                    scopes.UNSCOPED: pytest.approx(20e-9),
                    "optimizer": pytest.approx(160e-9)}


def test_scope_seconds_refuses_the_map_of_another_program():
    smap = scopes.scope_map(HLO)
    del smap["%copy.1"]
    assert scopes.scope_seconds(_train_trace(), "jit_train_step",
                                smap) is None


def test_train_readers_read_per_step_and_stay_silent_without_scopes():
    ctx = {"trace": _train_trace(), "scopes": scopes.scope_map(HLO)}
    read = lambda m: harness.reader(m)(ctx)  # noqa: E731
    assert read("attention_ms.train") == pytest.approx(50e-6)
    assert read("loss_ms.train") == pytest.approx(170e-6)
    assert read("mlp_ms.train") is None
    # a program without named scopes (the parent commit's) reads nothing
    ctx = {"trace": _train_trace(),
           "scopes": {k: (v and scopes.UNSCOPED)
                      for k, v in scopes.scope_map(HLO).items()}}
    for m in ("attention_ms.train", "mlp_ms.train", "loss_ms.train"):
        assert harness.reader(m)(ctx) is None


def test_train_scopes_on_the_cpu_read_nothing_and_do_not_raise():
    cell = harness.load_cell("internlm2-train-4k")
    ctx = {"trace": _train_trace(), "model": cell["config_file"],
           "seq_len": cell["mix"]["seq_len"], "chips": 1}
    assert scopes.train_cell(ctx)["name"] == "internlm2-train-4k"
    assert scopes.train_cell(dict(ctx, seq_len=123)) is None
    assert scopes.train_scopes(ctx) is None          # no accelerator here
    assert harness.reader("attention_ms.train")(ctx) is None


def test_two_train_cells_alike_are_refused(monkeypatch):
    """A second train cell of the same configuration, sequence length and
    chips leaves the traced one unknown: the reader raises."""
    cell = harness.load_cell("internlm2-train-4k")
    monkeypatch.setattr(harness, "load_cell",
                        lambda name: dict(cell, name=name))
    ctx = {"model": cell["config_file"], "seq_len": cell["mix"]["seq_len"],
           "chips": 1}
    with pytest.raises(ValueError, match="cannot tell"):
        scopes.train_cell(ctx)


def test_the_compiled_train_step_maps_to_the_scopes():
    cell, devs = cells.train_cell(), jax.devices()[:1]
    smap = scopes.compiled_scopes(
        lambda: scopes.train_step_lowered(cell, devs))
    found = set(smap.values())
    assert set(scopes.SCOPES) - {"unembed"} <= found
    assert None in found and scopes.UNSCOPED in found


class _Lowered:
    """A lowered program whose lowering has the attention scope, and
    whose compiled text has it only when compiled with the persistent
    cache off (as after a cache hit on a build without the scopes)."""
    SCOPED = 'loc("jit(f)/attention/dot_general"(#loc1))'
    #: the lowering of a program without scopes that calls a function
    #: named ``mlp``, in a checkout under a directory named ``loss``
    UNSCOPED = ('loc("mlp"(#loc2))\nloc("jit(f)/jit(silu)/mul"(#loc3))\n'
                'loc("/work/loss/src/repro/models/layers.py":540:10)')

    def __init__(self, log, scoped=True):
        self.log, self.scoped = log, scoped

    def as_text(self, debug_info=False):
        assert debug_info
        return self.SCOPED if self.scoped else self.UNSCOPED

    def compile(self):
        cached = jax.config.jax_enable_compilation_cache
        self.log.append(cached)
        meta = "" if cached else \
            ', metadata={op_name="jit(f)/attention/dot_general"}'
        text = f"  %fusion.1 = f32[8]{{0}} fusion(f32[8]{{0}} %a){meta}"
        return type("Compiled", (), {"as_text": lambda _: text})()


def test_a_cached_executable_without_scopes_is_compiled_afresh():
    log = []
    smap = scopes.compiled_scopes(lambda: _Lowered(log))
    assert smap == {"%fusion.1": "attention"}
    assert log == [True, False]
    assert jax.config.jax_enable_compilation_cache


def test_a_program_without_scopes_is_not_compiled():
    log = []
    assert scopes.compiled_scopes(lambda: _Lowered(log, False)) is None
    assert log == []


def _serve_trace():
    """Three decode runs (30, 25 and 30 ns) and a prefill between them."""
    modules = [("jit_decode_step", 10, 40), ("jit_one_row_prefill", 110, 150),
               ("jit_dynamic_update_slice", 160, 170),
               ("jit_decode_step", 170, 195), ("jit_decode_step", 310, 340)]
    return _trace([(f"{n}/%fusion.0", a, b) for n, a, b in modules], modules)


def test_decode_reader_reads_the_decode_program_alone():
    assert harness.reader("decode_ms.chat")({"trace": _serve_trace()}) == \
        pytest.approx((30 + 25 + 30) / 3 * 1e-6)


def test_serve_readers_stay_silent_on_the_parent_program():
    """The trace recorded before the decode program had a name: the new
    reader finds nothing and raises nothing."""
    from bench import trace

    t = trace.load(os.path.join(cells.DATA, "tiny-serve.xplane.pb"),
                   device_ids=[0])
    assert harness.reader("decode_ms.chat")({"trace": t}) is None
