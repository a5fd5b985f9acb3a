"""``ssd_kernel_share.train8k``: the share of the SSD scan's device time in
Mosaic kernels, on HLO text and a trace made by hand."""
from bench import harness, ssm_scopes
from bench.trace import Device, Trace

HLO = """\
ENTRY %main (a: f32[8]) -> f32[8] {
  %fusion.1 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop, calls=%f1, metadata={op_name="jit(train_step)/jvp()/while/body/closed_call/checkpoint/rematted_computation/mixer/dot_general"}
  %ssd_fwd.1 = (bf16[8]{0}, f32[8]{0}) custom-call(f32[8]{0} %a), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp()/while/body/checkpoint/mixer/ssd/cond/branch_0_fun/ssd_fwd/pallas_call"}
  %fusion.2 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop, calls=%f2, metadata={op_name="jit(train_step)/jvp()/while/body/checkpoint/mixer/ssd/swapaxes"}
  %ssd_bwd.1 = (bf16[8]{0}, f32[8]{0}) custom-call(f32[8]{0} %a), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/transpose(jvp())/while/body/checkpoint/mixer/ssd/cond/branch_0_fun/ssd_bwd/pallas_call"}
  ROOT %fusion.5 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop, calls=%f5, metadata={op_name="jit(train_step)/transpose(jvp(loss))/dot_general"}
}
"""


def _trace():
    ops = [(f"jit_train_step/{n}", a, b) for n, a, b in (
        ("%fusion.1", 0, 100), ("%ssd_fwd.1", 100, 400),
        ("%fusion.2", 400, 450), ("%ssd_bwd.1", 450, 950),
        ("%fusion.5", 950, 1000))]
    return Trace(window=(0, 1000), devices=[Device(
        "/device:TPU:0", ops, [("jit_train_step", 0, 1000)])], spans={})


def test_kernels_are_the_tpu_custom_calls():
    read = harness.reader("ssd_kernel_share.train8k")
    kernels = read.__globals__["kernels"](HLO)
    assert kernels == {"%ssd_fwd.1", "%ssd_bwd.1"}


def test_share_counts_the_scan_alone():
    read = harness.reader("ssd_kernel_share.train8k")
    share = read.__globals__["share"]
    smap = ssm_scopes.scope_map(HLO)
    # the scan: 300 + 50 + 500; its kernels 800
    assert share(_trace(), smap, {"%ssd_fwd.1", "%ssd_bwd.1"}) == \
        100.0 * 800 / 850
    # a scan of XLA's alone (the parent's) reads nothing
    assert share(_trace(), smap, set()) is None


def test_reads_nothing_without_the_scopes_or_a_chip():
    read = harness.reader("ssd_kernel_share.train8k")
    assert read({"trace": _trace(), "ssm_scopes": None}) is None
    cell = harness.load_cell("mamba2-train-8k")
    ctx = {"trace": _trace(), "model": cell["config_file"],
           "seq_len": cell["mix"]["seq_len"], "chips": 1}
    assert read(ctx) is None                         # no accelerator here
