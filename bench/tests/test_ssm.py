"""The Mamba-2 configuration's parts of the benchmark, on the CPU at small
sizes: the program against the plain float32 reference (the forward pass
exactly, the cell's three train steps within limits that the fp8 control
breaks), the family's mapping of the published keys, the operation and
byte counts against XLA's own, and the mixer's readers on a trace made by
hand."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import calibrate, harness, ssm_scopes
from bench.counts import ssm as counts
from bench.families import ssm as family
from bench.reference import ssm as reference
from bench.reference.common import Frozen, rms_norm, seed_key
from bench.scopes import UNSCOPED
from bench.tests import cells
from bench.trace import Device, Trace

SEED = 2**33 + 7           # above 32 bits, as a run's seed may be
#: The tiny cell's limits: on the CPU, the program in bfloat16 reads at
#: most 1.6e-3 of either gap and 4e-5 of the loss's on the two seeds
#: below, the control at least 1.0e-2 of the gradient's and 4.9e-3 of the
#: change's.
LIMITS = {"loss_gap": 2e-4, "grad_gap_mean": 4e-3, "change_gap_mean": 3e-3}


def _tokens(V, shape, seed=1):
    return np.random.default_rng(seed).integers(0, V, shape).astype(np.int32)


def test_forward_matches_reference_in_float32():
    from repro.models import build

    model = cells.model("tiny-ssm")
    api = build(family.program_config(model, dtype="float32"))
    params = jax.jit(api.init)(seed_key(SEED))
    toks = _tokens(model["vocab_size"], (2, 40))
    got, _ = api.logits(params, {"tokens": jnp.asarray(toks)})
    ref = reference.init(Frozen(model), seed_key(SEED))
    x = jnp.take(ref["embed"]["table"], toks, axis=0)
    for i in range(model["n_layer"]):
        w = jax.tree_util.tree_map(lambda a: a[i], ref["blocks"])
        x = reference.block(model, w, x, "f32")
    h = rms_norm(x, 1.0, model["norm_epsilon"])
    want = jnp.matmul(h, ref["embed"]["table"].T, precision="highest")
    assert got.shape == (2, 40, family.padded_vocab(model))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=0)


@pytest.mark.parametrize("seed", [SEED, 5])
def test_train_steps_within_limits_that_the_control_breaks(seed):
    cell = dict(cells.train_cell("tiny-ssm"), check=LIMITS)
    r = calibrate.train_readings(cell, seed, True, jax.devices()[:1])
    for name, limit in LIMITS.items():
        assert r[name] <= limit, (name, r[name])
    assert any(r[f"control_{name}"] > limit for name, limit in LIMITS.items())
    assert r["control_grad_gap_mean"] >= 3 * r["grad_gap_mean"], r


def test_family_keeps_the_registered_widths():
    from repro.models import get_config

    path = os.path.join(harness.BENCH, "configs", "mamba2-780m-24layer.json")
    with open(path) as f:
        model = json.load(f)
    got, want = family.program_config(model), get_config("mamba2-780m")
    for key in ("d_model", "vocab_size", "norm_eps", "tie_embeddings",
                "ssm_state", "ssm_expand", "ssm_head_dim", "ssm_conv",
                "ssm_groups", "ssm_d_inner", "ssm_heads"):
        assert getattr(got, key) == getattr(want, key), key
    assert (got.num_layers, want.num_layers) == (24, 48)
    assert (got.ssm_d_inner, got.ssm_heads, got.vocab_size) == \
        (3072, 48, 50288)


@pytest.mark.parametrize("key,value", [("conv_bias", False),
                                       ("A_init_range", [1, 8]),
                                       ("norm_before_gate", True)])
def test_family_refuses_settings_the_program_does_not_follow(key, value):
    model = cells.model("tiny-ssm")
    model["mamba2"][key] = value
    with pytest.raises(ValueError, match=key):
        family.program_config(model)


def test_matmul_params_by_hand():
    model = cells.model("tiny-ssm")
    # d 64, d_inner 128, state 16, 8 heads: z and x 64x128 each, B and C
    # 64x16 each, dt 64x8, out 128x64; the tied head 256x64
    layer = 2 * 8192 + 2 * 1024 + 512 + 8192
    assert counts.matmul_params(model) == 2 * layer + 256 * 64


def _ssd_cost(b, l, h, p, n, chunk):
    """XLA's operations and bytes of the forward and backward pass of the
    program's SSD scan, inputs in the dtypes the mixer hands it."""
    from repro.models import layers as L

    def f(x, dt, A, B, C, D):
        y, _ = L.ssd_chunked(x, dt, A, B, C, D, chunk=chunk)
        return jnp.sum(y.astype(jnp.float32))

    args = (jax.ShapeDtypeStruct((b, l, h, p), jnp.bfloat16),
            jax.ShapeDtypeStruct((b, l, h), jnp.float32),
            jax.ShapeDtypeStruct((h,), jnp.float32),
            jax.ShapeDtypeStruct((b, l, 1, n), jnp.bfloat16),
            jax.ShapeDtypeStruct((b, l, 1, n), jnp.bfloat16),
            jax.ShapeDtypeStruct((h,), jnp.float32))
    cost = jax.jit(jax.grad(f, argnums=(0, 1, 3, 4))).lower(
        *args).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    return cost["flops"], cost["bytes accessed"]


@pytest.mark.parametrize("l,chunk", [(128, 32), (256, 64)])
def test_ssd_counts_stay_within_what_xla_computes(l, chunk):
    """So that the scan's roofline share cannot read over 100%."""
    model = dict(cells.model("tiny-ssm"), n_layer=1)
    s = reference.sizes(model)
    flops, nbytes = _ssd_cost(1, l, s["H"], s["P"], s["N"], chunk)
    ops = counts.ssd_ops(model, l, chunk)
    assert 0.3 * flops <= ops <= flops
    assert 0 < counts.ssd_bytes(model, l) <= nbytes


def test_train_ops_per_token_count_the_published_chunk():
    model = cells.model("tiny-ssm")
    per_layer = counts.ssd_forward_ops(model, 64, 256) / 64
    assert counts.train_ops_per_token(model, 64) == pytest.approx(
        3 * (2 * counts.matmul_params(model) + 2 * per_layer))


# -- the mixer's readers ------------------------------------------------------

HLO = """\
ENTRY %main (a: f32[8]) -> f32[8] {
  %while.3 = (s32[], f32[8]) while((s32[], f32[8]) %t), condition=%c, body=%b, metadata={op_name="jit(train_step)/jvp()/while"}
  %fusion.1 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop, calls=%f1, metadata={op_name="jit(train_step)/jvp()/while/body/closed_call/checkpoint/rematted_computation/mixer/dot_general"}
  %fusion.2 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop, calls=%f2, metadata={op_name="jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/mixer/ssd/exp"}
  %fusion.3 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop, calls=%f3, metadata={op_name="jit(train_step)/transpose(jvp(mixer))/ssd/while/body/mul"}
  %copy.4 = f32[8]{0} copy(f32[8]{0} %a)
  ROOT %fusion.5 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop, calls=%f5, metadata={op_name="jit(train_step)/transpose(jvp(loss))/dot_general"}
}
"""


@pytest.mark.parametrize("op_name,scope", [
    ("jit(train_step)/jvp()/while/body/mixer/dot_general", "mixer"),
    ("jit(train_step)/transpose(jvp())/while/body/mixer/ssd/exp", "ssd"),
    ("jit(train_step)/transpose(jvp(mixer))/transpose(jvp(ssd))/mul", "ssd"),
    ("jit(train_step)/jvp(loss)/while/body/unembed/dot_general", UNSCOPED),
    ("jit(train_step)/jvp()/while/body/mixer_like/ssd_ish/add", UNSCOPED),
])
def test_scope_of_names_the_scan_inside_the_mixer(op_name, scope):
    assert ssm_scopes.scope_of(op_name) == scope


def _ctx(smap, **kw):
    ops, modules = [], []
    for s in (0, 1000):
        modules.append(("jit_train_step", s, s + 900))
        ops += [(f"jit_train_step/{n}", s + a, s + b) for n, a, b in (
            ("%while.3", 0, 600), ("%fusion.1", 0, 100),
            ("%fusion.2", 100, 400), ("%fusion.3", 400, 600),
            ("%copy.4", 600, 650), ("%fusion.5", 650, 900))]
    trace = Trace(window=(0, 2000),
                  devices=[Device("/device:TPU:0", ops, modules)], spans={})
    cell = harness.load_cell("mamba2-train-8k")
    return {"trace": trace, "ssm_scopes": smap, "model": cell["config_file"],
            "seq_len": cell["mix"]["seq_len"], "chips": 1,
            "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}, **kw}


def test_mixer_readers_read_a_step_and_stay_silent_without_scopes():
    smap = ssm_scopes.scope_map(HLO)
    assert smap == {"%while.3": None, "%fusion.1": "mixer",
                    "%fusion.2": "ssd", "%fusion.3": "ssd",
                    "%copy.4": UNSCOPED, "%fusion.5": UNSCOPED}
    ctx = _ctx(smap)
    read = lambda m: harness.reader(m)(ctx)  # noqa: E731
    assert read("ssd_ms.train8k") == pytest.approx(500e-6)
    assert read("mixer_ms.train8k") == pytest.approx(100e-6)
    # the least time of the cell's scan, 2 rows a step, over 0.5 us
    model = ctx["model"]
    least = max(2 * counts.ssd_ops(model, 8192, 128) / 197e12,
                2 * counts.ssd_bytes(model, 8192) / 819e9)
    assert read("ssd_roofline.train8k") == pytest.approx(
        100 * least / 500e-9)
    # a program without the marks (the parent commit's) reads nothing
    ctx = _ctx(None)
    for m in ("ssd_ms.train8k", "mixer_ms.train8k", "ssd_roofline.train8k"):
        assert harness.reader(m)(ctx) is None


def test_mixer_scopes_on_the_cpu_read_nothing_and_do_not_raise():
    ctx = _ctx(None)
    del ctx["ssm_scopes"]
    assert ssm_scopes.train_scopes(ctx) is None      # no accelerator here
    assert harness.reader("ssd_ms.train8k")(ctx) is None


def test_mfu_and_idle_readers_of_the_cell():
    ctx = _ctx(None, train_tok_s=10_000.0)
    want = 100 * counts.train_ops_per_token(ctx["model"], 8192) * 1e4 / 197e12
    assert harness.reader("mfu.train8k")(ctx) == pytest.approx(want)
    assert harness.reader("idle_share.train8k")(ctx) == pytest.approx(
        100 * 200 / 2000)


def test_the_compiled_ssm_train_step_maps_to_the_mixer_scopes():
    cell, devs = cells.train_cell("tiny-ssm"), jax.devices()[:1]
    from bench import scopes

    smap = ssm_scopes.compiled_scopes(
        lambda: scopes.train_step_lowered(cell, devs))
    assert {"ssd", "mixer", UNSCOPED, None} <= set(smap.values())


class _Lowered:
    """A lowered program whose lowering has the scopes and whose compiled
    text has them only when compiled with the persistent cache off (a
    cache hit on a build without them); or, unscoped, a lowering with a
    file under a directory named ``mixer`` and no such scope."""
    SCOPED = 'loc("jit(f)/mixer/ssd/exp"(#loc1))'
    UNSCOPED = ('loc("jit(f)/jit(silu)/mul"(#loc3))\n'
                'loc("/work/mixer/src/repro/models/layers.py":900:10)')

    def __init__(self, log, scoped=True):
        self.log, self.scoped = log, scoped

    def as_text(self, debug_info=False):
        assert debug_info
        return self.SCOPED if self.scoped else self.UNSCOPED

    def compile(self):
        cached = jax.config.jax_enable_compilation_cache
        self.log.append(cached)
        meta = "" if cached else ', metadata={op_name="jit(f)/mixer/dot"}'
        text = f"  %fusion.1 = f32[8]{{0}} fusion(f32[8]{{0}} %a){meta}"
        return type("Compiled", (), {"as_text": lambda _: text})()


def test_a_cached_executable_without_the_mixer_is_compiled_afresh():
    log = []
    assert ssm_scopes.compiled_scopes(lambda: _Lowered(log)) == {
        "%fusion.1": "mixer"}
    assert log == [True, False]
    assert jax.config.jax_enable_compilation_cache


def test_a_program_without_the_mixer_scopes_is_not_compiled():
    log = []
    assert ssm_scopes.compiled_scopes(lambda: _Lowered(log, False)) is None
    assert log == []
