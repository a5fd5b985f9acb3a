"""The program against the plain references at a small size, on the CPU.

Each comparison runs the program as a cell runs it (the engine's
bucketed prefill, splice and ragged decode; the sharded train step), in
float32 where the check is of the mathematics and in the configuration's
bfloat16 where it is of the cell's own readings.
"""
import jax
import jax.numpy as jnp
import numpy as np

from bench.drivers import train as train_driver
from bench.families import transformer as transformer_family
from bench.reference import transformer as transformer_ref
from bench.reference.common import Frozen, seed_key
from bench.tests import cells

SEED = 2**33 + 7           # above 32 bits, as a run's seed may be


def _tokens(V, shape, seed=1):
    return np.random.default_rng(seed).integers(0, V, shape).astype(np.int32)


def test_transformer_forward_matches_reference():
    from repro.models import build

    model = cells.model("tiny-transformer")
    api = build(transformer_family.program_config(
        model, dtype="float32", attn_impl="naive"))
    params = jax.jit(api.init)(seed_key(SEED))
    toks = _tokens(model["vocab_size"], (2, 24))
    got, _ = api.logits(params, {"tokens": jnp.asarray(toks)})
    ref = transformer_ref.init(Frozen(model), seed_key(SEED))
    x = jnp.take(ref["embed"]["table"], toks, axis=0)
    for i in range(model["num_hidden_layers"]):
        x = transformer_ref.block(
            model, jax.tree_util.tree_map(lambda a: a[i], ref["blocks"]), x,
            "f32")
    h = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                          + model["rms_norm_eps"])
    want = jnp.matmul(h, ref["unembed"]["table"].T, precision="highest")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=0)


def test_served_tokens_match_reference_in_float32():
    """Greedy tokens served by the engine in float32 are the reference's
    first choice, up to rounding."""
    from repro.models import build
    from repro.serve import ServeConfig, ServeEngine

    model = cells.model("tiny-transformer")
    api = build(transformer_family.program_config(model, dtype="float32"))
    params = jax.jit(api.init)(seed_key(SEED))
    dep = model["deployment"]
    eng = ServeEngine(api, params, ServeConfig(
        max_batch=dep["max_batch"], max_len=dep["max_len"],
        prompt_buckets=tuple(dep["prompt_buckets"]),
        cache_dtype=jnp.float32))
    reqs = [eng.submit(_tokens(model["vocab_size"], (n,), seed=n),
                       max_tokens=12) for n in (9, 30, 50, 17, 61)]
    eng.run()
    served = [(list(r.prompt), r.output) for r in reqs]
    gaps, _ = transformer_ref.served_gaps(model, SEED, served)
    assert max(gaps) < 1e-4


def test_train_steps_match_reference():
    cell = cells.train_cell()
    run = train_driver.Run(cell, SEED, 0, jax.devices()[:1])
    run.setup()
    got = {"loss": run.losses, "grad": run.grad, "change": run.change}
    run.release()
    gaps = train_driver.compare(got, run.readings())
    assert gaps["loss_gap"] < 2e-4
    assert gaps["grad_gap_mean"] < 0.005
    assert gaps["change_gap_mean"] < 0.005
