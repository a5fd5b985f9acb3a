"""Multi-device SPMD correctness — subprocess with 8 host devices.

Covers: sharded-vs-single-device train step equivalence, shard_map MoE,
elastic resharded restore (8→4 devices).  Subprocesses because XLA locks
the device count at first jax init (the main pytest process must keep 1
device).
"""
import os
import subprocess
import sys
import textwrap

import pytest


def run_sub(code: str, devices: int = 8):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=600,
                       env=env)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout


@pytest.mark.slow
def test_sharded_train_step_matches_single_device():
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.models import build, get_config
        from repro.train import AdamWConfig, make_train_step
        from repro.train.step import make_init_fn
        from repro.distributed import partition as part
        from repro.distributed.logical import default_rules, logical_rules
        from repro.launch.mesh import make_mesh

        cfg = get_config("llama3.2-1b").reduced().override(num_layers=2)
        api = build(cfg)
        opt = AdamWConfig(lr=1e-3)
        init_fn = make_init_fn(api, opt)
        step_fn = make_train_step(api, opt)
        key = jax.random.PRNGKey(0)
        batch = {"tokens": jax.random.randint(key, (8, 32), 0,
                                              cfg.vocab_size)}
        # single-device result
        state = init_fn(key)
        s1, m1 = jax.jit(step_fn)(state, batch)
        # sharded result, on the mesh the launcher builds
        mesh = make_mesh((2, 4), ("data", "model"))
        pspecs = part.param_specs(cfg, jax.eval_shape(init_fn, key)["params"],
                                  mesh)
        shard = lambda t: jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), t,
            is_leaf=lambda x: isinstance(x, P))
        state_specs = {"params": pspecs,
                       "opt": {"m": pspecs, "v": pspecs, "count": P()},
                       "step": P()}
        with mesh, logical_rules(default_rules(cfg, mesh)):
            state2 = jax.jit(init_fn,
                             out_shardings=shard(state_specs))(key)
            s2, m2 = jax.jit(step_fn,
                             in_shardings=(shard(state_specs), None),
                             out_shardings=(shard(state_specs), None))(
                state2, batch)
        d = abs(float(m1["loss"]) - float(m2["loss"]))
        assert d < 2e-3, d
        # params equal after one step
        for a, b in zip(jax.tree.leaves(s1["params"]),
                        jax.tree.leaves(s2["params"])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-3)
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_moe_shard_map_matches_reference():
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.models import layers as L
        from repro.models.config import ModelConfig
        from repro.distributed.logical import default_rules, logical_rules
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        cfg = ModelConfig(name="t", family="moe", num_layers=2, d_model=32,
                          num_heads=4, num_kv_heads=2, d_ff=64,
                          vocab_size=128, moe_num_experts=8, moe_top_k=2,
                          moe_d_ff=64, moe_capacity_factor=8.0)
        p = L.init_moe(jax.random.PRNGKey(0), 32, 8, 64, 0)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32))
        y_ref, _ = L.moe_scatter(p, x, top_k=2, capacity_factor=8.0)
        rules = default_rules(cfg, mesh)
        with mesh:
            pw = dict(p)
            for k in ("w_up", "w_gate", "w_down"):
                pw[k] = jax.device_put(p[k],
                                       NamedSharding(mesh,
                                                     P("model", None, None)))
            xs = jax.device_put(x, NamedSharding(mesh, P("data", None,
                                                         None)))
            with logical_rules(rules):
                y, _ = jax.jit(
                    lambda p, x: L.moe_layer(p, x, cfg))(pw, xs)
        err = np.abs(np.asarray(y) - np.asarray(y_ref)).max()
        assert err < 1e-5, err
        print("OK")
    """)
    assert "OK" in out


def test_decode_gqa_path_follows_the_head_rules():
    """On a 4-way model axis the rules shard ``heads`` (4) but not
    ``kv_heads`` (2): decode repeats the cache (``gqa_repeated``).  On a
    2-way axis both are sharded and decode reads each kv head's cache as
    stored (``gqa_grouped``).  Both match the ragged decode with no rules
    bound."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.models import build, get_config, transformer
        from repro.distributed.logical import default_rules, logical_rules
        from repro.launch.mesh import make_mesh

        cfg = get_config("internlm2-1.8b").reduced().override(num_layers=2)
        assert (cfg.num_heads, cfg.num_kv_heads) == (4, 2)
        api = build(cfg)
        params = api.init(jax.random.PRNGKey(0))
        B, S = 4, 32
        cache = api.init_cache(B, S)
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        cache["k"] = jax.random.normal(ks[0], cache["k"].shape,
                                       cache["k"].dtype)
        cache["v"] = jax.random.normal(ks[1], cache["v"].shape,
                                       cache["v"].dtype)
        cache["pos"] = jnp.asarray([0, 5, 17, S - 1], jnp.int32)
        tokens = jax.random.randint(ks[2], (B, 1), 0, cfg.vocab_size)

        def step(p, t, c):
            return transformer.decode_step(cfg, p, t, c)

        ref, _ = jax.jit(step)(params, tokens, cache)
        for model, scope in ((4, "gqa_repeated"), (2, "gqa_grouped")):
            mesh = make_mesh((4 // model, model), ("data", "model"))
            with mesh, logical_rules(default_rules(cfg, mesh)):
                lowered = jax.jit(step).lower(params, tokens, cache)
                text = lowered.as_text(debug_info=True)
                assert scope in text, (model, scope)
                other = ({"gqa_repeated", "gqa_grouped"} - {scope}).pop()
                assert other not in text, (model, other)
                got, _ = lowered.compile()(params, tokens, cache)
            np.testing.assert_allclose(np.asarray(got, np.float32),
                                       np.asarray(ref, np.float32),
                                       atol=2e-2, rtol=0)
        print("OK")
    """, devices=4)
    assert "OK" in out


@pytest.mark.slow
def test_elastic_restore_reshard(tmp_path=None):
    """Save sharded on 8 devices, restore onto a 4-device mesh."""
    out = run_sub("""
        import tempfile, jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import save_checkpoint
        from repro.checkpoint.store import restore_resharded
        mesh8 = jax.make_mesh((2, 4), ("data", "model"))
        tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
        spec = {"w": P("data", "model")}
        placed = jax.device_put(
            tree, jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh8, s), spec,
                is_leaf=lambda x: isinstance(x, P)))
        with tempfile.TemporaryDirectory() as d:
            save_checkpoint(d + "/ck", placed, step=3)
            mesh4 = jax.make_mesh((4,), ("model",))
            out, step = restore_resharded(
                d + "/ck", tree, mesh4, {"w": P("model", None)})
            assert step == 3
            np.testing.assert_array_equal(np.asarray(out["w"]),
                                          np.asarray(tree["w"]))
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.slow
def test_dryrun_single_cell_small_overrides():
    """The dry-run machinery end-to-end on one cell (512 fake devices)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "llama3.2-1b", "--shape", "decode_32k", "--out",
         "/tmp/dryrun_test", "--tag", "pytest", "--override",
         "num_layers=2"],
        capture_output=True, text=True, timeout=580, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "compiled in" in r.stdout or "SKIP (cached)" in r.stdout
