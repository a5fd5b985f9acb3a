"""Per-arch smoke tests (reduced configs) + serving-consistency checks."""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import build, get_config, list_archs
from repro.models import layers as L

ARCHS = list(list_archs())


def make_batch(cfg, B=2, S=32, key=jax.random.PRNGKey(7)):
    batch = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size)}
    if cfg.family == "vlm":
        batch["vision_embeds"] = jax.random.normal(
            key, (B, S, cfg.d_model)) * 0.02
        batch["vision_mask"] = jnp.zeros((B, S), bool).at[:, :4].set(True)
    if cfg.family in ("audio", "encdec"):
        batch["frames"] = jax.random.normal(
            key, (B, cfg.enc_seq, cfg.d_model)) * 0.02
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward_loss(arch):
    """Assigned-arch smoke: reduced config, one loss step, shapes+finite."""
    cfg = get_config(arch).reduced()
    api = build(cfg)
    params = api.init(jax.random.PRNGKey(0))
    batch = make_batch(cfg)
    loss, metrics = jax.jit(api.loss)(params, batch)
    assert np.isfinite(float(loss))
    logits, _ = jax.jit(api.logits)(params, batch)
    assert logits.shape == (2, 32, cfg.vocab_size)
    assert np.all(np.isfinite(np.asarray(logits, np.float32)))


@pytest.mark.parametrize("arch", ["mamba2-780m", "jamba-v0.1-52b"])
def test_ssd_families_loss_gradients_are_finite(arch):
    """The SSD mixer's backward pass, in the SSM and the hybrid family."""
    cfg = get_config(arch).reduced()
    api = build(cfg)
    params = api.init(jax.random.PRNGKey(0))
    batch = make_batch(cfg)
    grads = jax.jit(jax.grad(lambda p: api.loss(p, batch)[0]))(params)
    for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
        assert np.all(np.isfinite(np.asarray(g, np.float32))), path


@pytest.mark.parametrize("arch", ["llama3.2-1b", "qwen3-1.7b",
                                  "deepseek-moe-16b", "mamba2-780m",
                                  "jamba-v0.1-52b", "whisper-small",
                                  "qwen2-vl-2b", "internlm2-1.8b"])
def test_decode_matches_teacher_forcing(arch):
    """prefill+decode must reproduce the teacher-forced logits."""
    cfg = get_config(arch).reduced().override(moe_capacity_factor=8.0)
    api = build(cfg)
    params = api.init(jax.random.PRNGKey(1))
    B, S = 2, 24
    batch = make_batch(cfg, B, S, jax.random.PRNGKey(2))
    full, _ = jax.jit(api.logits)(params, batch)
    pre = {k: (v[:, :S - 1] if v.ndim >= 2 and v.shape[1] == S else v)
           for k, v in batch.items()}
    cache = api.init_cache(B, S + 4)
    lp, cache = jax.jit(api.prefill)(params, pre, cache)
    ld, cache = jax.jit(api.decode_step)(
        params, batch["tokens"][:, S - 1:S], cache)
    np.testing.assert_allclose(np.asarray(lp[:, 0], np.float32),
                               np.asarray(full[:, S - 2], np.float32),
                               atol=5e-2, rtol=1e-2)
    np.testing.assert_allclose(np.asarray(ld[:, 0], np.float32),
                               np.asarray(full[:, S - 1], np.float32),
                               atol=5e-2, rtol=1e-2)


@pytest.mark.parametrize("arch", ["internlm2-1.8b", "deepseek-moe-16b"])
def test_decode_per_row_pos_matches_scalar_pos(arch):
    """A [B] ``pos`` whose rows are all equal writes and reads the cache as
    the scalar ``pos`` does: the per-row scatter and the all-rows
    ``dynamic_update_slice`` of ``write_kv`` give the same logits and
    caches."""
    from repro.models import transformer
    cfg = get_config(arch).reduced().override(moe_capacity_factor=8.0)
    api = build(cfg)
    params = api.init(jax.random.PRNGKey(1))
    B, S = 2, 12
    batch = make_batch(cfg, B, S, jax.random.PRNGKey(2))
    _, cache = jax.jit(api.prefill)(params, batch, api.init_cache(B, S + 4))
    tokens = batch["tokens"][:, -1:]
    step = jax.jit(lambda c: transformer.decode_step(cfg, params, tokens, c))
    ls, cs = step(cache)
    lr, cr = step(dict(cache, pos=jnp.full((B,), S, jnp.int32)))
    assert cs["pos"].shape == () and cr["pos"].shape == (B,)
    np.testing.assert_array_equal(np.asarray(cr["pos"]), S + 1)
    np.testing.assert_allclose(np.asarray(lr, np.float32),
                               np.asarray(ls, np.float32), rtol=0, atol=0)
    for name in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(cr[name], np.float32),
                                      np.asarray(cs[name], np.float32))


@pytest.mark.parametrize("slot", [0, 1, 2])
def test_write_slot_changes_only_that_slot(slot):
    """A prefill's one-row cache lands in slot ``slot`` of a 3-slot pool:
    that slot's keys, values and position are the row's, and every other
    slot is as it was."""
    from repro.models import transformer
    Ln, B, S, K, hd = 2, 3, 8, 2, 4
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    pool = {"k": jax.random.normal(ks[0], (Ln, B, S, K, hd), jnp.bfloat16),
            "v": jax.random.normal(ks[1], (Ln, B, S, K, hd), jnp.bfloat16),
            "pos": jnp.asarray([5, 6, 7], jnp.int32)}
    row = {"k": jax.random.normal(ks[2], (Ln, 1, S, K, hd), jnp.bfloat16),
           "v": jax.random.normal(ks[3], (Ln, 1, S, K, hd), jnp.bfloat16),
           "pos": jnp.asarray([3], jnp.int32)}
    want = {name: np.array(pool[name]) for name in pool}
    out = jax.jit(transformer.write_slot)(pool, row, slot)
    for name in ("k", "v"):
        want[name][:, slot] = np.asarray(row[name])[:, 0]
    want["pos"][slot] = 3
    assert sorted(out) == sorted(want)
    for name, a in out.items():
        assert a.dtype == pool[name].dtype
        np.testing.assert_array_equal(np.asarray(a), want[name])


def test_attention_sublayer_is_written_once():
    """Outside ``layers.py`` no model family projects q/k/v, applies rope
    or calls an attention kernel itself: they all go through
    ``layers.attention`` and ``layers.attention_decode``, the one place
    that reads ``attn_impl``.  encdec's cross-attention, the only one of
    its kind, calls its kernels on its own (query ``qx``)."""
    import ast
    import os
    from repro import models
    sublayer = {"_qkv", "apply_rope", "naive_attention",
                "flash_attention_xla"}
    calls, impl_reads = [], []
    for path in sorted(glob.glob(os.path.join(
            os.path.dirname(models.__file__), "*.py"))):
        name = os.path.basename(path)
        with open(path) as f:
            tree = ast.parse(f.read())
        for fn in ast.walk(tree):
            if not isinstance(fn, ast.FunctionDef):
                continue
            for node in ast.walk(fn):
                if isinstance(node, ast.Attribute) \
                        and node.attr == "attn_impl":
                    impl_reads.append((name, fn.name))
        if name == "layers.py":
            continue
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            callee = f.attr if isinstance(f, ast.Attribute) else \
                getattr(f, "id", None)
            if callee not in sublayer:
                continue
            first = node.args[0] if node.args else None
            cross = (name == "encdec.py" and isinstance(first, ast.Name)
                     and first.id == "qx")
            if not cross:
                calls.append((name, node.lineno, callee))
    assert calls == []
    assert sorted(set(impl_reads)) == [("layers.py", "attention")]


def _jaxpr_shapes(jaxpr):
    """Shapes of every intermediate of a jaxpr, scan and jit bodies too."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield tuple(getattr(v.aval, "shape", ()))
        for param in eqn.params.values():
            for sub in (param if isinstance(param, (tuple, list))
                        else (param,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _jaxpr_shapes(sub)


@pytest.mark.parametrize("arch,kv_heads", [("internlm2-1.8b", None),
                                           ("deepseek-moe-16b", 4)])
def test_ragged_decode_reads_gqa_cache_once_per_kv_head(arch, kv_heads):
    """With no rules bound (the serve engine on one chip) the ragged decode
    step reads each kv head's cache as stored: no intermediate holds both
    the query-head count and the cache length when K < H, none holds the
    cache's S*K rows as one axis (a view that would score every query head
    against every kv head), and the compiled program carries the
    ``gqa_grouped`` scope.  deepseek-moe-16b runs K == H, as published."""
    from repro.models import transformer
    cfg = get_config(arch).reduced().override(num_layers=3)
    if kv_heads:
        cfg = cfg.override(num_kv_heads=kv_heads)
    B, S = 2, 40
    H, K = cfg.num_heads, cfg.num_kv_heads
    assert S not in (B, H, K, cfg.hd, cfg.num_layers)
    api = build(cfg)
    params = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: api.init_cache(B, S))
    cache["pos"] = jax.ShapeDtypeStruct((B,), jnp.int32)
    tokens = jax.ShapeDtypeStruct((B, 1), jnp.int32)

    def step(p, t, c):
        return transformer.decode_step(cfg, p, t, c)

    jaxpr = jax.make_jaxpr(step)(params, tokens, cache).jaxpr
    shapes = set(_jaxpr_shapes(jaxpr))
    assert not [sh for sh in shapes if S * K in sh]
    if K < H:
        assert not [sh for sh in shapes if H in sh and S in sh]
    text = jax.jit(step).lower(params, tokens, cache).as_text(
        debug_info=True)
    assert "gqa_grouped" in text and "gqa_repeated" not in text


def test_mrope_collapses_to_rope_for_text():
    """qwen2-vl M-RoPE with equal t/h/w positions == standard RoPE."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 4, 32))
    pos = jnp.broadcast_to(jnp.arange(8)[None], (2, 8))
    pos3 = jnp.broadcast_to(pos[None], (3, 2, 8))
    a = L.apply_rope(x, pos, 10000.0)
    b = L.apply_rope(x, pos3, 10000.0, mrope_sections=(6, 5, 5))
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_param_counts_match_known_sizes():
    expect = {"llama3.2-1b": 1.24e9, "mamba2-780m": 0.78e9,
              "stablelm-12b": 12.1e9, "jamba-v0.1-52b": 51.5e9,
              "deepseek-moe-16b": 16.9e9, "whisper-small": 0.24e9}
    for arch, n in expect.items():
        got = get_config(arch).num_params()
        assert abs(got - n) / n < 0.06, (arch, got, n)


def test_moe_active_params_smaller():
    cfg = get_config("deepseek-moe-16b")
    assert cfg.num_active_params() < 0.25 * cfg.num_params()


def test_config_registry_complete():
    assert len(ARCHS) == 10
    fams = {get_config(a).family for a in ARCHS}
    assert {"dense", "moe", "ssm", "hybrid", "vlm", "audio"} <= fams
