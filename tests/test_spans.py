"""The program's own instrumentation: the span helper, the serve engine's
spans and step records, the decode program's name, and the named layer
scopes in the compiled programs' metadata."""
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.spans import span
from repro.models import build, get_config
from repro.serve import ServeConfig, ServeEngine
from repro.serve import engine as engine_mod

SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src")

ADMIT_CHILDREN = ("engine.prefill", "engine.first_token", "engine.splice")
DECODE_CHILDREN = ("engine.upload", "engine.decode_wait", "engine.sample",
                   "engine.retire")


@pytest.fixture(scope="module")
def small():
    cfg = get_config("llama3.2-1b").reduced().override(
        num_layers=2, vocab_size=128)
    api = build(cfg)
    return cfg, api, api.init(jax.random.PRNGKey(0))


def _engine(small, **kw):
    _, api, params = small
    return ServeEngine(api, params, ServeConfig(
        **{"max_batch": 2, "max_len": 64, "prompt_buckets": (8, 16), **kw}))


def test_span_adds_host_seconds_to_its_record():
    rec = {}
    with span("a", rec, uid=1):
        with span("b", rec):
            pass
    with span("a", rec):
        pass
    with span("c"):                        # no record: a trace span only
        pass
    assert set(rec) == {"a", "b"}
    assert 0.0 <= rec["b"] <= rec["a"]
    with pytest.raises(ValueError):
        with span("d", rec):
            raise ValueError
    assert "d" in rec


def test_trace_annotations_go_through_the_span_helper():
    users = []
    for path in glob.glob(os.path.join(SRC, "**", "*.py"), recursive=True):
        with open(path) as f:
            if "TraceAnnotation" in f.read():
                users.append(os.path.relpath(path, SRC))
    assert users == [os.path.join("repro", "core", "spans.py")]


def test_step_records_account_for_every_request(small):
    eng = _engine(small)
    lengths = [5, 12, 7, 16, 3]
    reqs = [eng.submit(np.arange(1, 1 + n), max_tokens=3) for n in lengths]
    done = eng.run()
    assert len(done) == len(reqs)
    recs = list(eng.step_records)
    admitted = [uid for r in recs for uid in r.admitted]
    assert sorted(admitted) == sorted(r.uid for r in reqs)
    bucket = {r.uid: (8 if r.prompt_len <= 8 else 16) for r in reqs}
    plen = {r.uid: r.prompt_len for r in reqs}
    for r in recs:
        assert r.padded_tokens == sum(bucket[u] for u in r.admitted)
        assert r.prompt_tokens == sum(plen[u] for u in r.admitted)
        assert 0 <= r.live <= 2 and r.queue_depth >= r.live
        assert r.start <= r.end
        for name, secs in r.spans.items():
            assert 0.0 <= secs <= r.seconds, name
        admit = r.spans.get("engine.admit", 0.0)
        assert sum(r.spans.get(n, 0.0) for n in ADMIT_CHILDREN) <= admit
        assert ("engine.admit" in r.spans) == bool(r.admitted)
        assert ("engine.decode" in r.spans) == bool(r.live)
        if r.live:
            assert sum(r.spans[n] for n in DECODE_CHILDREN) \
                <= r.spans["engine.decode"]
        assert admit + r.spans.get("engine.decode", 0.0) <= r.seconds
    assert list(eng.queue_depth_log) == [r.queue_depth for r in recs]
    assert len(eng.queue_depth_log) == len(recs)
    assert eng.queue_depth_log[-1] == recs[-1].queue_depth
    assert eng.queue_depth_log[:2] == [r.queue_depth for r in recs[:2]]
    for r in reqs:
        assert r.submitted_at <= r.admitted_at <= r.first_token_at


def test_step_records_are_bounded(small, monkeypatch):
    monkeypatch.setattr(engine_mod, "MAX_STEP_RECORDS", 4)
    eng = _engine(small)
    eng.submit(np.arange(1, 6), max_tokens=2)
    for _ in range(10):
        eng.step()
    assert len(eng.step_records) == 4
    assert list(eng.queue_depth_log) == [0, 0, 0, 0]


def test_summary_reports_the_queue_wait(small):
    eng = _engine(small, max_batch=1)
    for n in (4, 6, 5):
        eng.submit(np.arange(1, 1 + n), max_tokens=2)
    stats = ServeEngine.summarize(eng.run())
    assert 0.0 < stats["queue_wait_mean_s"] <= stats["ttft_mean_s"]


def _events(trace_dir):
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("engine."):
                    out.append((e.name, int(e.start_ns),
                                int(e.start_ns + e.duration_ns),
                                dict(e.stats)))
    return out


def test_engine_spans_nest_in_the_profiler_trace(small, tmp_path):
    eng = _engine(small)
    eng.submit(np.arange(1, 6), max_tokens=2)
    eng.run()                                       # compile outside
    with jax.profiler.trace(str(tmp_path)):
        reqs = [eng.submit(np.arange(1, 1 + n), max_tokens=3)
                for n in (5, 9, 4)]
        eng.run()
    events = _events(str(tmp_path))
    by = {}
    for name, a, b, stats in events:
        by.setdefault(name, []).append((a, b, stats))

    def inside(child, parent):
        for a, b, _ in by[child]:
            assert any(pa <= a and b <= pb for pa, pb, _ in by[parent]), \
                (child, parent)

    for child in ADMIT_CHILDREN:
        inside(child, "engine.admit")
    for child in DECODE_CHILDREN:
        inside(child, "engine.decode")
    admits = by["engine.admit"]
    assert sorted(s["uid"] for _, _, s in admits) == [r.uid for r in reqs]
    assert {(s["bucket"], s["tokens"]) for _, _, s in admits} == {
        (8, 5), (16, 9), (8, 4)}
    assert len(by["engine.decode"]) == sum(
        1 for r in eng.step_records if r.live) - 1   # the warm-up's one


def test_decode_program_is_named(small):
    eng = _engine(small)
    toks = jnp.zeros((2, 1), jnp.int32)
    text = eng._decode.lower(eng.params, toks, eng.cache).as_text()
    assert re.search(r"module @jit_decode_step\b", text)


def _op_names(text):
    return re.findall(r'op_name="([^"]*)"', text)


def _scopes(text):
    """The named scopes found in a compiled program's op_name metadata,
    including their jvp(...) and transpose(jvp(...)) forms."""
    found = set()
    for name in _op_names(text):
        for part in re.split(r"[/;]", name):
            found.add(re.sub(r"^(?:[\w-]+\()+|\)+$", "", part))
    return found


@pytest.fixture(scope="module")
def programs(small):
    from repro.distributed.logical import default_rules, logical_rules
    from repro.launch.mesh import make_mesh
    from repro.launch.train import sharded_train_fns
    from repro.train import AdamWConfig

    cfg = small[0].override(remat="full", loss_chunk=16)
    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    structs, _, _, step = sharded_train_fns(cfg, AdamWConfig(), mesh)
    batch = {k: jax.ShapeDtypeStruct((2, 32), jnp.int32)
             for k in ("tokens", "labels")}
    with mesh, logical_rules(default_rules(cfg, mesh)):
        train = step.lower(structs, batch).compile().as_text()
    ssm = get_config("mamba2-780m").reduced().override(
        num_layers=2, vocab_size=128, remat="full", loss_chunk=16)
    ssm_structs, _, _, ssm_step = sharded_train_fns(ssm, AdamWConfig(), mesh)
    with mesh, logical_rules(default_rules(ssm, mesh)):
        ssm_train = ssm_step.lower(ssm_structs, batch).compile().as_text()
    eng = _engine(small)
    decode = eng._decode.lower(eng.params, jnp.zeros((2, 1), jnp.int32),
                               eng.cache).compile().as_text()
    return {"train": train, "decode": decode, "ssm_train": ssm_train}


@pytest.mark.parametrize("program,scopes", [
    ("train", {"embed", "attention", "mlp", "loss", "optimizer"}),
    ("decode", {"embed", "attention", "mlp", "unembed"}),
    ("ssm_train", {"embed", "mixer", "ssd", "loss", "optimizer"}),
])
def test_compiled_programs_carry_the_layer_scopes(programs, program, scopes):
    assert scopes <= _scopes(programs[program])


def test_backward_ops_carry_their_forward_scope(programs):
    names = _op_names(programs["train"])
    assert any("transpose(jvp(loss))" in n for n in names)
    assert any(n.startswith("jit(train_step)/transpose(")
               and "/attention/" in n for n in names)


def test_ssd_scope_nests_in_the_mixer_forward_and_backward(programs):
    names = _op_names(programs["ssm_train"])
    assert any("/mixer/ssd/" in n for n in names)
    assert any(n.startswith("jit(train_step)/transpose(")
               and "/mixer/ssd/" in n for n in names)
