"""Run orchestrator: parallel/sequential equivalence at both shard
grains, crash isolation, manifest + resume, shard merging, and
baseline-compare verdicts (repro.core.orchestrate / repro.core.baseline)."""
import json
import os
import textwrap

import pytest

from repro.core import baseline as bl
from repro.core.flags import FlagRegistry
from repro.core.hooks import HookChain
from repro.core.orchestrate import (OrchestratorOptions, ScopeShard,
                                    execute, merge_shards, read_manifest,
                                    scope_error_record)
from repro.core.registry import BenchmarkRegistry
from repro.core.runner import RunOptions, run_benchmarks
from repro.core.scope import ScopeManager

FAST = RunOptions(min_time=0.002)


def make_mgr(modules):
    mgr = ScopeManager(registry=BenchmarkRegistry(), flags=FlagRegistry(),
                       hooks=HookChain())
    mgr.load(modules)
    mgr.register_all()
    return mgr


def _ensure_src_on_child_path(monkeypatch, extra=None):
    parts = [os.path.abspath("src")]
    if extra:
        parts.append(str(extra))
    old = os.environ.get("PYTHONPATH")
    if old:
        parts.append(old)
    monkeypatch.setenv("PYTHONPATH", os.pathsep.join(parts))


# ---------------------------------------------------------------------------
# equivalence
# ---------------------------------------------------------------------------

def test_inline_merged_matches_sequential_runner():
    """Orchestrated inline run == plain run_benchmarks, record for record
    (names + schema; timings vary)."""
    mgr = make_mgr(["repro.scopes.example_scope"])
    seq = run_benchmarks(mgr.registry.filter(".*"), FAST, progress=False)
    res = execute(mgr, mgr.registry,
                  OrchestratorOptions(jobs=1, run=FAST))
    assert sorted(res.doc) == ["benchmarks", "context"]
    assert [r["name"] for r in res.doc["benchmarks"]] == \
        [r["name"] for r in seq["benchmarks"]]
    assert [frozenset(r) for r in res.doc["benchmarks"]] == \
        [frozenset(r) for r in seq["benchmarks"]]


def test_worker_processes_refuse_to_share_a_chip(monkeypatch):
    """Worker modes start processes that each initialize JAX: without
    JAX_PLATFORMS=cpu they could reach a chip, so run/ci/execute refuse
    at once, naming the reason, and touch no device to decide."""
    from repro.core.ci import ci_main
    from repro.core.main import run_main
    from repro.core.orchestrate import chip_sharing_error
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    msg = chip_sharing_error(OrchestratorOptions(jobs=2))
    assert "one process at a time" in msg and "JAX_PLATFORMS=cpu" in msg
    assert chip_sharing_error(OrchestratorOptions(jobs=1)) is None
    assert chip_sharing_error(
        OrchestratorOptions(jobs=1, isolate="subprocess")) is not None
    mgr = make_mgr(["repro.scopes.example_scope"])
    with pytest.raises(ValueError, match="--jobs 2"):
        execute(mgr, mgr.registry, OrchestratorOptions(jobs=2, run=FAST))
    assert run_main(["--jobs", "2"]) == 2
    assert ci_main(["--jobs", "2", "--results-dir", "unused"]) == 2
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert chip_sharing_error(OrchestratorOptions(jobs=2)) is None


@pytest.mark.slow
def test_parallel_subprocess_matches_inline(monkeypatch, tmp_path):
    """--jobs 2 scope-grained subprocess run: same names/schema as
    inline, per-scope shards persisted under results/<run-id>/."""
    _ensure_src_on_child_path(monkeypatch)
    mgr = make_mgr(["repro.scopes.example_scope",
                    "repro.scopes.instr_scope"])
    inline = execute(mgr, mgr.registry,
                     OrchestratorOptions(jobs=1, run=FAST))
    par = execute(mgr, mgr.registry,
                  OrchestratorOptions(jobs=2, isolate="subprocess",
                                      shard_grain="scope", run=FAST,
                                      results_dir=str(tmp_path),
                                      run_id="t1"))
    assert [s.status for s in par.shards] == ["ok", "ok"]
    assert [r["name"] for r in par.doc["benchmarks"]] == \
        [r["name"] for r in inline.doc["benchmarks"]]
    # schema equivalence: identical key-sets per record position
    assert [frozenset(r) for r in par.doc["benchmarks"]] == \
        [frozenset(r) for r in inline.doc["benchmarks"]]
    # persistence: one shard per scope + merged.json
    out = tmp_path / "t1"
    assert sorted(p.name for p in out.iterdir()) == \
        ["example.json", "instr.json", "merged.json"]
    merged = json.loads((out / "merged.json").read_text())
    assert [s["scope"] for s in merged["context"]["shards"]] == \
        ["example", "instr"]

    # scopeplot reads run directories and merged documents
    from repro.scopeplot import load
    bf = load(str(out))
    assert bf.scope_names() == ["example", "instr"]
    assert [s["status"] for s in bf.shards()] == ["ok", "ok"]
    assert len(bf.for_scope("example")) == \
        len(load(str(out / "example.json")))


# ---------------------------------------------------------------------------
# crash isolation
# ---------------------------------------------------------------------------

CRASHY = textwrap.dedent("""
    import os
    from repro.core import Scope, State, benchmark
    from repro.core.registry import BenchmarkRegistry

    NAME = "crashy"

    def _register(registry):
        @benchmark(scope=NAME, registry=registry)
        def die(state: State):
            os._exit(42)

    SCOPE = Scope(name=NAME, register=_register)
""")


@pytest.mark.slow
def test_crash_isolation_subprocess(monkeypatch, tmp_path):
    """A scope that kills its interpreter yields a crashed shard with an
    error record; sibling scopes still complete."""
    (tmp_path / "crashy_scope.py").write_text(CRASHY)
    monkeypatch.syspath_prepend(str(tmp_path))
    _ensure_src_on_child_path(monkeypatch, extra=tmp_path)
    mgr = make_mgr(["repro.scopes.example_scope", "crashy_scope"])
    res = execute(mgr, mgr.registry,
                  OrchestratorOptions(jobs=2, isolate="subprocess",
                                      shard_grain="scope", run=FAST))
    by = {s.scope: s for s in res.shards}
    assert by["example"].status == "ok"
    assert by["crashy"].status == "crashed"
    assert "42" in by["crashy"].error
    failed = [r for r in res.doc["benchmarks"]
              if r["name"] == "crashy/SCOPE_FAILED"]
    assert len(failed) == 1 and failed[0]["error_occurred"]
    assert any(r["name"].startswith("example/")
               for r in res.doc["benchmarks"])


FAULTY = textwrap.dedent("""
    from repro.core import Scope

    NAME = "faulty"

    def _register(registry):
        raise RuntimeError("registration exploded")

    SCOPE = Scope(name=NAME, register=_register)
""")


@pytest.mark.slow
def test_subprocess_distinguishes_error_from_crash(monkeypatch, tmp_path):
    """A worker that raises a normal exception reports an ERROR shard
    (with the traceback), not a CRASHED one."""
    (tmp_path / "faulty_scope.py").write_text(FAULTY)
    monkeypatch.syspath_prepend(str(tmp_path))
    _ensure_src_on_child_path(monkeypatch, extra=tmp_path)
    make_mgr(["faulty_scope"])
    # registration failure only manifests in the worker (parent-side
    # register_all already marked it unavailable) — dispatch explicitly
    from repro.core.orchestrate import _run_subprocess
    opts = OrchestratorOptions(jobs=1, isolate="subprocess", run=FAST)
    shard = _run_subprocess("faulty", "faulty_scope", opts)
    assert shard.status == "error"
    assert "registration exploded" in shard.error


@pytest.mark.slow
def test_crash_breaks_pool_but_run_recovers(monkeypatch, tmp_path):
    """Pool mode: an interpreter-killing worker breaks the
    ProcessPoolExecutor; unfinished scopes are retried in standalone
    subprocesses and the run still produces every shard."""
    (tmp_path / "crashy_scope.py").write_text(CRASHY)
    monkeypatch.syspath_prepend(str(tmp_path))
    _ensure_src_on_child_path(monkeypatch, extra=tmp_path)
    mgr = make_mgr(["repro.scopes.example_scope", "crashy_scope"])
    res = execute(mgr, mgr.registry,
                  OrchestratorOptions(jobs=2, isolate="pool",
                                      shard_grain="scope", run=FAST))
    by = {s.scope: s for s in res.shards}
    assert set(by) == {"example", "crashy"}
    assert by["example"].status == "ok"
    assert by["crashy"].status == "crashed"


def test_import_failure_yields_error_shard(tmp_path):
    """A scope whose import fails is reported, not silently dropped —
    and inline siblings still run."""
    mgr = make_mgr(["repro.scopes.example_scope"])
    shards = [
        ScopeShard("example", "repro.scopes.example_scope", "ok",
                   run_benchmarks(mgr.registry.filter(".*"), FAST,
                                  progress=False)),
        ScopeShard("broken", "no.such.module", "error",
                   error="ModuleNotFoundError: no.such.module"),
    ]
    doc = merge_shards(shards, run_id="r1")
    assert doc["context"]["run_id"] == "r1"
    assert [s["status"] for s in doc["context"]["shards"]] == \
        ["ok", "error"]
    names = [r["name"] for r in doc["benchmarks"]]
    assert "broken/SCOPE_FAILED" in names


def test_scope_error_record_schema_matches_runner():
    """SCOPE_FAILED records carry the same schema as real error records
    so GB-JSON consumers need no special casing."""
    rec = scope_error_record(ScopeShard("x", "m", "crashed", error="boom"))
    for key in ("name", "run_name", "run_type", "repetitions",
                "repetition_index", "threads", "iterations", "real_time",
                "cpu_time", "time_unit", "error_occurred",
                "error_message"):
        assert key in rec
    assert rec["error_occurred"] is True
    assert "boom" in rec["error_message"]


# ---------------------------------------------------------------------------
# benchmark grain: plan scheduling, manifest, resume, instance isolation
# ---------------------------------------------------------------------------

def _names(doc):
    return [r["name"] for r in doc["benchmarks"]]


def _schemas(doc):
    return [frozenset(r) for r in doc["benchmarks"]]


def test_plan_grain_inline_matches_scope_grain(tmp_path):
    """--shard-grain benchmark produces a merged document benchmark-for-
    benchmark equivalent to a scope-grained inline run, with per-instance
    shards + a complete manifest under results/<run-id>/."""
    mgr = make_mgr(["repro.scopes.example_scope"])
    scope_run = execute(mgr, mgr.registry,
                        OrchestratorOptions(jobs=1, run=FAST))
    plan_run = execute(mgr, mgr.registry,
                       OrchestratorOptions(jobs=1, shard_grain="benchmark",
                                           run=FAST,
                                           results_dir=str(tmp_path),
                                           run_id="p1"))
    assert _names(plan_run.doc) == _names(scope_run.doc)
    assert _schemas(plan_run.doc) == _schemas(scope_run.doc)
    # per-instance persistence: shards/<id>.json for every plan item
    out = tmp_path / "p1"
    assert (out / "merged.json").exists()
    shard_files = sorted(p.name for p in (out / "shards").iterdir()
                         if p.suffix == ".json")
    assert len(shard_files) == len(plan_run.plan.items)
    manifest = read_manifest(str(out))
    assert manifest["run_id"] == "p1"
    assert manifest["grain"] == "benchmark"
    assert manifest["completed"] == manifest["total"] == \
        len(plan_run.plan.items)
    assert [e["name"] for e in manifest["items"]] == \
        [i.name for i in plan_run.plan.items]
    assert all(e["status"] == "ok" and e["finished"] is not None
               for e in manifest["items"])
    # per-scope rollups keep scope-grained consumers working
    assert [(s.scope, s.status) for s in plan_run.shards] == \
        [("example", "ok")]
    merged = json.loads((out / "merged.json").read_text())
    assert [s["status"] for s in merged["context"]["shards"]] == ["ok"]

    # scopeplot + baseline read the instance-sharded run directory
    from repro.scopeplot import load
    assert [r.name for r in load(str(out))] == _names(plan_run.doc)
    (out / "merged.json").unlink()      # interrupted-run view
    assert _names(bl.load_document(str(out))) == _names(plan_run.doc)
    assert [r.name for r in load(str(out))] == _names(plan_run.doc)


def test_resume_skips_completed_instances(tmp_path):
    """--resume re-runs only instances whose shard is missing/failed;
    finished instances keep their manifest timestamps (proof they were
    not re-executed)."""
    mgr = make_mgr(["repro.scopes.example_scope"])
    opts = OrchestratorOptions(jobs=1, shard_grain="benchmark", run=FAST,
                               results_dir=str(tmp_path), run_id="r1")
    first = execute(mgr, mgr.registry, opts)
    out = tmp_path / "r1"
    before = {e["name"]: e for e in read_manifest(str(out))["items"]}

    # simulate an interruption: one instance never finished
    victim = first.plan.items[2]
    (out / "shards" / f"{victim.instance_id}.json").unlink()
    (out / "merged.json").unlink()

    opts.resume = True
    second = execute(mgr, mgr.registry, opts)
    after = {e["name"]: e for e in read_manifest(str(out))["items"]}
    for name, entry in after.items():
        if name == victim.name:
            assert entry["finished"] > before[name]["finished"]
            assert not entry.get("cached")
        else:
            assert entry["finished"] == before[name]["finished"]
            assert entry.get("cached")
    # the resumed merged document is complete and in plan order
    assert _names(second.doc) == _names(first.doc)
    assert _schemas(second.doc) == _schemas(first.doc)
    assert (out / "merged.json").exists()


INSTANCE_CRASHY = textwrap.dedent("""
    import os
    from repro.core import Scope, State, benchmark

    NAME = "crashy"

    def _register(registry):
        @benchmark(scope=NAME, registry=registry)
        def ok_before(state: State):
            while state.keep_running():
                pass

        @benchmark(scope=NAME, registry=registry)
        def die(state: State):
            if state.range(0) == 2:
                os._exit(42)
            while state.keep_running():
                pass
        die.range_multiplier_args(1, 4)

        @benchmark(scope=NAME, registry=registry)
        def ok_after(state: State):
            while state.keep_running():
                pass

    SCOPE = Scope(name=NAME, register=_register)
""")


@pytest.mark.slow
def test_instance_crash_degrades_only_itself(monkeypatch, tmp_path):
    """Benchmark grain: an interpreter-killing *instance* yields an error
    record for that instance only — its family and scope siblings still
    report real records (scope grain would have lost the whole scope)."""
    # distinct module name: other tests import their own crashy_scope and
    # the parent process's module cache would serve the stale one
    (tmp_path / "instance_crashy_scope.py").write_text(INSTANCE_CRASHY)
    monkeypatch.syspath_prepend(str(tmp_path))
    _ensure_src_on_child_path(monkeypatch, extra=tmp_path)
    mgr = make_mgr(["instance_crashy_scope"])
    res = execute(mgr, mgr.registry,
                  OrchestratorOptions(jobs=2, isolate="subprocess",
                                      shard_grain="benchmark", run=FAST))
    by = {r.item.name: r for r in res.instances}
    assert by["crashy/die/2"].status == "crashed"
    assert "42" in by["crashy/die/2"].error
    for name in ("crashy/ok_before", "crashy/die/1", "crashy/die/4",
                 "crashy/ok_after"):
        assert by[name].status == "ok"
    recs = {r["name"]: r for r in res.doc["benchmarks"]}
    assert recs["crashy/die/2"]["error_occurred"]
    assert not recs["crashy/ok_after"].get("error_occurred")
    # the scope rolls up as partial, not failed
    assert [(s.scope, s.status) for s in res.shards] == \
        [("crashy", "partial")]


@pytest.mark.slow
def test_merge_determinism_across_grains_and_resume(monkeypatch, tmp_path):
    """merged.json benchmark names/order/schema are identical across
    --jobs 1 --isolate inline, --jobs 4 --shard-grain benchmark, and a
    resumed run (the ISSUE's merge-determinism contract)."""
    _ensure_src_on_child_path(monkeypatch)
    mgr = make_mgr(["repro.scopes.example_scope",
                    "repro.scopes.instr_scope"])
    inline = execute(mgr, mgr.registry,
                     OrchestratorOptions(jobs=1, isolate="inline",
                                         run=FAST))
    par = execute(mgr, mgr.registry,
                  OrchestratorOptions(jobs=4, isolate="subprocess",
                                      shard_grain="benchmark", run=FAST,
                                      results_dir=str(tmp_path),
                                      run_id="d1"))
    assert _names(par.doc) == _names(inline.doc)
    assert _schemas(par.doc) == _schemas(inline.doc)

    # interrupt: drop two instances, then resume with a different job count
    out = tmp_path / "d1"
    for item in (par.plan.items[1], par.plan.items[-1]):
        (out / "shards" / f"{item.instance_id}.json").unlink()
    (out / "merged.json").unlink()
    resumed = execute(mgr, mgr.registry,
                      OrchestratorOptions(jobs=2, isolate="subprocess",
                                          shard_grain="benchmark",
                                          run=FAST, resume=True,
                                          results_dir=str(tmp_path),
                                          run_id="d1"))
    assert sum(1 for r in resumed.instances if r.cached) == \
        len(par.plan.items) - 2
    assert _names(resumed.doc) == _names(inline.doc)
    assert _schemas(resumed.doc) == _schemas(inline.doc)
    merged = json.loads((out / "merged.json").read_text())
    assert _names(merged) == _names(inline.doc)


def test_external_scopes_run_inline_at_benchmark_grain():
    """add_scope() scopes (no importable module) can't be re-imported by
    a worker — the plan runs them inline even under --jobs N."""
    from repro.core.benchmark import Benchmark
    from repro.core.scope import Scope
    mgr = make_mgr([])
    def _register(reg):
        reg.register(Benchmark("ext/x", lambda s: None, scope="ext"))
    mgr.add_scope(Scope(name="ext", register=_register))
    mgr.register_all()
    res = execute(mgr, mgr.registry,
                  OrchestratorOptions(jobs=2, isolate="subprocess",
                                      shard_grain="benchmark", run=FAST))
    assert [r.item.name for r in res.instances] == ["ext/x"]
    assert res.instances[0].status == "ok"
    assert _names(res.doc) == ["ext/x"]


# ---------------------------------------------------------------------------
# baseline comparison
# ---------------------------------------------------------------------------

def _doc(entries):
    """entries: {name: [times_us...]} -> GB-JSON document."""
    benchmarks = []
    for name, times in entries.items():
        for i, t in enumerate(times):
            benchmarks.append({
                "name": name, "run_name": name, "run_type": "iteration",
                "repetitions": len(times), "repetition_index": i,
                "threads": 1, "iterations": 100,
                "real_time": t, "cpu_time": t, "time_unit": "us",
            })
    return {"context": {}, "benchmarks": benchmarks}


def test_compare_flags_2x_slowdown():
    base = _doc({"s/a": [10.0, 10.1, 9.9], "s/b": [5.0, 5.1, 4.9]})
    new = _doc({"s/a": [20.0, 20.2, 19.8], "s/b": [5.1, 5.0, 4.9]})
    comps = {c.name: c for c in bl.compare_documents(base, new)}
    assert comps["s/a"].verdict == "regression"
    assert comps["s/a"].ratio == pytest.approx(2.0, rel=0.05)
    assert comps["s/b"].verdict == "similar"


def test_compare_stddev_gates_noisy_changes():
    """A 15% mean shift inside the noise band must NOT be flagged."""
    base = _doc({"s/noisy": [10.0, 14.0, 6.0]})
    new = _doc({"s/noisy": [11.5, 16.0, 7.0]})
    (c,) = bl.compare_documents(base, new)
    assert c.verdict == "similar" and not c.significant


def test_compare_improvement_added_removed_errors():
    base = _doc({"s/fast": [10.0, 10.0, 10.0], "s/gone": [1.0]})
    new = _doc({"s/fast": [5.0, 5.0, 5.0], "s/new": [1.0]})
    new["benchmarks"].append({
        "name": "s/err", "run_name": "s/err", "run_type": "iteration",
        "repetitions": 1, "repetition_index": 0, "threads": 1,
        "iterations": 0, "real_time": 0.0, "cpu_time": 0.0,
        "time_unit": "us", "error_occurred": True, "error_message": "x"})
    base["benchmarks"].append(dict(new["benchmarks"][-1]))
    comps = {c.name: c for c in bl.compare_documents(base, new)}
    assert comps["s/fast"].verdict == "improvement"
    assert comps["s/gone"].verdict == "removed"
    assert comps["s/new"].verdict == "added"
    assert comps["s/err"].verdict == "errors"


def test_compare_units_normalized():
    base = {"context": {}, "benchmarks": [{
        "name": "s/x", "run_name": "s/x", "run_type": "iteration",
        "repetitions": 1, "repetition_index": 0, "threads": 1,
        "iterations": 1, "real_time": 1.0, "cpu_time": 1.0,
        "time_unit": "ms"}]}
    new = {"context": {}, "benchmarks": [{
        "name": "s/x", "run_name": "s/x", "run_type": "iteration",
        "repetitions": 1, "repetition_index": 0, "threads": 1,
        "iterations": 1, "real_time": 1000.0, "cpu_time": 1000.0,
        "time_unit": "us"}]}
    (c,) = bl.compare_documents(base, new)
    assert c.verdict == "similar"
    assert c.ratio == pytest.approx(1.0)


def test_compare_cli_exit_codes(tmp_path, capsys):
    base = _doc({"s/a": [10.0, 10.0, 10.1]})
    slow = _doc({"s/a": [20.0, 20.0, 20.2]})
    pa, pb = tmp_path / "a.json", tmp_path / "b.json"
    pa.write_text(json.dumps(base))
    pb.write_text(json.dumps(slow))
    assert bl.compare_main([str(pa), str(pb)]) == 1
    assert "REGRESSION" in capsys.readouterr().out
    assert bl.compare_main([str(pa), str(pa)]) == 0


def test_gate_fails_on_vanished_or_errored_benchmarks():
    """A crashed scope (benchmarks vanish or turn into error records in
    the contender) must fail the CI gate, not slide through as
    'removed'/'added'."""
    base = _doc({"s/a": [10.0], "s/b": [10.0]})
    vanished = _doc({"s/a": [10.0]})
    assert [c.name for c in
            bl.gate_failures(bl.compare_documents(base, vanished))] == \
        ["s/b"]
    errored = _doc({"s/a": [10.0]})
    errored["benchmarks"].append({
        "name": "s/b", "run_name": "s/b", "run_type": "iteration",
        "repetitions": 1, "repetition_index": 0, "threads": 1,
        "iterations": 0, "real_time": 0.0, "cpu_time": 0.0,
        "time_unit": "us", "error_occurred": True, "error_message": "x"})
    assert [c.name for c in
            bl.gate_failures(bl.compare_documents(base, errored))] == \
        ["s/b"]
    # already broken in the baseline → not a new failure
    base_broken = _doc({"s/a": [10.0]})
    base_broken["benchmarks"].append(dict(errored["benchmarks"][-1]))
    assert bl.gate_failures(
        bl.compare_documents(base_broken, errored)) == []


def test_load_document_reads_interrupted_run_dir(tmp_path):
    """A run directory without merged.json (crash mid-run) still loads:
    the per-scope shards are concatenated."""
    a = _doc({"s/a": [1.0]})
    b = _doc({"s/b": [2.0]})
    (tmp_path / "a.json").write_text(json.dumps(a))
    (tmp_path / "b.json").write_text(json.dumps(b))
    doc = bl.load_document(str(tmp_path))
    assert [r["name"] for r in doc["benchmarks"]] == ["s/a", "s/b"]


def test_aggregates_are_not_double_counted():
    doc = _doc({"s/a": [10.0, 10.0]})
    doc["benchmarks"].append({
        "name": "s/a_mean", "run_name": "s/a", "run_type": "aggregate",
        "aggregate_name": "mean", "repetitions": 2, "threads": 1,
        "iterations": 100, "real_time": 10.0, "cpu_time": 10.0,
        "time_unit": "us"})
    stats = bl.collect_stats(doc)
    assert stats["s/a"].n == 2
