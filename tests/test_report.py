"""Report pipeline: golden report.md from a fixture run dir, spec
round-trips for every plot type, SpecError line numbers, batch-mode
staleness, and the two-run end-to-end trend (repro.scopeplot.report).

Regenerate the golden after an intentional report-format change::

    REPORT_GOLDEN_UPDATE=1 PYTHONPATH=src python -m pytest tests/test_report.py
"""
import json
import os

import pytest
import yaml

from repro.core import history as hist
from repro.scopeplot.plot import (PLOT_TYPES, SpecError, is_stale,
                                  load_spec, render_spec,
                                  render_spec_dir)
from repro.scopeplot.report import (generate_history_report,
                                    generate_run_report, report_main)

GOLDEN = os.path.join(os.path.dirname(__file__), "data",
                      "report_golden.md")

CTX = {"date": "2026-07-31T00:00:00", "host_name": "fixturehost",
       "machine": "x86_64", "num_cpus": 8, "jax_version": "0.0-test",
       "backend": "cpu", "device_count": 1, "device_kind": "cpu",
       "scope_version": "1.0.0-jax"}


def gb_doc(run_id, means_us, date="2026-07-31T00:00:00"):
    ctx = dict(CTX, run_id=run_id, date=date)
    ctx["shards"] = [{"scope": "s", "module": "m", "status": "ok",
                      "duration_s": 0.5}]
    return {"context": ctx, "benchmarks": [
        {"name": n, "run_name": n, "run_type": "iteration",
         "repetitions": 1, "repetition_index": 0, "threads": 1,
         "iterations": 10, "real_time": us, "cpu_time": us,
         "time_unit": "us"} for n, us in means_us.items()]}


def fixture_run_dir(tmp_path):
    """Two deterministic runs recorded in history; r2 persisted."""
    results = tmp_path / "results"
    run_dir = results / "r2"
    run_dir.mkdir(parents=True)
    doc1 = gb_doc("r1", {"s/a/n:1": 2.2, "s/a/n:2": 4.0},
                  date="2026-07-30T00:00:00")
    doc2 = gb_doc("r2", {"s/a/n:1": 2.0, "s/a/n:2": 4.0})
    hist.append_run(str(results), doc1)
    hist.append_run(str(results), doc2)
    (run_dir / "merged.json").write_text(json.dumps(doc2, indent=2))
    return run_dir


# ---------------------------------------------------------------------------
# golden file
# ---------------------------------------------------------------------------

def test_report_md_matches_golden(tmp_path):
    """The Markdown report from a fixed run dir is byte-stable —
    everything in it derives from the run artifacts, never from the
    machine or clock the report was generated on."""
    run_dir = fixture_run_dir(tmp_path)
    paths = generate_run_report(str(run_dir))
    got = open(paths["md"]).read()
    if os.environ.get("REPORT_GOLDEN_UPDATE"):
        os.makedirs(os.path.dirname(GOLDEN), exist_ok=True)
        with open(GOLDEN, "w") as f:
            f.write(got)
        pytest.skip("golden updated")
    assert got == open(GOLDEN).read()


def test_report_artifacts(tmp_path):
    run_dir = fixture_run_dir(tmp_path)
    paths = generate_run_report(str(run_dir))
    out = run_dir / "report"
    assert paths["html"] == str(out / "index.html")
    for f in ("index.html", "report.md", "s_times.png", "s_trend.png",
              "s_speedup.png"):
        assert (out / f).exists(), f
    html = open(paths["html"]).read()
    assert '<img src="s_times.png"' in html
    assert "Drift watch" in html
    # generated specs are real, re-renderable ScopePlot specs
    specs = sorted(os.listdir(out / "specs"))
    assert specs == ["s_speedup.yaml", "s_times.yaml", "s_trend.yaml"]
    for result in render_spec_dir(str(out / "specs"), force=True):
        assert result[2] == "rendered", result


def test_report_on_older_run_ignores_later_runs(tmp_path):
    """Reporting run r1 after r2 was recorded must compare r1 against
    the runs *before* it — never present r2-vs-window data as r1's."""
    results = fixture_run_dir(tmp_path).parent
    run1 = results / "r1"
    run1.mkdir()
    (run1 / "merged.json").write_text(json.dumps(
        gb_doc("r1", {"s/a/n:1": 2.2, "s/a/n:2": 4.0},
               date="2026-07-30T00:00:00"), indent=2))
    paths = generate_run_report(str(run1))
    md = open(paths["md"]).read()
    # nothing recorded before r1: no speedup plot, no drift comparison
    assert "speedup" not in md
    assert "Needs at least two recorded runs" in md
    assert "`r2`" not in md.split("## Drift watch")[1]
    # the trend spec reads a materialized history *prefix* — r2 (recorded
    # after r1) must not appear in r1's trend plot
    trend = load_spec(str(run1 / "report" / "specs" / "s_trend.yaml"))
    scoped = hist.load_history(os.path.join(
        str(run1 / "report" / "specs"), trend["series"][0]["input_file"]))
    assert hist.run_ids(scoped) == ["r1"]


def test_grouped_bar_keeps_duplicate_categories(tmp_path):
    """An x category repeated within one series is disambiguated, not
    silently collapsed to the last value."""
    doc = gb_doc("r", {"s/a/n:1": 2.0, "s/a/n:2": 4.0,
                       "s/b/n:1": 3.0, "s/b/n:2": 5.0})
    src = tmp_path / "r.json"
    src.write_text(json.dumps(doc))
    from repro.scopeplot.plot import _draw_grouped_bar
    import matplotlib.pyplot as plt
    fig, ax = plt.subplots()
    _draw_grouped_bar(ax, {"series": [{"input_file": str(src),
                                       "xfield": "n",
                                       "yfield": "real_time"}]}, ".")
    labels = [t.get_text() for t in ax.get_xticklabels()]
    plt.close(fig)
    assert labels == ["1", "2", "1 (2)", "2 (2)"]


def test_history_report(tmp_path):
    run_dir = fixture_run_dir(tmp_path)
    results = run_dir.parent
    paths = generate_history_report(str(results / "history.jsonl"))
    md = open(paths["md"]).read()
    assert "| r1 |" in md and "| r2 |" in md
    assert (results / "report" / "s_trend.png").exists()


def test_report_main_cli(tmp_path, capsys):
    run_dir = fixture_run_dir(tmp_path)
    results = str(run_dir.parent)
    assert report_main(["r2", "--results-dir", results]) == 0
    out = capsys.readouterr().out.splitlines()
    assert out[0].endswith("index.html") and out[1].endswith("report.md")
    assert report_main(["history", "--results-dir", results]) == 0
    capsys.readouterr()
    # unknown run: error names the known runs
    assert report_main(["nope", "--results-dir", results]) == 2
    assert "r2" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# spec round-trip: every plot type through dump → load_spec → render
# ---------------------------------------------------------------------------

def _spec_for(ptype, src, history_file):
    spec = {"title": f"t-{ptype}", "type": ptype,
            "series": [{"label": "a", "input_file": src,
                        "xfield": "n", "yfield": "real_time"}]}
    if ptype == "speedup":
        spec["baseline"] = {"input_file": src}
    if ptype == "timeseries":
        spec["series"] = [{"label": "a", "input_file": history_file,
                           "regex": "^s/"}]
    return spec


@pytest.mark.parametrize("ptype", PLOT_TYPES)
def test_spec_roundtrip_each_plot_type(tmp_path, ptype):
    run_dir = fixture_run_dir(tmp_path)
    src = str(run_dir / "merged.json")
    history_file = str(run_dir.parent / "history.jsonl")
    spec = _spec_for(ptype, src, history_file)
    spec["output"] = str(tmp_path / f"{ptype}.png")
    spec_path = tmp_path / f"{ptype}.yaml"
    spec_path.write_text(yaml.safe_dump(spec))
    loaded = load_spec(str(spec_path))
    assert loaded["type"] == ptype
    out = render_spec(loaded)
    assert os.path.exists(out) and os.path.getsize(out) > 0


# ---------------------------------------------------------------------------
# load_spec error contract (documented in docs/scopeplot.md)
# ---------------------------------------------------------------------------

def _write_spec(tmp_path, text):
    p = tmp_path / "spec.yaml"
    p.write_text(text)
    return str(p)


def test_load_spec_unknown_type_line_numbered(tmp_path):
    p = _write_spec(tmp_path,
                    "title: x\ntype: pie\nseries:\n  - input_file: r.json\n")
    with pytest.raises(SpecError) as e:
        load_spec(p)
    assert f"{p}:2: " in str(e.value)
    assert "unknown plot type 'pie'" in str(e.value)
    for t in PLOT_TYPES:
        assert t in str(e.value)           # error lists the valid types
    assert isinstance(e.value, ValueError)  # old except clauses still work


def test_load_spec_output_and_series_validation(tmp_path):
    p = _write_spec(tmp_path, "type: line\noutput: [a, b]\n"
                              "series:\n  - input_file: r.json\n")
    with pytest.raises(SpecError, match=r"spec\.yaml:2: 'output'"):
        load_spec(p)
    p = _write_spec(tmp_path, "title: x\ntype: line\n")
    with pytest.raises(SpecError, match="non-empty 'series'"):
        load_spec(p)
    p = _write_spec(tmp_path, "type: line\nseries:\n  - label: a\n")
    with pytest.raises(SpecError, match=r"series\[0\] needs an 'input_file'"):
        load_spec(p)
    p = _write_spec(tmp_path, "type: speedup\nseries:\n"
                              "  - input_file: r.json\n")
    with pytest.raises(SpecError, match="needs a 'baseline'"):
        load_spec(p)
    p = _write_spec(tmp_path, "[1, 2]\n")
    with pytest.raises(SpecError, match="must be a YAML mapping"):
        load_spec(p)


def test_load_spec_invalid_yaml(tmp_path):
    p = _write_spec(tmp_path, "type: line\n  bad indent: [\n")
    with pytest.raises(SpecError, match="invalid YAML"):
        load_spec(p)


# ---------------------------------------------------------------------------
# batch mode: only stale specs re-render
# ---------------------------------------------------------------------------

def test_batch_renders_only_stale(tmp_path):
    run_dir = fixture_run_dir(tmp_path)
    src = run_dir / "merged.json"
    specs = tmp_path / "specs"
    specs.mkdir()
    for name in ("one", "two"):
        spec = {"type": "bar", "output": f"{name}.png",
                "series": [{"input_file": str(src), "xfield": "n",
                            "yfield": "real_time"}]}
        (specs / f"{name}.yaml").write_text(yaml.safe_dump(spec))
    first = render_spec_dir(str(specs))
    assert [s for _, _, s in first] == ["rendered", "rendered"]
    second = render_spec_dir(str(specs))
    assert [s for _, _, s in second] == ["fresh", "fresh"]
    # touching one data dependency makes only dependents stale
    future = os.path.getmtime(specs / "one.png") + 60
    os.utime(src, (future, future))
    spec = load_spec(str(specs / "one.yaml"))
    assert is_stale(str(specs / "one.yaml"), spec)
    third = render_spec_dir(str(specs))
    assert [s for _, _, s in third] == ["rendered", "rendered"]
    # a broken spec reports an error but doesn't stop the batch
    (specs / "zz.yaml").write_text("type: pie\nseries: []\n")
    results = render_spec_dir(str(specs), force=True)
    assert [s.split(":")[0] for _, _, s in results] == \
        ["rendered", "rendered", "error"]


# ---------------------------------------------------------------------------
# end-to-end: two orchestrated runs → trend plot shows both
# ---------------------------------------------------------------------------

def test_two_runs_then_report_shows_trend(tmp_path):
    from repro.core.flags import FlagRegistry
    from repro.core.hooks import HookChain
    from repro.core.orchestrate import OrchestratorOptions, execute
    from repro.core.registry import BenchmarkRegistry
    from repro.core.runner import RunOptions
    from repro.core.scope import ScopeManager

    results = str(tmp_path / "results")
    for rid in ("e1", "e2"):
        mgr = ScopeManager(registry=BenchmarkRegistry(),
                           flags=FlagRegistry(), hooks=HookChain())
        mgr.load(["repro.scopes.example_scope"])
        mgr.register_all()
        execute(mgr, mgr.registry, OrchestratorOptions(
            jobs=1, isolate="inline", shard_grain="benchmark",
            run=RunOptions(min_time=0.002), results_dir=results,
            run_id=rid))
    paths = generate_run_report(os.path.join(results, "e2"))
    md = open(paths["md"]).read()
    assert "history: 2 recorded run(s)" in md
    assert "![example: trend across runs](example_trend.png)" in md
    assert "![example: speedup vs previous run](example_speedup.png)" in md
    out = os.path.join(results, "e2", "report")
    assert os.path.getsize(os.path.join(out, "example_trend.png")) > 0
    # the trend spec reads the real history store with both runs in it
    trend = load_spec(os.path.join(out, "specs", "example_trend.yaml"))
    history_file = os.path.join(
        out, "specs", trend["series"][0]["input_file"])
    records = hist.load_history(history_file)
    assert hist.run_ids(records) == ["e1", "e2"]


# ---------------------------------------------------------------------------
# latency counters: verdict columns and the CDF page are strictly opt-in
# ---------------------------------------------------------------------------

def latency_run_dir(tmp_path):
    """A serve-scope run whose records carry the latency meter's
    counters (plus a plain r1 so the trend machinery has history)."""
    results = tmp_path / "results"
    run_dir = results / "r2"
    run_dir.mkdir(parents=True)
    doc1 = gb_doc("r1", {"serve/load/arrival:poisson": 2.2},
                  date="2026-07-30T00:00:00")
    doc2 = gb_doc("r2", {"serve/load/arrival:poisson": 2.0,
                         "serve/load/arrival:bursty": 2.4})
    for rec in doc2["benchmarks"]:
        rec.update({"latency_p50_s": 0.010, "latency_p90_s": 0.020,
                    "latency_p99_s": 0.050, "latency_p999_s": 0.090,
                    "goodput_rps": 31.5, "slo_attainment": 1.0})
    hist.append_run(str(results), doc1)
    hist.append_run(str(results), doc2)
    (run_dir / "merged.json").write_text(json.dumps(doc2, indent=2))
    return run_dir


def test_report_without_latency_counters_omits_latency_columns(tmp_path):
    """The pre-latency report shape is untouched (the golden test pins
    it byte-for-byte; this states the property directly)."""
    run_dir = fixture_run_dir(tmp_path)
    paths = generate_run_report(str(run_dir))
    md = open(paths["md"]).read()
    assert "p99 latency" not in md
    assert "goodput" not in md
    assert "latency" not in "".join(
        os.listdir(os.path.join(str(run_dir), "report", "specs")))


def test_report_with_latency_counters_adds_columns_and_cdf(tmp_path):
    run_dir = latency_run_dir(tmp_path)
    paths = generate_run_report(str(run_dir))
    md = open(paths["md"]).read()
    assert "| p99 latency | goodput |" in md
    assert "31.5 req/s" in md
    assert "serve_latency.png" in md
    out = run_dir / "report"
    assert (out / "serve_latency.png").exists()
    assert (out / "specs" / "serve_latency.yaml").exists()
    # the emitted spec is a real, re-renderable ScopePlot spec
    for result in render_spec_dir(str(out / "specs"), force=True):
        assert result[2] == "rendered", result
