"""BENCHMARK.json against the rules a benchmark file is held to, and
every cell, configuration and metric it names against the files the
harness finds them by."""
import json
import os
import re

import pytest

from bench import harness

ROOT = harness.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_shape(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["bench"]
    assert 1 <= bench["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert runs * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    for part, keys in KEYS.items():
        names = [e["name"] for e in bench[part]]
        assert len(names) == len(set(names)), part
        for e in bench[part]:
            extra = {"workloads"} if part in ("end_to_end",
                                              "per_layer") else set()
            assert keys <= set(e) <= keys | extra, e
            assert NAME.match(e["name"]), e["name"]
            if "unit" in e:
                assert UNIT.match(e["unit"]) and e["better"] in (
                    "lower", "higher")
            for k in ("why", "layer", "source"):
                if k in e:
                    assert _line(e[k]), e[k]


def test_configs(bench):
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert c["name"] in used
        assert c["file"] == f"bench/configs/{c['name']}.json"
        with open(os.path.join(ROOT, c["file"])) as f:
            model = json.load(f)
        assert model["name"] == c["name"] and model["source"] == c["source"]
        assert model["reduced"] == c["reduced"]
        assert all(k in model for k in c["reduced"])
        assert os.path.exists(os.path.join(
            ROOT, "bench", "families", f"{model['family']}.py"))


def test_cells_and_metrics(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25 and "workloads" not in e2e[
        "setup_s"]
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)
    pairs = {(w["config"], w["traffic"]) for w in bench["workloads"]}
    assert len(pairs) == len(bench["workloads"])
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        assert (cell["config"], cell["traffic"], cell["chips"], cell["why"]) \
            == (w["config"], w["traffic"], w["chips"], w["why"])
        assert os.path.exists(os.path.join(
            ROOT, "bench", "drivers", f"{cell['kind']}.py"))
        reported, layer = harness.declared(w["name"], bench)
        names = {m["name"] for m in reported}
        assert "setup_s" in names and len(names) >= 2, w["name"]
        assert layer, w["name"]
        for m in layer:
            assert m["moves"] in names, (w["name"], m["name"])
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert os.path.exists(os.path.join(
            ROOT, "bench", "metrics", f"{m['name']}.py")), m["name"]
    layers = {m["layer"] for m in bench["per_layer"]}
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for name in layers:
        assert name in perf, name


def test_size(bench):
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536
