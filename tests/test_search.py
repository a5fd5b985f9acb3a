"""repro.core.search + repro.kernels.tuning: the tuner's search
strategies on a deterministic quadratic bowl (no measurement, no jax
arrays), and the tuned-default registry's precedence/validation
contract.  A small end-to-end `repro tune` run closes the loop."""
import json
import os

import pytest

from repro.core import ParamSpace, Params
from repro.core.search import (STRATEGIES, Trial, TrialError,
                               lower_is_better, oriented, pareto_front,
                               run_search, screening_plan)
from repro.kernels import tuning

# ---------------------------------------------------------------------------
# a deterministic 3-axis quadratic bowl: axis `a` dominates the
# objective, `b` matters less, `c` barely — minimum at (4, 8, 2)
# ---------------------------------------------------------------------------

BOWL = ParamSpace.product(a=[1, 2, 3, 4, 5],
                          b=[2, 4, 8, 16],
                          c=[1, 2, 3])


def bowl_eval(p):
    return {"real_time_s": (100.0 * (p.a - 4) ** 2
                            + 1.0 * (p.b - 8) ** 2
                            + 0.01 * (p.c - 2) ** 2
                            + 0.5)}


BOWL_MIN = {"a": 4, "b": 8, "c": 2}


def trial_keys(result):
    return [t.params.canonical() for t in result.trials]


# ---------------------------------------------------------------------------
# screening
# ---------------------------------------------------------------------------

def test_screening_plan_is_center_plus_axis_extremes():
    plan = screening_plan(BOWL)
    labels = [label for label, _ in plan]
    assert labels[0] == "center"
    # center = per-axis median value
    assert dict(plan[0][1]) == {"a": 3, "b": 4, "c": 2}
    # two extreme variants per axis (none collide with the center here)
    assert labels[1:] == ["a", "a", "b", "b", "c", "c"]
    for label, params in plan[1:]:
        assert params[label] in (min(BOWL.points(), key=lambda p: p[label])[label],
                                 max(BOWL.points(), key=lambda p: p[label])[label])


def test_screening_plan_respects_constraints():
    # prune exactly the geometric-center point (axis values unchanged)
    space = BOWL.where(lambda p: dict(p) != {"a": 3, "b": 4, "c": 2})
    plan = screening_plan(space)
    # falls back to the first in-space point, deterministically
    assert plan[0][1] == space.points()[0]
    members = {p.canonical() for p in space.points()}
    assert all(p.canonical() in members for _, p in plan)


def test_screening_ranks_most_sensitive_axis_first():
    result = run_search(BOWL, bowl_eval, strategy="screening", budget=7)
    axes = [axis for axis, _ in result.sensitivity]
    spans = [span for _, span in result.sensitivity]
    assert axes == ["a", "b", "c"]
    assert spans == sorted(spans, reverse=True)
    assert spans[0] > 100 * spans[2]


# ---------------------------------------------------------------------------
# hill-climb / auto
# ---------------------------------------------------------------------------

def test_auto_converges_to_the_bowl_minimum_within_budget():
    result = run_search(BOWL, bowl_eval, strategy="auto", budget=20, seed=0)
    assert result.best is not None
    assert dict(result.best.params) == BOWL_MIN
    assert result.best.metrics["real_time_s"] == pytest.approx(0.5)
    assert len(result.trials) <= 20


def test_hillclimb_only_converges_from_the_center():
    result = run_search(BOWL, bowl_eval, strategy="hillclimb", budget=30,
                        seed=1)
    assert dict(result.best.params) == BOWL_MIN


def test_budget_is_a_hard_ceiling_and_exhaustion_is_reported():
    result = run_search(BOWL, bowl_eval, strategy="auto", budget=3)
    assert len(result.trials) == 3
    assert result.exhausted
    full = run_search(BOWL, bowl_eval, strategy="screening", budget=50)
    assert not full.exhausted
    assert len(full.trials) == len(screening_plan(BOWL))


def test_cached_configs_do_not_consume_budget():
    calls = []

    def counting_eval(p):
        calls.append(p.canonical())
        return bowl_eval(p)

    result = run_search(BOWL, counting_eval, strategy="auto", budget=25)
    assert len(calls) == len(set(calls))          # never re-evaluated
    assert len(result.trials) == len(calls) <= 25


def test_same_seed_same_trial_sequence_different_seed_may_differ():
    a = run_search(BOWL, bowl_eval, strategy="auto", budget=12, seed=7)
    b = run_search(BOWL, bowl_eval, strategy="auto", budget=12, seed=7)
    assert trial_keys(a) == trial_keys(b)
    assert a.to_json() == b.to_json()


def test_rate_objectives_are_maximized():
    assert lower_is_better("real_time_s")
    assert not lower_is_better("flops_per_second")

    def rate_eval(p):
        return {"flops_per_second": float(p.a)}

    result = run_search(BOWL, rate_eval, objective="flops_per_second",
                        strategy="auto", budget=15, seed=0)
    assert result.best.params["a"] == 5


def test_trial_errors_consume_budget_and_are_recorded():
    def flaky(p):
        if p.a == 3:
            raise TrialError("boom")
        return bowl_eval(p)

    result = run_search(BOWL, flaky, strategy="screening", budget=7)
    errored = [t for t in result.trials if not t.ok]
    assert errored and all(t.error == "boom" for t in errored)
    assert result.best is not None
    assert result.best.params["a"] != 3


def test_everything_fails_yields_no_best():
    def always(p):
        raise TrialError("nope")

    result = run_search(BOWL, always, strategy="auto", budget=5)
    assert result.best is None
    assert all(not t.ok for t in result.trials)


def test_baseline_runs_first_when_in_space():
    base = Params({"a": 1, "b": 2, "c": 1})
    result = run_search(BOWL, bowl_eval, strategy="auto", budget=10,
                        baseline=base)
    assert result.baseline is not None
    assert result.baseline.index == 0
    assert result.trials[0].params.canonical() == base.canonical()


def test_cost_hints_steer_evaluation_order():
    plan = screening_plan(BOWL)
    expensive = plan[1][1].canonical()  # first a-extreme variant

    def hint(p):
        return 9.9 if p.canonical() == expensive else 0.1

    result = run_search(BOWL, bowl_eval, strategy="screening", budget=7,
                        cost_hint=hint)
    # the hinted-expensive variant is evaluated last of the variants
    assert trial_keys(result)[-1] == expensive


def test_invalid_strategy_and_budget_raise():
    with pytest.raises(ValueError):
        run_search(BOWL, bowl_eval, strategy="exhaustive")
    with pytest.raises(ValueError):
        run_search(BOWL, bowl_eval, budget=0)
    with pytest.raises(ValueError):
        run_search(ParamSpace.product(a=[1]).where(lambda p: False),
                   bowl_eval)
    assert set(STRATEGIES) == {"auto", "screening", "hillclimb"}


# ---------------------------------------------------------------------------
# pareto frontier
# ---------------------------------------------------------------------------

def _trial(i, time_s, rate=None, error=None):
    metrics = {} if error else {"real_time_s": time_s}
    if rate is not None and not error:
        metrics["flops_per_second"] = rate
    return Trial(index=i, phase="screen", params=Params({"a": i}),
                 metrics=metrics, error=error)


def test_pareto_front_is_orientation_aware():
    trials = [
        _trial(0, 1.0, rate=10.0),   # fast, slow rate — on the front
        _trial(1, 2.0, rate=20.0),   # slower but higher rate — on front
        _trial(2, 2.0, rate=5.0),    # dominated by 0 (and 1)
        _trial(3, 3.0, rate=20.0),   # dominated by 1
        _trial(4, 9.9, error="x"),   # failed — excluded
        _trial(5, 4.0),              # missing the rate — excluded
    ]
    front = pareto_front(trials, ["real_time_s", "flops_per_second"])
    assert [t.index for t in front] == [0, 1]


def test_pareto_front_single_objective_is_the_argmin():
    trials = [_trial(0, 3.0), _trial(1, 1.0), _trial(2, 2.0)]
    front = pareto_front(trials, ["real_time_s"])
    assert [t.index for t in front] == [1]


def test_oriented_scores():
    t = _trial(0, 2.0, rate=8.0)
    assert oriented("real_time_s", t) == 2.0
    assert oriented("flops_per_second", t) == -8.0
    assert oriented("missing_metric", t) == float("inf")
    assert oriented("real_time_s", _trial(1, 0, error="x")) == float("inf")


# ---------------------------------------------------------------------------
# tuned-default registry (repro.kernels.tuning)
# ---------------------------------------------------------------------------

@pytest.fixture
def tuned_dir(tmp_path, monkeypatch):
    monkeypatch.setenv(tuning.DIR_ENV, str(tmp_path))
    monkeypatch.delenv(tuning.DISABLE_ENV, raising=False)
    for kernel in tuning.kernels():
        for knob in tuning.KERNEL_KNOBS[kernel]:
            monkeypatch.delenv(
                f"REPRO_TUNED_{kernel.upper()}_{knob.upper()}",
                raising=False)
    tuning.invalidate_cache()
    yield tmp_path
    tuning.invalidate_cache()


def test_resolve_builtin_when_nothing_tuned(tuned_dir):
    assert tuning.resolve("matmul") == tuning.BUILTIN_DEFAULTS["matmul"]


def test_resolve_precedence_chain(tuned_dir, monkeypatch):
    # 4. artifact beats builtin
    tuning.write_tuned("matmul", {"config": {"bm": 128, "bn": 64, "bk": 32}})
    assert tuning.resolve("matmul") == {"bm": 128, "bn": 64, "bk": 32}
    # 3. env beats artifact (per knob)
    monkeypatch.setenv("REPRO_TUNED_MATMUL_BM", "256")
    assert tuning.resolve("matmul")["bm"] == 256
    assert tuning.resolve("matmul")["bn"] == 64
    # 2. override beats env
    with tuning.override("matmul", {"bm": 64}):
        assert tuning.resolve("matmul")["bm"] == 64
        # 1. explicit kwarg beats override
        assert tuning.resolve("matmul", bm=32)["bm"] == 32
    # override is restored on exit
    assert tuning.resolve("matmul")["bm"] == 256


def test_repro_tuned_off_disables_artifacts_only(tuned_dir, monkeypatch):
    tuning.write_tuned("rmsnorm", {"config": {"br": 1024}})
    assert tuning.resolve("rmsnorm") == {"br": 1024}
    monkeypatch.setenv(tuning.DISABLE_ENV, "off")
    assert tuning.resolve("rmsnorm") == tuning.BUILTIN_DEFAULTS["rmsnorm"]
    monkeypatch.setenv("REPRO_TUNED_RMSNORM_BR", "512")
    assert tuning.resolve("rmsnorm") == {"br": 512}    # env still applies


def test_non_integer_env_raises(tuned_dir, monkeypatch):
    monkeypatch.setenv("REPRO_TUNED_MATMUL_BM", "huge")
    with pytest.raises(ValueError, match="not an integer"):
        tuning.resolve("matmul")


def test_corrupt_artifact_degrades_to_builtin(tuned_dir):
    path = tuning.tuned_path("ssd_scan")
    os.makedirs(os.path.dirname(path))
    with open(path, "w") as fh:
        fh.write("{not json")
    assert tuning.resolve("ssd_scan") == tuning.BUILTIN_DEFAULTS["ssd_scan"]


def test_write_tuned_is_byte_deterministic(tuned_dir, tmp_path):
    payload = {"config": {"bq": 128, "bk": 256}, "kernel": "flash_attention",
               "objective": "real_time_s", "seed": 0}
    p1 = tuning.write_tuned("flash_attention", payload,
                            path=str(tmp_path / "one.json"))
    p2 = tuning.write_tuned("flash_attention", dict(reversed(payload.items())),
                            path=str(tmp_path / "two.json"))
    with open(p1, "rb") as a, open(p2, "rb") as b:
        assert a.read() == b.read()


def test_write_tuned_validates_payload(tuned_dir):
    with pytest.raises(ValueError, match="config"):
        tuning.write_tuned("matmul", {"kernel": "matmul"})
    with pytest.raises(ValueError, match="no knob"):
        tuning.write_tuned("matmul", {"config": {"tile": 8}})
    with pytest.raises(ValueError, match="unknown tunable kernel"):
        tuning.write_tuned("conv", {"config": {"bm": 8}})


def test_override_rejects_unknown_knobs():
    with pytest.raises(ValueError, match="no knob"):
        with tuning.override("rmsnorm", {"bm": 8}):
            pass


def test_validate_blocks_reports_every_problem():
    with pytest.raises(ValueError) as exc:
        tuning.validate_blocks("matmul", {"bm": 48, "bn": -1, "bk": 64},
                               dims={"bm": 128, "bn": 128, "bk": 128})
    msg = str(exc.value)
    assert "bm=48" in msg and "does not divide" in msg
    assert "bn=-1" in msg and "positive" in msg
    assert "bk=64" not in msg
    assert "repro tune" in msg            # remediation, not a stack trace


def test_validate_blocks_enforces_the_vmem_budget():
    limit = tuning.VMEM_LIMIT_BYTES
    with pytest.raises(ValueError, match="VMEM"):
        tuning.validate_blocks("matmul", {"bm": 128}, dims={"bm": 128},
                               vmem_bytes=limit + 1.0)
    tuning.validate_blocks("matmul", {"bm": 128}, dims={"bm": 128},
                           vmem_bytes=float(limit))


# ---------------------------------------------------------------------------
# end-to-end: `python -m repro tune` on the real mxu/matmul family
# ---------------------------------------------------------------------------

def tune_cli(args):
    """One tune_main call against a pristine global registry (the
    process-global REGISTRY would otherwise accumulate registrations
    across calls and collide) with FLAGS snapshotted."""
    from repro.core.flags import FLAGS
    from repro.core.registry import REGISTRY
    from repro.core.tune import tune_main
    specs, values = dict(FLAGS._specs), dict(FLAGS._values)
    saved = dict(REGISTRY._benchmarks)
    REGISTRY._benchmarks.clear()
    try:
        return tune_main(args)
    finally:
        REGISTRY._benchmarks.clear()
        REGISTRY._benchmarks.update(saved)
        FLAGS._specs.clear(), FLAGS._specs.update(specs)
        FLAGS._values.clear(), FLAGS._values.update(values)


def test_tune_cli_end_to_end(tuned_dir, tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    rc = tune_cli(["mxu/matmul", "--budget", "2", "--seed", "0",
                    "--strategy", "hillclimb", "--no-report",
                    "--results-dir", str(tmp_path / "results"),
                    "--run-id", "tunetest", "--enable-scope", "mxu",
                    "--benchmark_min_time", "0.001"])
    assert rc == 0
    artifact = json.load(open(tuning.tuned_path("matmul")))
    assert set(artifact["config"]) == {"bm", "bn", "bk"}
    assert artifact["source"]["family"] == "mxu/matmul"
    assert artifact["source"]["run_id"] == "tunetest"
    summary = json.load(open(tmp_path / "results" / "tunetest" / "tune.json"))
    assert summary["kernel"] == "matmul"
    assert summary["best"]["params"] == artifact["config"]
    assert len(summary["search"]["trials"]) <= 3  # budget + exempt baseline
    with open(tmp_path / "results" / "history.jsonl") as fh:
        records = [json.loads(line) for line in fh]
    assert records and all(r.get("tag") == "tune" for r in records)
    assert all(r["name"].startswith("tune/matmul/") for r in records)
    # the written artifact now *is* the kernel default
    tuning.invalidate_cache()
    assert tuning.resolve("matmul") == artifact["config"]


def test_tune_cli_list_and_bad_family(tuned_dir, capsys):
    assert tune_cli(["--list"]) == 0
    out = capsys.readouterr().out
    assert "mxu/matmul" in out and "nn/rmsnorm" in out
    assert tune_cli(["mxu/nope"]) == 1
    # the miss prints the tunable-family listing as a hint
    assert "mxu/matmul" in capsys.readouterr().out
