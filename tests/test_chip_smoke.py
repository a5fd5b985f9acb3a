"""chip_smoke.py's phases on the CPU at reduced sizes.

The script proves the main paths on a TPU; these tests run the same phase
functions here, so a broken path or a bad argument shows before any chip
time is spent.  Also: ``repro run`` exits non-zero when an instance
errors, which the run-stage phase relies on.
"""
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def cli_state():
    """A pristine process-global registry/flags/hooks for an in-process
    ``repro run``, restored afterwards (other tests load scopes too)."""
    from repro.core.flags import FLAGS
    from repro.core.hooks import HOOKS
    from repro.core.registry import REGISTRY
    specs, values = dict(FLAGS._specs), dict(FLAGS._values)
    benches = dict(REGISTRY._benchmarks)
    hooks = (list(HOOKS._pre_parse), list(HOOKS._post_parse))
    for name, spec in specs.items():
        if spec.owner != "core":
            del FLAGS._specs[name]
    REGISTRY._benchmarks.clear()
    yield
    FLAGS._specs.clear(), FLAGS._specs.update(specs)
    FLAGS._values.clear(), FLAGS._values.update(values)
    REGISTRY._benchmarks.clear(), REGISTRY._benchmarks.update(benches)
    HOOKS._pre_parse[:], HOOKS._post_parse[:] = hooks


ERRING_SCOPE = '''\
from repro.core import Scope, State, benchmark


def _register(registry):
    @benchmark(scope="erring", registry=registry)
    def fine(state: State):
        x = 0.0
        while state.keep_running():
            x = state.deliver(x + 1.0)
    fine.set_sync(lambda ctx: None)

    @benchmark(scope="erring", registry=registry)
    def broken(state: State):
        raise RuntimeError("body failed")


SCOPE = Scope(name="erring", register=_register)
'''


@pytest.fixture
def erring_scope(tmp_path, monkeypatch):
    (tmp_path / "erring_scope_mod.py").write_text(ERRING_SCOPE)
    monkeypatch.syspath_prepend(str(tmp_path))
    yield "erring_scope_mod"
    sys.modules.pop("erring_scope_mod", None)


def test_repro_run_exits_nonzero_when_an_instance_errors(
        cli_state, erring_scope, tmp_path):
    from repro.core.main import run_main
    out = tmp_path / "doc.json"
    rc = run_main(["--results-dir", str(tmp_path / "r"),
                   "--benchmark_min_time", "0.001",
                   "--benchmark_out", str(out)],
                  scope_modules=[erring_scope])
    assert rc == 1
    doc = json.loads(out.read_text())
    ok = [r for r in doc["benchmarks"] if not r.get("error_occurred")]
    assert [r["name"] for r in ok] == ["erring/fine"]   # isolation kept


def test_run_stage_phase_passes_a_clean_scope(smoke, cli_state, tmp_path,
                                              capsys):
    detail = smoke.run_stage(str(tmp_path / "results"), min_time=0.001,
                             scopes=["example"])
    assert "none failed" in detail
    assert "skipped: none" in capsys.readouterr().out


def test_run_stage_phase_fails_on_an_erroring_instance(
        smoke, cli_state, erring_scope, tmp_path, monkeypatch):
    from repro.core import main as core_main
    real = core_main.main
    monkeypatch.setattr(core_main, "main", lambda argv: real(
        argv, scope_modules=[erring_scope]))
    with pytest.raises(smoke.PhaseFailed, match="erring/broken"):
        smoke.run_stage(str(tmp_path / "results"), min_time=0.001)


def test_kernels_phase_refuses_interpret_mode(smoke):
    """On the CPU every Pallas op runs interpreted: the phase that proves
    the chip runs Mosaic kernels must notice."""
    with pytest.raises(smoke.PhaseFailed, match="interpret mode"):
        smoke.kernels()


def test_serve_phase_reduced(smoke):
    detail = smoke.serve(reduced=True, n_requests=4, max_tokens=8)
    assert "4 requests x 8 tokens" in detail


def test_train_phase_reduced(smoke):
    detail = smoke.train(reduced=True, layers=2, batch=2, seq=64, steps=2)
    assert "depth cut to 2 of 28 layers" in detail


def test_sharded_train_step_phase_on_four_cpu_devices():
    """The --chips 4 phase on four virtual CPU devices: the 2x2 mesh, the
    sharding rules and the comparison, in a process of its own (the
    device count is fixed when JAX starts)."""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = textwrap.dedent(f"""
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", {os.path.join(ROOT, "chip_smoke.py")!r})
        cs = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(cs)
        print(cs.sharded_train_step(reduced=True, layers=2, batch=4,
                                    seq=32))
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "2x2: state bytes per device 0:" in r.stdout
    assert "max param diff" in r.stdout


def test_main_refuses_without_a_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_script_alone_fails_without_the_repo(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
