"""A whole run of a small cell with the timed path broken underneath:
``correct`` has to come out false for each fault the cell can have, and
true with nothing broken.  The look for a chip is skipped (the devices
are the CPU's)."""
import jax
import pytest

from bench import harness
from bench.drivers import serve as serve_driver
from bench.tests import cells

SEED = 2**33 + 3


@pytest.fixture(autouse=True)
def cpu_peaks(monkeypatch):
    monkeypatch.setattr(serve_driver, "peaks", lambda device: cells.PEAKS)


def run(cell, trace=False):
    return harness.run_cell(cell, SEED, 2.0, trace, jax.devices()[:1],
                            t_start=0.0, benchmark=cells.benchmark())


@pytest.mark.parametrize("make,trace,metrics", [
    (cells.serve_cell, False, {"itl_p99_ms", "serve_tok_s", "setup_s"}),
    # the CPU's trace holds no device: the serve cell's per-layer metrics
    # all read the device, and are left out
    (cells.serve_cell, True, set()),
    (cells.train_cell, True, {"data_wait_ms.train", "mfu.train"}),
])
def test_sound_run_is_correct(make, trace, metrics):
    out = run(make(), trace=trace)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == metrics, out


def test_altered_token_is_not_correct(monkeypatch):
    from repro.serve.engine import ServeEngine

    decode = ServeEngine._decode_step

    def altered(self):
        done = decode(self)
        live = [r for r in self.slots if r is not None] + done
        if live and len(live[0].output) == 3:
            req = live[0]
            req.output[-1] = (req.output[-1] + 1) % 256
        return done

    monkeypatch.setattr(ServeEngine, "_decode_step", altered)
    out = run(cells.serve_cell())
    assert not out["correct"], out["checks"]


def _wrap_step(monkeypatch, wrap):
    import repro.launch.train as launch

    fns = launch.sharded_train_fns

    def patched(*a, **kw):
        structs, shardings, init, step = fns(*a, **kw)
        return structs, shardings, init, wrap(step)

    monkeypatch.setattr(launch, "sharded_train_fns", patched)


def test_unchanged_state_is_not_correct(monkeypatch):
    def wrap(step):
        inner = step.__wrapped__ if hasattr(step, "__wrapped__") else step

        @jax.jit
        def unchanged(state, batch):
            _, metrics = inner(state, batch)
            return state, metrics
        return unchanged

    _wrap_step(monkeypatch, wrap)
    out = run(cells.train_cell())
    assert not out["correct"], out["checks"]


def test_half_batch_is_not_correct(monkeypatch):
    def wrap(step):
        def half(state, batch):
            n = batch["tokens"].shape[0] // 2
            return step(state, {k: v[:n] for k, v in batch.items()})
        return half

    _wrap_step(monkeypatch, wrap)
    out = run(cells.train_cell())
    assert not out["correct"], out["checks"]


def _serve_control(monkeypatch):
    """The served tokens scored as the fp8 control's first choices."""
    from bench.reference import transformer as ref

    gaps = ref.served_gaps

    def control(cfg, seed, served, control=False):
        _, ctl = gaps(cfg, seed, served, control=True)
        return ctl, ctl

    monkeypatch.setattr(ref, "served_gaps", control)
    return cells.serve_cell()


def _train_control(monkeypatch):
    """The first steps' readings taken from the fp8 control."""
    from bench.drivers import train as train_driver

    setup = train_driver.Run.setup

    def control(self):
        setup(self)
        ctl = self.readings("fp8")
        self.losses, self.grad, self.change = (ctl["loss"], ctl["grad"],
                                               ctl["change"])

    monkeypatch.setattr(train_driver.Run, "setup", control)
    return cells.train_cell()


@pytest.mark.parametrize("make", [_serve_control, _train_control])
def test_control_in_the_programs_place_is_not_correct(monkeypatch, make):
    out = run(make(monkeypatch))
    assert not out["correct"], out["checks"]
