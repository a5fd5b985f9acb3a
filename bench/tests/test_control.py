"""The control: the reference put in the program's place and computed in
fp8, one step below the bfloat16 that the configurations state.  At the
cells' sizes on the chip its readings set each limit's upper end
(PERF.md); here, at a small size, it has to read well above the
program's readings, or the numbers could not tell the two apart."""
import jax

from bench import calibrate
from bench.tests import cells

SEEDS = (2**33 + 11, 5)


def test_serve_control_reads_far_above_the_program():
    for seed in SEEDS:
        r = calibrate.serve_readings(cells.serve_cell(), seed, 2.0, True,
                                     jax.devices()[:1])
        assert r["control_logit_gap"] >= 3 * r["logit_gap"], r


def test_train_control_reads_far_above_the_program():
    for seed in SEEDS:
        r = calibrate.train_readings(cells.train_cell(), seed, True,
                                     jax.devices()[:1])
        assert r["control_grad_gap_mean"] >= 3 * r["grad_gap_mean"], r
        assert r["control_change_gap_mean"] >= 3 * r["change_gap_mean"], r
