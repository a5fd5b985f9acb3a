"""Read the numbers that decide ``correct`` over many seeds, for the
program and for its control, to set each limit from (PERF.md gives the
readings and the limits).  Not part of a benchmark run.

    python3 bench/calibrate.py --workload <cell> --seeds 11,12,13 \\
        [--control-seeds 11,12,13] [--seconds 20]

One process for all seeds.  A serve cell runs a short window at its own
load for each seed, then scores the served tokens against the reference
and, with the control, the tokens the fp8 reference would have put first
at the same positions.  A train cell drives its first steps and compares
them with the float32 reference and, with the control, the fp8 reference
with the float32 one.  One JSON line a seed.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# libtpu logs to /tmp/tpu_logs unless told otherwise: write nothing there
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def serve_readings(cell, seed, seconds, control, devs):
    from bench.drivers.serve import Run

    run = Run(cell, seed, seconds, devs)
    run.setup()
    run.window(seconds, None)
    run.end_to_end()
    run.release()
    gc.collect()
    run.check(control=control)
    out = {"logit_gap": run.readings["logit_gap"],
           "tokens": run.readings["tokens"]}
    if control:
        out["control_logit_gap"] = run.readings["control_gap"]
    return out


def train_readings(cell, seed, control, devs):
    import statistics

    from bench.drivers.train import STILL_LEAF, Run, compare

    run = Run(cell, seed, 0, devs)
    run.setup()
    got = {"loss": run.losses, "grad": run.grad, "change": run.change}
    run.release()
    gc.collect()
    ref = run.readings()
    med = statistics.median(ref["grad"].values())
    out = dict(compare(got, ref), losses=run.losses, ref_losses=ref["loss"],
               still_leaves=[k for k, g in ref["grad"].items()
                             if g < STILL_LEAF * med],
               leaves=leaf_gaps(got, ref))
    if control:
        ctl = run.readings("fp8")
        out.update({f"control_{k}": v
                    for k, v in compare(ctl, ref).items()})
        out["control_leaves"] = leaf_gaps(ctl, ref)
    return out


def leaf_gaps(got, ref):
    """Each step's relative loss gap, and each leaf's gap of the first
    gradient's and of the change's norm: to see which leaves set each
    number."""
    from bench.drivers.train import leaf_gaps as gaps

    return {"loss": [abs(a - b) / abs(b)
                     for a, b in zip(got["loss"], ref["loss"])],
            "grad": gaps(got["grad"], ref["grad"]),
            "change": gaps(got["change"], ref["change"])}


def main() -> int:
    from bench.harness import configure_jax, devices, load_cell

    ap = argparse.ArgumentParser(prog="bench/calibrate.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=20)
    args = ap.parse_args()
    cell = load_cell(args.workload)
    devs = devices(cell["chips"])
    configure_jax()
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in [int(s) for s in args.seeds.split(",")]:
        if cell["kind"] == "serve":
            out = serve_readings(cell, seed, args.seconds, seed in controls,
                                 devs)
        else:
            out = train_readings(cell, seed, seed in controls, devs)
        print(json.dumps({"seed": seed, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
