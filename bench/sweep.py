"""Find the knee of a serve cell's traffic: the highest arrival rate
before the throughput levels off and the time to the first token jumps.
Run once, on the chip, when a serve cell is made; the cell's file then
holds the rate it runs at and its pre-roll as numbers.

    python3 bench/sweep.py --workload internlm2-serve-chat \
        --rates 0.6,0.8,1.0 --seeds 7,8 --seconds 60

One process: the cell is set up once, then each rate and seed runs an
open-loop window of its own from an empty engine.  One JSON line each:
requests that arrived and finished, tokens per second, TTFT and
inter-token percentiles, the backlog (requests waiting) at the window's
end, the 90th percentile of TTFT over the first and the second half of
the arrivals (a backlog that grows shows as a second half far above the
first), and the mean number of busy slots over the second half of the
window: the steady state that the cell's pre-roll stands for.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# libtpu logs to /tmp/tpu_logs unless told otherwise: write nothing there
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def sweep(cell, rates, seconds, seeds, devs, log=sys.stderr):
    from bench.drivers.serve import Run
    from bench.stats import percentile
    from bench.traffic.generate import serve_schedule

    run = Run(cell, seeds[0], seconds, devs, log=log)
    run.setup()
    eng = run.engine
    busy = []                       # (time after a step, busy slots)
    step = run._step

    def counted(traced=False):
        step(traced)
        busy.append((time.perf_counter(),
                     sum(s is not None for s in eng.slots)))
    run._step = counted
    for rate in rates:
        for seed in seeds:
            eng.queue.clear()
            eng.slots = [None] * eng.cfg.max_batch
            run.requests, run.stamps, run.late = [], {}, []
            busy.clear()
            run.schedule = serve_schedule(cell["mix"], rate, seconds, seed)
            run.window(seconds, None)
            e2e = run.end_to_end()
            arrived = [(at, run.stamps[r.uid]) for r, at in run.requests]
            half = len(arrived) // 2
            ttft = [(st[0] if st and st[0] <= run.t_close else run.t_close)
                    - at for at, st in arrived]
            mid = run.t0 + seconds / 2
            late = [(b[0] - a[0], a[1]) for a, b in zip(busy, busy[1:])
                    if a[0] >= mid]
            out = {"rate": rate, "seed": seed, "arrived": len(arrived),
                   "finished": sum(1 for r, _ in run.requests
                                   if r.done_at is not None),
                   "waiting_at_end": len(eng.queue),
                   "busy_slots_second_half": (
                       sum(dt * n for dt, n in late)
                       / max(sum(dt for dt, _ in late), 1e-9)),
                   "ttft_p90_first_half_ms":
                       1e3 * percentile(ttft[:half], 0.9),
                   "ttft_p90_second_half_ms":
                       1e3 * percentile(ttft[half:], 0.9),
                   "ttft_p50_ms": 1e3 * percentile(ttft, 0.5), **e2e}
            print(json.dumps(out), flush=True)
    run.release()


def main() -> int:
    from bench.harness import configure_jax, devices, load_cell

    ap = argparse.ArgumentParser(prog="bench/sweep.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated arrival rates, requests/s")
    ap.add_argument("--seconds", type=float, default=30)
    ap.add_argument("--seeds", default="7",
                    help="comma-separated seeds, each run at every rate")
    args = ap.parse_args()
    cell = load_cell(args.workload)
    devs = devices(cell["chips"])
    configure_jax()
    sweep(cell, [float(r) for r in args.rates.split(",")], args.seconds,
          [int(s) for s in args.seeds.split(",")], devs)
    return 0


if __name__ == "__main__":
    sys.exit(main())
