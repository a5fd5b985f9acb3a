"""Train cells: the program's sharded train step over its data pipeline.

Set-up builds one object, the step that ``launch/train.sharded_train_fns``
returns with the state its ``init`` makes from the seed, on a mesh of
exactly the cell's chips, and drives it through its first three steps
with batches from ``data.make_pipeline``: the window's own call and
feed, on rows that all differ.  From those steps it keeps what the
comparison needs: each loss, the norm of each leaf of the first gradient
as the optimizer got it (its first moment after one step, over
1 - beta1), and the norm of each leaf's change after the three steps.
The window then goes on with the same state and the same pipeline.

``check`` runs the reference through the same three batches from the
same seed and compares, each number relative to the reference's.
"""
from __future__ import annotations

import collections
import importlib
import math
import statistics
import sys
import time
from typing import Dict, List

from bench import trace as bench_trace
from bench.reference.common import leaf_norms, seed_key

#: Steps that set-up drives and the reference follows.
CHECK_STEPS = 3
#: Leaves whose reference gradient is under this share of the median
#: leaf's move under Adam by round-off alone; their change is not
#: compared.
STILL_LEAF = 1e-3
#: Seconds of steps the window keeps dispatched ahead of the one whose
#: loss it waits for, so that a host stall shorter than this leaves the
#: device busy.
AHEAD_S = 4.0


class Run:
    """One run of a train cell: set-up, the window, its results, the check."""

    def __init__(self, cell: Dict, seed: int, seconds: float, devs,
                 log=sys.stderr):
        # every driver takes the window's length; a train run needs it
        # only in its window
        self.cell, self.seed, self.devs, self.log = cell, seed, devs, log
        self.model = cell["config_file"]
        self.attempted = 0
        self.failed = 0
        self.done: List[float] = []         # when each step's loss arrived
        self.untraced_from = 0
        self.data_wait: List[float] = []
        self.ahead = 1                      # steps queued behind the running

    def program_config(self):
        family = importlib.import_module(
            f"bench.families.{self.model['family']}")
        return family.program_config(self.model, **self.cell.get("program", {}))

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp

        from repro.data import DataConfig, make_pipeline
        from repro.distributed.logical import default_rules, logical_rules
        from repro.launch.mesh import make_mesh
        from repro.launch.train import sharded_train_fns
        from repro.models import build
        from repro.train import AdamWConfig

        cfg = self.program_config()
        shape = tuple(self.cell.get("mesh", (len(self.devs), 1)))
        self.mesh = make_mesh(shape, ("data", "model"), devices=self.devs)
        self.opt = dict(self.cell["optimizer"])
        _, _, init, self.step = sharded_train_fns(
            cfg, AdamWConfig(**self.opt), self.mesh)
        self.rules = default_rules(cfg, self.mesh)
        mix = self.cell["mix"]
        self.batch, self.seq = self.cell["global_batch"], mix["seq_len"]
        self.pipe = make_pipeline(DataConfig(
            vocab_size=self.model["vocab_size"], seq_len=self.seq,
            global_batch=self.batch, seed=self.seed,
            mean_doc_len=mix["mean_doc_len"]))
        api = build(cfg)
        key = seed_key(self.seed)
        b1 = self.opt["b1"]
        with self.mesh, logical_rules(self.rules):
            self.state = init(key)
            self.first: List[Dict] = []
            self.losses: List[float] = []
            for s in range(CHECK_STEPS):
                t_step = time.perf_counter()
                _, batch = next(self.pipe)
                self.first.append(batch)
                self.state, metrics = self.step(
                    self.state, {k: jnp.asarray(v) for k, v in batch.items()})
                self.losses.append(float(metrics["loss"]))
                step_s = time.perf_counter() - t_step
                if s == 0:
                    self.grad = _floats(jax.jit(lambda m: leaf_norms(
                        jax.tree_util.tree_map(lambda a: a / (1 - b1), m)))(
                            self.state["opt"]["m"]))
            self.change = _floats(jax.jit(lambda p, k: leaf_norms(
                jax.tree_util.tree_map(lambda a, b: a - b, p, api.init(k))))(
                    self.state["params"], key))
        # the last checked step compiled nothing and was waited for alone
        self.ahead = max(1, math.ceil(AHEAD_S / step_s))
        print(f"train: {self.ahead} steps dispatched ahead "
              f"(a step {1e3 * step_s:.1f} ms alone)", file=self.log)

    def _dispatch(self):
        """Enqueue one step on the batch the pipeline gives; returns its
        loss, still on the device."""
        import jax
        import jax.numpy as jnp

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("next_batch"):
            _, batch = next(self.pipe)
        self.data_wait.append(time.perf_counter() - t0)
        with jax.profiler.TraceAnnotation("train_step"):
            self.state, metrics = self.step(
                self.state, {k: jnp.asarray(v) for k, v in batch.items()})
        return metrics["loss"]

    def _fetch(self, loss) -> None:
        """A step ends when its loss is on the host."""
        import jax

        with jax.profiler.TraceAnnotation("loss_fetch"):
            float(loss)
        self.done.append(time.perf_counter())

    def _steps(self, until=None, count=None) -> None:
        """Run steps until the clock passes ``until`` or ``count`` steps
        have been dispatched, keeping ``self.ahead`` steps queued behind
        the one whose loss it waits for (as a training loop that reads its
        losses late does), so that the host's work and its stalls overlap
        the device's steps; then sends nothing more and returns once every
        step it began has ended."""
        pending, n = collections.deque(), 0
        while (count is None or n < count) and (
                until is None or time.perf_counter() < until):
            pending.append(self._dispatch())
            n += 1
            if len(pending) > self.ahead:
                self._fetch(pending.popleft())
        while pending:
            self._fetch(pending.popleft())

    def window(self, seconds: float, trace_dir) -> None:
        import jax

        from repro.distributed.logical import logical_rules

        tr = self.cell["trace"]
        with self.mesh, logical_rules(self.rules):
            self.t0 = time.perf_counter()
            self.done = [self.t0]
            end = self.t0 + seconds
            if trace_dir:
                self._steps(until=end, count=tr["start_step"])
                bench_trace.start(trace_dir)
                with jax.profiler.TraceAnnotation("bench.window"):
                    self._steps(count=tr["steps"])
                jax.profiler.stop_trace()
                # the stretch after the trace, from the end of its first
                # step (the one that waited for the trace to be written)
                self._steps(count=1)
                self.untraced_from = len(self.done) - 1
            self._steps(until=end)
            self.t_close = self.done[-1]
        self.attempted = len(self.done) - 1
        gaps = sorted(b - a for a, b in zip(self.done, self.done[1:]))
        if gaps:
            print(f"train: {len(gaps)} steps; step ms median "
                  f"{1e3 * gaps[len(gaps) // 2]:.2f}, max {1e3 * gaps[-1]:.2f}",
                  file=self.log)

    def end_to_end(self) -> Dict[str, float]:
        tokens = (len(self.done) - 1) * self.batch * self.seq
        return {"train_tok_s": tokens / (self.t_close - self.t0)}

    def release(self) -> None:
        self.pipe.close()
        del self.state, self.step

    def readings(self, precision: str = "f32", batches=None) -> Dict:
        ref = importlib.import_module(
            f"bench.reference.{self.model['family']}")
        return ref.train_readings(self.model, self.opt, self.seed,
                                  batches or self.first, precision)

    def check(self) -> Dict:
        ref = self.readings()
        got = {"loss": self.losses, "grad": self.grad, "change": self.change}
        lim = self.cell["check"]
        return {name: {"value": value, "limit": lim[name]}
                for name, value in compare(got, ref).items() if name in lim}

    def context(self, trace) -> Dict:
        from bench.drivers.serve import peaks

        done = self.done[self.untraced_from:]
        tok_s = ((len(done) - 1) * self.batch * self.seq
                 / (done[-1] - done[0]) if len(done) > 1 else None)
        return {"trace": trace, "model": self.model, "chips": len(self.devs),
                "peaks": peaks(self.devs[0]), "seq_len": self.seq,
                "train_tok_s": tok_s, "data_wait_s": self.data_wait}


def _floats(tree: Dict) -> Dict[str, float]:
    return {k: float(v) for k, v in tree.items()}


def leaf_gaps(got: Dict[str, float], ref: Dict[str, float],
              keys=None) -> Dict[str, float]:
    """Each leaf's gap between two norms, relative to the reference's
    norm of that leaf or of the median leaf, whichever is larger."""
    keys = list(ref) if keys is None else keys
    med = statistics.median(ref.values())
    return {k: abs(got[k] - ref[k]) / max(ref[k], med) for k in keys}


def compare(got: Dict, ref: Dict) -> Dict[str, float]:
    """The numbers ``check`` holds to its limits: the largest relative
    gap of a step's loss; and the mean over the leaves of the first
    gradient's gap, and of the change's, over the leaves the reference's
    gradient moves (see STILL_LEAF).  The mean and not the worst leaf:
    which leaf is worst swings from seed to seed, and its gap with it, so
    that the worst leaf of the program on one seed reads as high as that
    of the fp8 control on another (PERF.md); a fault in one leaf of the
    twelve still moves the mean by a twelfth of its gap of about 1."""
    med = statistics.median(ref["grad"].values())
    moving = [k for k, g in ref["grad"].items() if g >= STILL_LEAF * med]
    grad = leaf_gaps(got["grad"], ref["grad"])
    change = leaf_gaps(got["change"], ref["change"], moving)
    return {
        "loss_gap": max(abs(a - b) / abs(b)
                        for a, b in zip(got["loss"], ref["loss"])),
        "grad_gap_mean": sum(grad.values()) / len(grad),
        "change_gap_mean": sum(change.values()) / len(change),
    }
