"""Serve cells: the program's ``ServeEngine`` under open-loop traffic.

Set-up builds the weights on the device in one jitted call from the
seed, in bfloat16 (the configuration's stated type; the engine casts
every weight to bfloat16 at each use, so the tokens served are those of
float32 storage), builds the engine from the configuration's
deployment, and warms up every prompt bucket the mix can reach and every
slot's splice.  A pre-roll, where the cell asks for one, is submitted and
admitted in set-up, with
the rest of each answer still to come, so the window opens with the
slots about as full as a steady state keeps them.

The window drives ``ServeEngine.submit`` at each request's scheduled
arrival and ``ServeEngine.step`` whenever a request waits or runs.
After each step the benchmark stamps one token for each token a request
gained (the step is fenced: its tokens are on the host).  A request's
first token carries the engine's own fenced stamp.

``check`` then takes a sample of the finished requests, drawn from the
seed with the longest among them, and has the reference score every
token served: the largest gap between the reference's best logit and
the served token's.
"""
from __future__ import annotations

import importlib
import random
import sys
import time
from typing import Dict, List

from bench import trace as bench_trace
from bench.reference.common import seed_key
from bench.traffic.generate import prompt_tokens, serve_schedule

_MS = 1e3


class Run:
    """One run of a serve cell: set-up, the window, its results, the check."""

    def __init__(self, cell: Dict, seed: int, seconds: float, devs,
                 log=sys.stderr):
        self.cell, self.seed, self.seconds, self.devs = cell, seed, seconds, devs
        self.log = log
        self.model = cell["config_file"]
        self.attempted = 0
        self.failed = 0
        self.requests: List = []        # (request, scheduled arrival)
        self.stamps: Dict[int, List[float]] = {}
        self.traced_prefill: List[int] = []       # prompt lengths
        self.late: List[float] = []               # generator lateness

    # -- set-up -------------------------------------------------------
    def program_config(self):
        family = importlib.import_module(
            f"bench.families.{self.model['family']}")
        return family.program_config(self.model)

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp

        from repro.models import build
        from repro.serve import ServeConfig, ServeEngine

        api = build(self.program_config())
        make = jax.jit(lambda k: jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16), api.init(k)))
        with jax.default_device(self.devs[0]):
            params = make(seed_key(self.seed))
            dep = self.model["deployment"]
            self.engine = ServeEngine(api, params, ServeConfig(
                max_batch=dep["max_batch"], max_len=dep["max_len"],
                prompt_buckets=tuple(dep["prompt_buckets"]),
                cache_dtype=jnp.bfloat16))
        self.rng = random.Random(self.seed)
        self.vocab = self.model["vocab_size"]
        self.schedule = serve_schedule(self.cell["mix"], self.cell["rate"],
                                       self.seconds, self.seed,
                                       self.cell.get("preroll", 0))
        self._warm_up()
        for a in self.schedule:
            if a.at < 0:
                self._submit(a, time.perf_counter())
        if self.engine.queue:
            self._step()

    def _warm_up(self) -> None:
        """Compile what the window will run: the prefill of each bucket the
        mix reaches, the splice into every slot, the decode step."""
        eng, mix = self.engine, self.cell["mix"]["prompt"]
        buckets = eng.cfg.prompt_buckets
        reach = [b for i, b in enumerate(buckets)
                 if b >= mix["min"] and (i == 0 or buckets[i - 1] < mix["max"])]
        lengths = [min(b, mix["max"]) for b in reach]
        lengths += [mix["min"]] * (eng.cfg.max_batch - len(lengths))
        for n in lengths:
            eng.submit(prompt_tokens(n, self.vocab, self.rng), max_tokens=2)
        eng.run()

    # -- the window -------------------------------------------------------
    def _submit(self, a, at: float) -> None:
        import jax

        with jax.profiler.TraceAnnotation("submit"):
            req = self.engine.submit(
                prompt_tokens(a.prompt_len, self.vocab, self.rng),
                max_tokens=a.max_tokens, submitted_at=at)
        self.requests.append((req, at))
        self.stamps[req.uid] = []
        self.late.append(time.perf_counter() - at)

    def _step(self, traced: bool = False) -> None:
        import jax

        eng = self.engine
        with jax.profiler.TraceAnnotation("engine.step"):
            finished = eng.step()
        t_end = time.perf_counter()
        for req in [r for r in eng.slots if r is not None] + finished:
            st = self.stamps[req.uid]
            n = len(req.output)
            if n == len(st):
                continue
            if not st:
                st.append(req.first_token_at)
                if traced:
                    self.traced_prefill.append(req.prompt_len)
            for j in range(len(st), n):
                st.append(t_end)

    def window(self, seconds: float, trace_dir) -> None:
        import jax

        arrivals = [a for a in self.schedule if a.at >= 0]
        tr = self.cell["trace"]
        t0 = time.perf_counter()
        end = t0 + seconds
        trace_at, trace_end, ann = t0 + tr["start_s"], None, None
        i = 0
        while True:
            now = time.perf_counter()
            if now >= end:
                break
            if trace_dir and ann is None and now >= trace_at:
                bench_trace.start(trace_dir)
                ann = jax.profiler.TraceAnnotation("bench.window")
                ann.__enter__()
                trace_end = time.perf_counter() + tr["seconds"]
            elif ann is not None and trace_end is not None and now >= trace_end:
                ann.__exit__(None, None, None)
                jax.profiler.stop_trace()
                trace_end = None
            while i < len(arrivals) and t0 + arrivals[i].at <= now:
                self._submit(arrivals[i], t0 + arrivals[i].at)
                i += 1
            eng = self.engine
            if eng.queue or any(s is not None for s in eng.slots):
                self._step(traced=trace_end is not None)
            else:
                nxt = t0 + arrivals[i].at if i < len(arrivals) else end
                with jax.profiler.TraceAnnotation("wait_arrival"):
                    time.sleep(max(0.0, min(nxt, end) - time.perf_counter()))
        if trace_end is not None:
            ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
        self.t0, self.t_end, self.t_close = t0, end, time.perf_counter()
        self.attempted = sum(1 for _, at in self.requests if at >= t0)
        late = self.late[-i:] if i else [0.0]
        print(f"serve: {i} of {len(arrivals)} arrivals submitted; generator "
              f"lateness mean {_MS * sum(late) / len(late):.3f} ms, max "
              f"{_MS * max(late):.3f} ms", file=self.log)

    # -- results ----------------------------------------------------------
    def end_to_end(self) -> Dict[str, float]:
        from bench.stats import percentile

        t0, close = self.t0, self.t_close
        tokens, gaps, ttft = 0, [], []
        for req, at in self.requests:
            st = self.stamps[req.uid]
            inside = [t for t in st if t0 <= t <= close]
            tokens += len(inside)
            gaps += [b - a for a, b in zip(st, st[1:]) if a >= t0 and b <= close]
            if t0 <= at <= self.t_end:
                first = st[0] if st and st[0] <= close else None
                ttft.append((first if first is not None else close) - at)
        out = {"serve_tok_s": tokens / (close - t0)}
        if gaps:
            out["itl_p99_ms"] = _MS * percentile(gaps, 0.99)
        if ttft:
            print(f"serve: time to first token over {len(ttft)} requests: "
                  f"median {_MS * percentile(ttft, 0.5):.3f} ms, mean "
                  f"{_MS * sum(ttft) / len(ttft):.3f} ms", file=self.log)
        return out

    def release(self) -> None:
        self.finished = [(list(map(int, r.prompt)), list(r.output))
                         for r, _ in self.requests if r.done_at is not None]
        del self.engine

    def sample(self) -> List:
        """The longest finished request and others drawn from the seed,
        up to the cell's count."""
        if not self.finished:
            return []
        order = sorted(range(len(self.finished)),
                       key=lambda j: -(len(self.finished[j][0])
                                       + len(self.finished[j][1])))
        rest = order[1:]
        random.Random(self.seed).shuffle(rest)
        return [self.finished[j] for j in [order[0]] + rest[
            :self.cell["check"]["samples"] - 1]]

    def check(self, control: bool = False) -> Dict:
        served_gaps = importlib.import_module(
            f"bench.reference.{self.model['family']}").served_gaps
        served = self.sample()
        if not served:
            return {}
        ref, ctl = served_gaps(self.model, self.seed, served, control)
        self.readings = {"logit_gap": max(ref), "control_gap": max(ctl),
                         "tokens": sum(len(o) for _, o in served)}
        return {"logit_gap": {"value": max(ref),
                              "limit": self.cell["check"]["logit_gap"]}}

    def context(self, trace) -> Dict:
        return {"trace": trace, "model": self.model,
                "chips": len(self.devs), "peaks": peaks(self.devs[0]),
                "traced_prefill": self.traced_prefill}


def peaks(device) -> Dict[str, float]:
    from bench.harness import load_json

    table = load_json("peaks.json")["kinds"]
    if device.device_kind not in table:
        raise ValueError(f"no peaks for device kind {device.device_kind!r} "
                         f"in bench/peaks.json")
    return table[device.device_kind]
