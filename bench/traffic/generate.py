"""The one traffic generator: a mix file of parameters in, a schedule out.

A serving mix (``kind: serve``) becomes a list of requests, each with a
scheduled arrival offset, a prompt length and an output length.  The
*set* of sizes and gaps is fixed by the mix file and the window length:

  * arrival gaps come from :mod:`bench.traffic.arrivals` under the mix's
    own ``pool_seed``, as many as fall inside the window;
  * prompt and output lengths are stratified quantiles of clipped
    lognormals (the i-th of n at probability (i + 1/2)/n), paired by a
    permutation drawn from ``pool_seed``; where the mix sets
    ``max_total``, an output is cut so that prompt plus output stay
    within it (the cache a slot holds);
  * a pre-roll stands for the requests already in flight when the
    window opens: the first sizes of the pool, each with the rest of its
    answer still to come, the shares staggered evenly (the i-th of n
    keeps (i + 1/2)/n of it), so that the slots end one by one as they
    do in a steady state and not all at once.

The run's ``--seed`` only reorders that set (requests and gaps) and
draws the prompt tokens.  So every seed offers the same work, and two
seeds differ by no more than the order in which it comes; a seed that
changed the total work would widen the spread of every metric.

A training mix (``kind: train``) is the data pipeline's parameters; the
train driver hands them to the program's pipeline as they are.
"""
from __future__ import annotations

import math
import random
from dataclasses import dataclass
from statistics import NormalDist
from typing import Dict, List

from bench.traffic import arrivals


@dataclass(frozen=True)
class Arrival:
    at: float            # seconds after the window opens (< 0: pre-roll)
    prompt_len: int
    max_tokens: int


def _lognormal_quantiles(spec: Dict, n: int) -> List[int]:
    """n stratified draws of ``median * exp(sigma * z)``, clipped."""
    nd = NormalDist()
    lo, hi = int(spec["min"]), int(spec["max"])
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        v = int(round(spec["median"] * math.exp(spec["sigma"] * z)))
        out.append(min(max(v, lo), hi))
    return out


def serve_schedule(mix: Dict, rate: float, seconds: float, seed: int,
                   preroll: int = 0) -> List[Arrival]:
    """The requests of one run: ``preroll`` in flight at offset -1
    (submitted and admitted during set-up), then open-loop arrivals at
    ``rate`` req/s over ``seconds``."""
    pool_seed = int(mix["pool_seed"])
    offsets: List[float] = []
    n = max(8, int(rate * seconds * 2) + 8)
    while True:
        offsets = arrivals.generate(mix["arrival"], rate, n, pool_seed)
        if offsets[-1] > seconds:
            break
        n *= 2
    offsets = [t for t in offsets if t <= seconds]
    k = len(offsets) + preroll
    prompts = _lognormal_quantiles(mix["prompt"], k)
    outputs = _lognormal_quantiles(mix["output"], k)
    pool_rng = random.Random(pool_seed)
    pool_rng.shuffle(outputs)
    total = mix.get("max_total")
    pool = [(p, min(o, total - p) if total else o)
            for p, o in zip(prompts, outputs)]
    pool_rng.shuffle(pool)
    early = [Arrival(-1.0, p, max(1, round(o * (i + 0.5) / preroll)))
             for i, (p, o) in enumerate(pool[:preroll])]

    rng = random.Random(seed)
    if mix["arrival"] == "poisson":
        # memoryless: the gaps in another order are the same process
        gaps = [b - a for a, b in zip([0.0] + offsets[:-1], offsets)]
        rng.shuffle(gaps)
        offsets = [sum(gaps[:i + 1]) for i in range(len(gaps))]
    rest = pool[preroll:]
    rng.shuffle(rest)
    return early + [Arrival(t, p, o) for t, (p, o) in zip(offsets, rest)]


def prompt_tokens(n: int, vocab: int, rng) -> List[int]:
    """A prompt of ``n`` token ids drawn uniformly from the vocabulary."""
    return [rng.randrange(vocab) for _ in range(n)]
