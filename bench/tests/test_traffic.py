"""The traffic generator and the tail arithmetic."""
import json
import os

import numpy as np
import pytest

from bench.stats import percentile
from bench.traffic.generate import serve_schedule

MIXES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "traffic")


def _mix(name):
    with open(os.path.join(MIXES, f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("q", [0.0, 0.5, 0.9, 0.99, 1.0])
def test_percentile_matches_numpy(q):
    xs = np.random.default_rng(0).lognormal(size=257)
    assert percentile(list(xs), q) == pytest.approx(np.percentile(xs, 100 * q))


@pytest.mark.parametrize("rate", [0.9, 4.0])
def test_every_seed_offers_the_same_work(rate):
    """A seed reorders the set of sizes and gaps, and changes nothing
    else: the same requests, the same pre-roll, the same total of tokens,
    the same span."""
    spec = _mix("chat")
    runs = [serve_schedule(spec, rate, 40, seed, preroll=3)
            for seed in (1, 2**33 + 5)]
    sizes = [sorted((a.prompt_len, a.max_tokens) for a in r) for r in runs]
    assert sizes[0] == sizes[1]
    last = [max(a.at for a in r) for r in runs]
    assert last[0] == pytest.approx(last[1])
    assert [a.at for a in runs[0]] != [a.at for a in runs[1]]
    assert all(sum(a.at < 0 for a in r) == 3 for r in runs)
    for r in runs:
        assert all(spec["prompt"]["min"] <= a.prompt_len
                   <= spec["prompt"]["max"] for a in r)
        assert all(spec["output"]["min"] <= a.max_tokens
                   <= spec["output"]["max"] for a in r)
        assert all(a.prompt_len + a.max_tokens <= spec["max_total"]
                   for a in r)
    assert len(runs[0]) - 3 == pytest.approx(rate * 40, rel=0.3)


def test_preroll_answers_are_staggered():
    """The requests in flight when the window opens have the i-th of n
    shares (i + 1/2)/n of their answers left, so they end one by one."""
    spec = _mix("chat")
    full = serve_schedule(spec, 1.0, 40, 5, preroll=0)
    rolled = serve_schedule(spec, 1.0, 40, 5, preroll=4)
    early = [a for a in rolled if a.at < 0]
    assert len(early) == 4
    assert sum(a.max_tokens for a in early) < sum(
        a.max_tokens for a in rolled if a.at >= 0) / (len(rolled) - 4) * 4
    assert len({a.max_tokens for a in early}) == 4
    assert len(full) == len(rolled) - 4
