"""Serve engine: continuous batching correctness + ragged decode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import build, get_config
from repro.serve import ServeConfig, ServeEngine


@pytest.fixture(scope="module")
def small():
    cfg = get_config("llama3.2-1b").reduced().override(
        num_layers=2, vocab_size=128)
    api = build(cfg)
    params = api.init(jax.random.PRNGKey(0))
    return cfg, api, params


def greedy_reference(cfg, api, params, prompt, n_tokens):
    """Uniform-batch reference generation (prefill + scalar-pos decode)."""
    toks = jnp.asarray(prompt, jnp.int32)[None]
    cache = api.init_cache(1, 256)
    logits, cache = jax.jit(api.prefill)(params, {"tokens": toks}, cache)
    out = [int(jnp.argmax(logits[0, -1]))]
    for _ in range(n_tokens - 1):
        logits, cache = jax.jit(api.decode_step)(
            params, jnp.asarray([[out[-1]]], jnp.int32), cache)
        out.append(int(jnp.argmax(logits[0, 0])))
    return out


def test_engine_matches_reference_single(small):
    cfg, api, params = small
    prompt = np.arange(1, 11)
    ref = greedy_reference(cfg, api, params, prompt, 6)
    eng = ServeEngine(api, params, ServeConfig(max_batch=2, max_len=256,
                                               prompt_buckets=(16,)))
    eng.submit(prompt, max_tokens=6)
    done = eng.run()
    assert len(done) == 1
    assert done[0].output == ref


def test_engine_mixed_lengths_match_reference(small):
    """Continuous batching with heterogeneous prompts must equal per-
    request generation — the per-slot position clock correctness check."""
    cfg, api, params = small
    prompts = [np.arange(1, 6), np.arange(20, 34), np.arange(3, 12)]
    refs = [greedy_reference(cfg, api, params, p, 5) for p in prompts]
    eng = ServeEngine(api, params, ServeConfig(max_batch=2, max_len=256,
                                               prompt_buckets=(16,)))
    reqs = [eng.submit(p, max_tokens=5) for p in prompts]
    done = eng.run()
    assert len(done) == 3
    by_uid = {r.uid: r.output for r in done}
    for req, ref in zip(reqs, refs):
        assert by_uid[req.uid] == ref, req.uid


def test_engine_throughput_summary(small):
    cfg, api, params = small
    eng = ServeEngine(api, params, ServeConfig(max_batch=2, max_len=256,
                                               prompt_buckets=(16,)))
    for i in range(4):
        eng.submit(np.arange(1, 8), max_tokens=3)
    done = eng.run()
    stats = ServeEngine.summarize(done)
    assert stats["requests"] == 4
    assert stats["tokens"] == 12
    assert stats["throughput_tok_s"] > 0


def test_queue_deeper_than_max_batch_refills_slots(small):
    """5 requests through a 2-slot pool: freed slots must refill from
    the queue until everything drains (no head-of-line blocking)."""
    cfg, api, params = small
    eng = ServeEngine(api, params, ServeConfig(max_batch=2, max_len=256,
                                               prompt_buckets=(16,)))
    reqs = [eng.submit(np.arange(1, 6 + i), max_tokens=3) for i in range(5)]
    done = eng.run()
    assert sorted(r.uid for r in done) == sorted(r.uid for r in reqs)
    assert all(len(r.output) == 3 for r in done)
    assert all(r.done_at is not None for r in done)
    # queue-depth evidence: the first step sees all 5 in flight/queued,
    # and depth only drains as slots free and refill
    assert eng.queue_depth_log[0] == 5
    assert max(eng.queue_depth_log) == 5
    assert min(eng.queue_depth_log) >= 1


def test_eos_frees_slot_midrun(small):
    """An EOS hit mid-generation must finish the request early AND free
    its slot for the queued request behind it."""
    cfg, api, params = small
    prompt = np.arange(1, 11)
    ref = greedy_reference(cfg, api, params, prompt, 8)
    eos = ref[3]
    # engine checks EOS only on decode-produced tokens (ref[1:])
    stop = next(i for i in range(1, len(ref)) if ref[i] == eos)
    eng = ServeEngine(api, params, ServeConfig(max_batch=1, max_len=256,
                                               prompt_buckets=(16,)))
    first = eng.submit(prompt, max_tokens=50, eos_id=int(eos))
    second = eng.submit(np.arange(30, 37), max_tokens=3)
    done = eng.run()
    assert [r.uid for r in done] == [first.uid, second.uid]
    assert first.output == ref[:stop + 1]          # stopped early, at EOS
    assert len(first.output) < 50
    assert len(second.output) == 3                 # the freed slot served it
    assert first.done_at <= second.done_at


def test_oversize_prompt_raises_actionably(small):
    cfg, api, params = small
    eng = ServeEngine(api, params, ServeConfig(max_batch=1, max_len=256,
                                               prompt_buckets=(16,)))
    with pytest.raises(ValueError, match="prompt_buckets"):
        eng.submit(np.arange(1, 30))
    assert not eng.queue                           # nothing half-enqueued


def test_prompt_exceeding_max_len_raises(small):
    cfg, api, params = small
    eng = ServeEngine(api, params, ServeConfig(max_batch=1, max_len=16,
                                               prompt_buckets=(32,)))
    with pytest.raises(ValueError, match="max_len"):
        eng.submit(np.arange(1, 21))
    assert not eng.queue


def test_max_len_exhaustion_truncates_and_terminates(small):
    """A request asking for more tokens than the slot's cache can hold
    must terminate (marked truncated), not overrun the static cache or
    spin forever."""
    cfg, api, params = small
    eng = ServeEngine(api, params, ServeConfig(max_batch=1, max_len=16,
                                               prompt_buckets=(16,)))
    req = eng.submit(np.arange(1, 9), max_tokens=100)     # 8-token prompt
    done = eng.run()
    assert [r.uid for r in done] == [req.uid]
    assert req.truncated
    assert req.done_at is not None
    assert len(req.output) == 16 - 8               # filled the cache exactly


def test_summarize_empty_and_all_failed_batches():
    from repro.serve.engine import Request
    assert ServeEngine.summarize([]) == {}
    dead = [Request(uid=i, prompt=np.arange(3), submitted_at=float(i))
            for i in (1, 2)]                       # never reached done_at
    stats = ServeEngine.summarize(dead)
    assert stats["requests"] == 2
    assert stats["ttft_mean_s"] == 0.0
    assert stats["latency_mean_s"] == 0.0
    assert stats["throughput_tok_s"] == 0.0


class _SlowPrefillApi:
    """ModelApi wrapper whose prefill drags a long serial compute chain
    into the compiled program — TTFT-visible latency without changing
    which tokens come out (the chain perturbs logits by a factor of
    (1 + ~1e-34), far below any logit gap)."""

    def __init__(self, api, chain=48, dim=192):
        self._api = api
        self.cfg = api.cfg
        self._chain = chain
        self._dim = dim

    def init(self, *a, **k):
        return self._api.init(*a, **k)

    def init_cache(self, *a, **k):
        return self._api.init_cache(*a, **k)

    def prefill(self, params, batch, cache, logit_pos=None):
        logits, cache = self._api.prefill(params, batch, cache,
                                          logit_pos=logit_pos)
        x = jnp.full((self._dim, self._dim), 0.5, jnp.float32)
        for _ in range(self._chain):
            x = jnp.sin(x @ x)                     # bounded: never inf/NaN
        return logits * (1.0 + x.mean() * 1e-34), cache


def test_fenced_ttft_not_below_unfenced(small):
    """The async-dispatch regression satellite: with fence_timestamps
    off, first_token_at is stamped when the prefill *dispatch* returns;
    with it on, after the logits are actually delivered.  On a model
    with genuinely slow prefill the fenced TTFT must be the larger one
    — if it isn't, the stamp is measuring enqueue, not delivery."""
    cfg, api, params = small
    slow = _SlowPrefillApi(api)
    eng = ServeEngine(slow, params, ServeConfig(max_batch=1, max_len=256,
                                                prompt_buckets=(16,)))
    prompt = np.arange(1, 11)
    eng.submit(prompt, max_tokens=2)
    eng.run()                                      # warm: compile both paths

    def ttft(fenced):
        eng.cfg.fence_timestamps = fenced
        req = eng.submit(prompt, max_tokens=2)
        eng.run()
        return req.first_token_at - req.submitted_at

    unfenced = min(ttft(False) for _ in range(3))
    fenced = min(ttft(True) for _ in range(3))
    assert fenced >= unfenced


def test_single_slot_engine_matches_reference(small):
    """max_batch=1 regression: the cache splice must handle a pool whose
    batch dim equals the row's (there is no axis-size difference to find)
    — a single-slot engine used to decode over a zero cache."""
    cfg, api, params = small
    prompt = np.arange(1, 11)
    ref = greedy_reference(cfg, api, params, prompt, 6)
    eng = ServeEngine(api, params, ServeConfig(max_batch=1, max_len=256,
                                               prompt_buckets=(16,)))
    eng.submit(prompt, max_tokens=6)
    done = eng.run()
    assert done[0].output == ref


def test_engine_updates_the_pool_cache_in_place(small):
    """The pool cache is donated to the splice and to the decode step, so
    a step that admits and decodes leaves the previous cache's buffers
    deleted (no second pool cache is kept), and one compiled splice
    serves every slot."""
    cfg, api, params = small
    eng = ServeEngine(api, params, ServeConfig(max_batch=3, max_len=64,
                                               prompt_buckets=(16,)))
    before = eng.cache["k"]
    compiled = eng._splice._cache_size()     # shared by every engine
    for p in (np.arange(1, 6), np.arange(2, 9), np.arange(4, 7)):
        eng.submit(p, max_tokens=3)
    eng.step()
    assert all(r is not None for r in eng.slots)
    assert before.is_deleted()
    assert eng._splice._cache_size() <= compiled + 1
