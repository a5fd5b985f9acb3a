"""Batched serving engine: prefill/decode steps + continuous batching.

Slot-based continuous batching (vLLM-style scheduling, TPU-adapted):
  * a fixed pool of ``max_batch`` slots shares one padded KV/SSM cache —
    shapes are static, so there is exactly ONE compiled decode program;
  * arriving requests prefill into a free slot (per-slot prefill keeps the
    decode batch running between admissions; prefill programs are compiled
    per padded prompt-bucket);
  * every decode step advances ALL live slots one token; finished slots
    (EOS or max_tokens) free immediately and are refilled from the queue —
    no head-of-line blocking on long generations;
  * per-slot position counters mask attention to each slot's own history
    (the cache is padded to ``max_len``).

The hardware adaptation vs GPU serving stacks: instead of paged KV blocks
(pointer-chasing is hostile to the TPU's dense DMA model), slots use
contiguous per-slot cache regions with static shapes — the standard
TPU serving layout.
"""
from __future__ import annotations

import collections
import time
from dataclasses import dataclass, field
from typing import Any, Deque, Dict, Iterator, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.logging import get_logger
from repro.core.spans import span
from repro.models.api import ModelApi

log = get_logger("serve")

#: Step records an engine keeps: the newest this many ``step()`` calls
#: (about 100 minutes of 90 ms steps).
MAX_STEP_RECORDS = 65_536


@dataclass
class Request:
    uid: int
    prompt: np.ndarray                 # [S] int32
    max_tokens: int = 32
    eos_id: Optional[int] = None
    # filled by the engine:
    output: List[int] = field(default_factory=list)
    submitted_at: float = 0.0
    admitted_at: Optional[float] = None    # left the queue for a slot
    first_token_at: Optional[float] = None
    done_at: Optional[float] = None
    prompt_len: int = 0
    # generation stopped because the slot's cache filled (max_len), not
    # because of EOS/max_tokens — the output is complete but shorter
    # than requested
    truncated: bool = False


@dataclass
class ServeConfig:
    max_batch: int = 8
    max_len: int = 512
    prompt_buckets: Tuple[int, ...] = (32, 64, 128, 256)
    cache_dtype: Any = jnp.bfloat16
    # fence (block_until_ready) decoded tokens before stamping
    # first_token_at/done_at, so TTFT/latency measure *delivery*.
    # False reverts to stamping at dispatch-return — enqueue time, the
    # async-dispatch bug class the wall meter fences in batch timing —
    # and exists so the regression test can measure the gap.
    fence_timestamps: bool = True


@dataclass(slots=True)
class StepRecord:
    """What one ``ServeEngine.step()`` did, on the host's clock.

    ``spans`` holds the host seconds of each ``engine.*`` span the step
    opened (``engine.admit`` summed over its admissions); ``end`` is
    taken after the step's tokens reached the host."""
    start: float
    end: float = 0.0
    admitted: List[int] = field(default_factory=list)     # uids
    prompt_tokens: int = 0          # the admitted prompts' real tokens
    padded_tokens: int = 0          # ... padded to their buckets
    live: int = 0                   # slots the step decoded
    queue_depth: int = 0            # queued + in flight after admission
    spans: Dict[str, float] = field(default_factory=dict)

    @property
    def seconds(self) -> float:
        return self.end - self.start


class _QueueDepths:
    """Read-only view of the step records' queue depths, oldest first."""

    def __init__(self, records: Deque[StepRecord]):
        self._records = records

    def __len__(self) -> int:
        return len(self._records)

    def __iter__(self) -> Iterator[int]:
        return (r.queue_depth for r in self._records)

    def __getitem__(self, i: Union[int, slice]):
        if isinstance(i, slice):
            return list(self)[i]
        return self._records[i].queue_depth


class ServeEngine:
    """Single-host engine driving a ModelApi; the multi-pod serve path
    reuses the same step functions under pjit (launch/serve.py)."""

    def __init__(self, api: ModelApi, params, cfg: ServeConfig):
        self.api = api
        self.cfg = cfg
        self.params = params
        self.queue: "collections.deque[Request]" = collections.deque()
        self.slots: List[Optional[Request]] = [None] * cfg.max_batch
        self._uid = 0

        # single shared cache for the whole slot pool, with PER-SLOT
        # position clocks (ragged decode)
        from repro.models import transformer
        from repro.models.api import family_module
        assert family_module(api.cfg) is transformer, \
            "ServeEngine drives decoder-only families (dense/moe/vlm)"
        self.cache = api.init_cache(cfg.max_batch, cfg.max_len,
                                    cfg.cache_dtype)
        self.cache["pos"] = jnp.zeros((cfg.max_batch,), jnp.int32)

        def decode_step(p, t, c):
            return transformer.decode_step(api.cfg, p, t, c)
        # a named function: the trace calls the program jit_decode_step.
        # The pool cache is donated to the decode step and to the splice,
        # which update it in place: the engine holds no other reference
        self._decode = jax.jit(decode_step, donate_argnums=(2,))
        self._splice = jax.jit(transformer.write_slot, donate_argnums=(0,))
        self._prefill_cache = {}
        # host-side per-slot position clocks (prefix + decoded tokens):
        # max_len exhaustion is a host decision, it must not force the
        # device cache
        self._slot_pos = [0] * cfg.max_batch
        # each slot's last token, the next decode step's input
        self._pending_tok = np.zeros(cfg.max_batch, np.int32)
        #: one record per step(), the newest MAX_STEP_RECORDS
        self.step_records: Deque[StepRecord] = collections.deque(
            maxlen=MAX_STEP_RECORDS)

    @property
    def queue_depth_log(self) -> _QueueDepths:
        """Queued + in-flight request count sampled once per step() —
        the queue-depth series latency meters average."""
        return _QueueDepths(self.step_records)

    # -- public API -------------------------------------------------------
    def submit(self, prompt: np.ndarray, max_tokens: int = 32,
               eos_id: Optional[int] = None,
               submitted_at: Optional[float] = None) -> Request:
        """Queue one request.  ``submitted_at`` lets open-loop drivers
        stamp the *scheduled arrival* instant so latency includes the
        queueing the arrival process created (default: now)."""
        prompt = np.asarray(prompt, np.int32)
        biggest = max(self.cfg.prompt_buckets)
        if len(prompt) > biggest:
            raise ValueError(
                f"prompt of {len(prompt)} tokens exceeds the largest "
                f"prefill bucket ({biggest}); raise ServeConfig."
                f"prompt_buckets (currently {self.cfg.prompt_buckets}) "
                f"or chunk the prompt")
        if len(prompt) >= self.cfg.max_len:
            raise ValueError(
                f"prompt of {len(prompt)} tokens cannot fit a "
                f"max_len={self.cfg.max_len} cache with room to decode; "
                f"raise ServeConfig.max_len")
        self._uid += 1
        req = Request(self._uid, prompt, max_tokens, eos_id,
                      submitted_at=(time.perf_counter()
                                    if submitted_at is None
                                    else submitted_at),
                      prompt_len=len(prompt))
        self.queue.append(req)
        return req

    def step(self) -> List[Request]:
        """One engine step: admit from the queue, decode every live slot
        one token.  Returns the requests that finished this step (empty
        when the pool is idle).  ``run`` is a loop over this; open-loop
        drivers interleave it with scheduled ``submit`` calls.  Each
        call appends a :class:`StepRecord` to ``step_records``."""
        rec = StepRecord(start=time.perf_counter())
        self.step_records.append(rec)
        self._admit()
        rec.live = sum(1 for s in self.slots if s is not None)
        rec.queue_depth = len(self.queue) + rec.live
        done = self._decode_step() if rec.live else []
        rec.end = time.perf_counter()
        return done

    def run(self, max_steps: int = 10_000) -> List[Request]:
        """Drive until queue and slots drain.  Returns finished requests."""
        finished: List[Request] = []
        for _ in range(max_steps):
            if not self.queue and not any(s is not None for s in self.slots):
                break
            finished.extend(self.step())
        return finished

    # -- internals ------------------------------------------------------
    def _bucket(self, n: int) -> int:
        for b in self.cfg.prompt_buckets:
            if n <= b:
                return b
        raise ValueError(                      # unreachable via submit()
            f"no prompt bucket fits {n} tokens "
            f"(buckets: {self.cfg.prompt_buckets})")

    def _admit(self) -> None:
        rec = self.step_records[-1]
        for i in range(self.cfg.max_batch):
            if self.slots[i] is not None or not self.queue:
                continue
            req = self.queue.popleft()
            req.admitted_at = time.perf_counter()
            bucket = self._bucket(len(req.prompt))
            with span("engine.admit", rec.spans, uid=req.uid, bucket=bucket,
                      tokens=req.prompt_len):
                self._prefill_into_slot(i, req, bucket)
            rec.admitted.append(req.uid)
            rec.prompt_tokens += req.prompt_len
            rec.padded_tokens += bucket
            self.slots[i] = req

    def _prefill_into_slot(self, slot: int, req: Request, bucket: int
                           ) -> None:
        """Per-slot prefill: bucket-padded single-row prefill, then splice
        the row's cache into the pool cache at ``slot``."""
        spans = self.step_records[-1].spans
        with span("engine.prefill", spans):
            toks = np.zeros((1, bucket), np.int32)
            n = min(len(req.prompt), bucket)
            toks[0, :n] = req.prompt[:n]
            if bucket not in self._prefill_cache:
                def one_row_prefill(params, tokens, n):
                    cache = self.api.init_cache(1, self.cfg.max_len,
                                                self.cfg.cache_dtype)
                    return self.api.prefill(params, {"tokens": tokens},
                                            cache, logit_pos=n - 1)
                self._prefill_cache[bucket] = jax.jit(one_row_prefill)
            logits_row, row_cache = self._prefill_cache[bucket](
                self.params, toks, n)
            # right-padded prompt: this slot's clock is n, so padded keys
            # beyond position n are masked by the per-slot prefix length
            row_cache = dict(row_cache, pos=jnp.asarray([n], jnp.int32))
            if self.cfg.fence_timestamps:
                jax.block_until_ready(logits_row)
            # fenced: the token is on the host — TTFT measures delivery;
            # unfenced: the dispatch just returned — TTFT measures enqueue
            req.first_token_at = time.perf_counter()
        with span("engine.first_token", spans):
            tok = int(jnp.argmax(logits_row[0, -1]))
        req.output.append(tok)
        with span("engine.splice", spans):
            self.cache = self._splice(self.cache, row_cache, slot)
        self._slot_pos[slot] = n
        self._pending_tok[slot] = tok

    def _decode_step(self) -> List[Request]:
        spans = self.step_records[-1].spans
        with span("engine.decode", spans):
            with span("engine.upload", spans):
                toks = jnp.asarray(self._pending_tok)[:, None]
            with span("engine.decode_wait", spans):
                logits, self.cache = self._decode(self.params, toks,
                                                  self.cache)
                if self.cfg.fence_timestamps:
                    jax.block_until_ready(logits)
            stamp = time.perf_counter()
            with span("engine.sample", spans):
                nxt = np.asarray(jnp.argmax(logits[:, 0], axis=-1), np.int32)
            with span("engine.retire", spans):
                return self._retire(nxt, stamp)

    def _retire(self, nxt: np.ndarray, stamp: float) -> List[Request]:
        """Hand each live slot its token; free the slots that finished."""
        done: List[Request] = []
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            tok = int(nxt[i])
            req.output.append(tok)
            self._pending_tok[i] = tok
            self._slot_pos[i] += 1
            # the slot's cache is full when the *next* decode would
            # write at max_len: terminate rather than overrun the
            # static cache (the request is truncated, not failed)
            exhausted = self._slot_pos[i] + 1 >= self.cfg.max_len
            if (len(req.output) >= req.max_tokens or
                    (req.eos_id is not None and tok == req.eos_id) or
                    exhausted):
                if exhausted and len(req.output) < req.max_tokens and \
                        not (req.eos_id is not None and tok == req.eos_id):
                    req.truncated = True
                req.done_at = stamp
                done.append(req)
                self.slots[i] = None
        return done

    # -- metrics ----------------------------------------------------------
    @staticmethod
    def summarize(reqs: List[Request]) -> Dict[str, float]:
        """Batch-level summary stats; robust to empty and all-failed
        batches (no request ever reached ``done_at``) — means and
        throughput report 0.0 rather than crashing mid-postmortem."""
        if not reqs:
            return {}
        ttft = [r.first_token_at - r.submitted_at for r in reqs
                if r.first_token_at is not None]
        wait = [r.admitted_at - r.submitted_at for r in reqs
                if r.admitted_at is not None]
        lat = [r.done_at - r.submitted_at for r in reqs
               if r.done_at is not None]
        toks = sum(len(r.output) for r in reqs)
        finished = [r.done_at for r in reqs if r.done_at is not None]
        span = (max(finished) - min(r.submitted_at for r in reqs)
                if finished else 0.0)
        return {"requests": len(reqs), "tokens": toks,
                "queue_wait_mean_s": float(np.mean(wait)) if wait else 0.0,
                "ttft_mean_s": float(np.mean(ttft)) if ttft else 0.0,
                "latency_mean_s": float(np.mean(lat)) if lat else 0.0,
                "throughput_tok_s": toks / span if span > 0 else 0.0}

