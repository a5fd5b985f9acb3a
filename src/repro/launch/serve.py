"""Serving driver: batched requests through the continuous-batching engine.

``python -m repro.launch.serve --arch llama3.2-1b --requests 16``

Uses a reduced config by default (CPU tests); ``--full-size`` serves the
published widths and depth (``chip_smoke.py`` serves qwen3-1.7b so on one
TPU v5e chip).
"""
from __future__ import annotations

import argparse
from typing import Any, Dict

import jax
import numpy as np

from repro.core.logging import get_logger
from repro.models import build, get_config
from repro.serve import ServeConfig, ServeEngine

log = get_logger("serve-main")


def serve_demo(arch: str, n_requests: int = 16, max_tokens: int = 16,
               max_batch: int = 4, reduced: bool = True, seed: int = 0
               ) -> Dict[str, Any]:
    """Serve ``n_requests`` seeded prompts to completion.  Returns the
    engine (model and weights), the finished requests and their
    summary stats."""
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    api = build(cfg)
    # jitted, so the stacked layer weights are written once in place
    params = jax.jit(api.init)(jax.random.PRNGKey(seed))
    engine = ServeEngine(api, params, ServeConfig(
        max_batch=max_batch, max_len=256, prompt_buckets=(16, 32, 64)))
    rng = np.random.default_rng(seed)
    for i in range(n_requests):
        plen = int(rng.integers(4, 24))
        prompt = rng.integers(1, cfg.vocab_size, size=plen)
        engine.submit(prompt, max_tokens=max_tokens)
    done = engine.run()
    stats = ServeEngine.summarize(done)
    log.info("served %s", stats)
    return {"engine": engine, "requests": done, "stats": stats}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="serve")
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-tokens", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--full-size", action="store_true",
                    help="use the full config (default: reduced)")
    args = ap.parse_args(argv)
    serve_demo(args.arch, args.requests, args.max_tokens, args.max_batch,
               reduced=not args.full_size)
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
