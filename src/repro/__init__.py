"""repro — SCOPE benchmarking framework reproduction.

Process-wide JAX configuration lives here so every entry point (pytest,
``python -m repro``, orchestrator workers, launch scripts, chip_smoke.py)
agrees:

  * ``jax_threefry_partitionable``: without it, the SPMD partitioner
    changes the bits ``jax.random`` produces when an init computation is
    jitted with shardings — sharded model init then silently disagrees
    with single-device init (observed 0.38 max param diff on the 2x4-mesh
    llama train-step equivalence test).  The partitionable generator is
    sharding-invariant.
  * the persistent compilation cache: where ``JAX_COMPILATION_CACHE_DIR``
    is set, JAX reads it and nothing is set here; otherwise the cache is
    ``.jax_cache/`` at the checkout root.  The path is part of the cache
    key, so it is fixed — never a temporary name, a pid or a time — and
    worker processes, which import this package too, share it.
"""
import os as _os
from pathlib import Path as _Path

import jax as _jax

_jax.config.update("jax_threefry_partitionable", True)

if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _jax.config.update("jax_compilation_cache_dir",
                       str(_Path(__file__).resolve().parents[2] / ".jax_cache"))
