"""Tuned-default registry for the Pallas kernel block knobs.

``python -m repro tune`` searches a kernel's block-size space and writes
the winner to a per-kernel artifact (``src/repro/kernels/<name>/tuned.json``).
This module is how the kernels read it back: every public wrapper in
``kernels/*/ops.py`` resolves its knobs through :func:`resolve` *outside*
jit, so a changed artifact (or a tune-trial override) is picked up on the
next call instead of being frozen into a cached trace.

Precedence, highest first (docs/tuning.md):

  1. explicit kwarg at the call site (``matmul(x, y, bm=256)``)
  2. an active :func:`override` context (how tune trials inject configs)
  3. environment: ``REPRO_TUNED_<KERNEL>_<KNOB>=<int>``
  4. the ``tuned.json`` artifact (skipped entirely when ``REPRO_TUNED``
     is ``off``/``0``/``false``)
  5. the builtin default baked into this module

The module is deliberately jax-free so the search tests and the lint rule
can import it without an accelerator stack.
"""
from __future__ import annotations

import json
import logging
import os
from contextlib import contextmanager
from typing import Any, Dict, Iterator, Mapping, Optional, Tuple

log = logging.getLogger("repro.kernels.tuning")

#: Every tunable kernel and the block knobs ``repro tune`` may set.
KERNEL_KNOBS: Dict[str, Tuple[str, ...]] = {
    "matmul": ("bm", "bn", "bk"),
    "flash_attention": ("bq", "bk"),
    "rmsnorm": ("br",),
    "ssd_scan": ("chunk",),
}

#: Fallback block sizes — the pre-tuning signature defaults.
BUILTIN_DEFAULTS: Dict[str, Dict[str, int]] = {
    "matmul": {"bm": 512, "bn": 512, "bk": 512},
    "flash_attention": {"bq": 512, "bk": 512},
    "rmsnorm": {"br": 256},
    "ssd_scan": {"chunk": 128},
}

#: ``REPRO_TUNED=off|0|false`` disables tuned.json artifacts entirely
#: (env/kwarg/override still apply) — the escape hatch for A/B runs.
DISABLE_ENV = "REPRO_TUNED"

#: Point artifact lookup at ``<dir>/<kernel>/tuned.json`` instead of the
#: installed package tree (tests, hermetic CI workspaces).
DIR_ENV = "REPRO_TUNED_DIR"

#: Scoped-VMEM limit every tunable kernel is compiled under: each
#: ``pallas_call`` passes it as ``vmem_limit_bytes``, so it is the
#: budget ``validate_blocks`` holds the wrappers' working-set estimates
#: to.  32 MiB is a quarter of a v5e core's 128 MiB of VMEM and twice
#: Mosaic's default scoped limit (16 MiB), which a block refused by the
#: chip's compiler would otherwise hit inside lowering.
VMEM_LIMIT_BYTES = 32 * 1024 * 1024

_TUNED_CACHE: Dict[str, Optional[Dict[str, int]]] = {}
_OVERRIDES: Dict[str, Dict[str, int]] = {}


def kernels() -> Tuple[str, ...]:
    """The tunable kernel names, stable order."""
    return tuple(KERNEL_KNOBS)


def _check_kernel(kernel: str) -> None:
    if kernel not in KERNEL_KNOBS:
        raise ValueError(f"unknown tunable kernel {kernel!r} "
                         f"(known: {', '.join(KERNEL_KNOBS)})")


def tuned_path(kernel: str) -> str:
    """Where ``<kernel>``'s artifact lives (honouring ``REPRO_TUNED_DIR``)."""
    _check_kernel(kernel)
    root = os.environ.get(DIR_ENV) or os.path.dirname(__file__)
    return os.path.join(root, kernel, "tuned.json")


def _artifacts_disabled() -> bool:
    return os.environ.get(DISABLE_ENV, "").lower() in ("off", "0", "false")


def load_tuned(kernel: str) -> Optional[Dict[str, int]]:
    """The artifact's knob config, or None.  Cached per path; a corrupt
    or knob-less artifact logs a warning and acts as absent."""
    path = tuned_path(kernel)
    if path in _TUNED_CACHE:
        return _TUNED_CACHE[path]
    config: Optional[Dict[str, int]] = None
    try:
        with open(path, encoding="utf-8") as fh:
            payload = json.load(fh)
        raw = payload.get("config", {})
        config = {k: int(raw[k]) for k in KERNEL_KNOBS[kernel] if k in raw}
        if not config:
            log.warning("%s carries no known %s knobs; ignoring", path,
                        kernel)
            config = None
    except FileNotFoundError:
        config = None
    except (OSError, ValueError, TypeError, AttributeError) as e:
        log.warning("tuned artifact %s unreadable (%s); using defaults",
                    path, e)
        config = None
    _TUNED_CACHE[path] = config
    return config


def invalidate_cache() -> None:
    """Forget loaded artifacts (call after writing one, or in tests)."""
    _TUNED_CACHE.clear()


@contextmanager
def override(kernel: str, config: Mapping[str, int]) -> Iterator[None]:
    """Force ``kernel``'s knobs for the dynamic extent of the block —
    how ``repro tune`` injects each trial's candidate config without
    touching artifacts or call sites.  Explicit kwargs still win."""
    _check_kernel(kernel)
    bad = [k for k in config if k not in KERNEL_KNOBS[kernel]]
    if bad:
        raise ValueError(f"{kernel} has no knob(s) {', '.join(sorted(bad))} "
                         f"(knobs: {', '.join(KERNEL_KNOBS[kernel])})")
    prev = _OVERRIDES.get(kernel)
    _OVERRIDES[kernel] = {k: int(v) for k, v in config.items()}
    try:
        yield
    finally:
        if prev is None:
            _OVERRIDES.pop(kernel, None)
        else:
            _OVERRIDES[kernel] = prev


def resolve(kernel: str, **explicit: Optional[int]) -> Dict[str, int]:
    """Final knob values for one call: kwarg > override > env > tuned.json
    > builtin.  ``None`` explicit values mean "not given"."""
    _check_kernel(kernel)
    active = _OVERRIDES.get(kernel, {})
    tuned = None if _artifacts_disabled() else load_tuned(kernel)
    out: Dict[str, int] = {}
    for knob in KERNEL_KNOBS[kernel]:
        value = explicit.get(knob)
        if value is None and knob in active:
            value = active[knob]
        if value is None:
            env = os.environ.get(f"REPRO_TUNED_{kernel.upper()}_"
                                 f"{knob.upper()}")
            if env is not None:
                try:
                    value = int(env)
                except ValueError:
                    raise ValueError(
                        f"REPRO_TUNED_{kernel.upper()}_{knob.upper()}="
                        f"{env!r} is not an integer") from None
        if value is None and tuned is not None and knob in tuned:
            value = tuned[knob]
        if value is None:
            value = BUILTIN_DEFAULTS[kernel][knob]
        out[knob] = int(value)
    return out


def write_tuned(kernel: str, payload: Mapping[str, Any],
                path: Optional[str] = None) -> str:
    """Write ``payload`` (must carry a ``config`` mapping of known knobs)
    as the kernel's artifact — canonical JSON, byte-deterministic for
    identical payloads — and invalidate the loader cache."""
    _check_kernel(kernel)
    config = payload.get("config")
    if not isinstance(config, Mapping) or not config:
        raise ValueError("tuned payload needs a non-empty 'config' mapping")
    bad = [k for k in config if k not in KERNEL_KNOBS[kernel]]
    if bad:
        raise ValueError(f"{kernel} has no knob(s) {', '.join(sorted(bad))}")
    out = path or tuned_path(kernel)
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w", encoding="utf-8") as fh:
        json.dump(payload, fh, indent=2, sort_keys=True)
        fh.write("\n")
    invalidate_cache()
    return out


def validate_blocks(kernel: str, blocks: Mapping[str, int],
                    dims: Mapping[str, int],
                    vmem_bytes: Optional[float] = None) -> None:
    """Fail fast on block configs Pallas would choke on.

    ``blocks`` are the effective (shape-clamped) knob values, ``dims``
    maps each knob to the array dimension it must divide, and
    ``vmem_bytes`` is the wrapper's estimate of the per-grid-step VMEM
    working set (pipelining double-buffer included), held to
    :data:`VMEM_LIMIT_BYTES`.  Raises a
    ``ValueError`` naming the offending knob(s) instead of letting the
    kernel die in lowering with a shape assert."""
    _check_kernel(kernel)
    problems = []
    for knob, block in blocks.items():
        dim = dims[knob]
        if block <= 0:
            problems.append(f"{knob}={block} must be positive")
        elif dim % block:
            problems.append(f"{knob}={block} does not divide the "
                            f"dimension it tiles ({dim})")
    if vmem_bytes is not None and vmem_bytes > VMEM_LIMIT_BYTES:
        cfg = ", ".join(f"{k}={v}" for k, v in sorted(blocks.items()))
        problems.append(
            f"blocks ({cfg}) need ~{vmem_bytes / 2 ** 20:.0f} MiB of VMEM "
            f"per grid step, over the {VMEM_LIMIT_BYTES / 2 ** 20:.0f} MiB "
            f"scoped-VMEM limit the kernel is compiled under")
    if problems:
        raise ValueError(
            f"invalid block config for kernel {kernel!r}: "
            + "; ".join(problems)
            + ".  Pass explicit kwargs, set REPRO_TUNED_"
            + kernel.upper() + "_<KNOB>, or re-run `python -m repro tune` "
            "(REPRO_TUNED=off ignores tuned.json)")
