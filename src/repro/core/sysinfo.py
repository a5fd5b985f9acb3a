"""System characterization context — what SCOPE puts in the JSON ``context``.

Google Benchmark emits a ``context`` block (date, host, cpu info, build
type).  We extend it with the JAX/TPU-stack facts that matter for systems
characterization: backend, device kinds/counts, mesh shape if active, jax &
jaxlib versions, and relevant XLA flags.  This block is what makes two
benchmark JSON files comparable across systems — the heart of SCOPE's
portability story.
"""
from __future__ import annotations

import datetime
import hashlib
import json
import os
import platform
import types
from typing import Any, Dict, Mapping, Optional

# Published per-chip peaks, keyed by ``device_kind`` as JAX reports it.
# Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 819
# GB/s HBM).  Immutable on purpose: benchmark bodies read these at call
# time, and the instance fingerprint (repro.core.fingerprint) only hashes
# source — a mutable table here could change measurements without
# changing digests (the SCOPE110 hazard).
DEVICE_PEAKS = types.MappingProxyType({
    "TPU v5 lite": types.MappingProxyType({
        "peak_bf16_flops": 197e12,     # FLOP/s per chip
        "hbm_bandwidth": 819e9,        # B/s per chip
    }),
})


def device_peaks(device) -> Optional[Mapping[str, float]]:
    """The peak rates of the device a measurement ran on.

    ``None`` on the CPU, which has no published roofline: callers write
    no roofline counter there.  Any other device must be in
    :data:`DEVICE_PEAKS`; an unknown kind raises instead of borrowing
    another chip's peaks.
    """
    if device.platform == "cpu":
        return None
    try:
        return DEVICE_PEAKS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for {device.platform} device kind "
            f"{device.device_kind!r}: add them, with their source, to "
            f"repro.core.sysinfo.DEVICE_PEAKS") from None


# The modeled target of the paths that model a TPU v5e pod without
# running on one: the dry-run on placeholder devices
# (repro.roofline.analysis) and comm/collective_modeled_v5e.
TPU_V5E = types.MappingProxyType({
    **DEVICE_PEAKS["TPU v5 lite"],
    "ici_link_bandwidth": 50e9,    # B/s per link (~50 GB/s/link)
    "ici_links_per_chip": 4,       # 2D torus: +x, -x, +y, -y
    "hbm_bytes": 16 * 2 ** 30,     # 16 GiB HBM per chip
    "mxu_shape": (128, 128),       # systolic array tile
    "dcn_bandwidth": 25e9,         # B/s per host cross-pod (modeled)
})


def _cpu_info() -> Dict[str, Any]:
    info: Dict[str, Any] = {
        "machine": platform.machine(),
        "processor": platform.processor() or "unknown",
        "num_cpus": os.cpu_count() or 1,
    }
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith("model name"):
                    info["model_name"] = line.split(":", 1)[1].strip()
                    break
    except OSError:
        pass
    return info


def _jax_info() -> Dict[str, Any]:
    try:
        import jax
        devs = jax.devices()
        return {
            "jax_version": jax.__version__,
            "backend": jax.default_backend(),
            "device_count": len(devs),
            "device_kind": devs[0].device_kind if devs else "none",
        }
    except Exception as e:  # pragma: no cover - jax import failure
        return {"jax_version": "unavailable", "error": str(e)}


# Context keys that determine whether two runs are comparable: the
# machine, the accelerator stack, and the XLA configuration — NOT the
# date/run-id, which differ on every run by construction.
_DIGEST_KEYS = (
    "host_name", "machine", "processor", "num_cpus", "model_name",
    "jax_version", "backend", "device_count", "device_kind",
    "xla_flags", "scope_version",
)


def context_digest(ctx: Dict[str, Any]) -> str:
    """Short stable digest of a context's comparability-relevant facts.

    Two runs with the same digest were produced by the same
    host/accelerator-stack configuration; run-history records carry it
    so cross-machine records are visibly not comparable.
    """
    facts = {k: ctx.get(k) for k in _DIGEST_KEYS}
    blob = json.dumps(facts, sort_keys=True, default=str)
    return hashlib.sha1(blob.encode("utf-8")).hexdigest()[:12]


def build_context(extra: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """The ``context`` object written at the top of every result JSON."""
    ctx: Dict[str, Any] = {
        "date": datetime.datetime.now().isoformat(timespec="seconds"),
        "host_name": platform.node(),
        "executable": "scope",
        "scope_version": "1.0.0-jax",
        "library_build_type": "release",
        "caches": [],
        **_cpu_info(),
        **_jax_info(),
        "xla_flags": os.environ.get("XLA_FLAGS", ""),
    }
    if extra:
        ctx.update(extra)
    return ctx
