"""Mesh construction — every mesh the program builds comes from here.

FUNCTIONS, not module-level constants: importing this module never touches
jax device state (device count is locked at first jax init, and smoke tests
must see 1 CPU device while the dry-run sees 512 placeholders).

Axes are ``Auto``: the model and train code place data with
``NamedSharding``, logical-axis rules and ``with mesh`` and leave the rest
to the SPMD partitioner.  ``jax.make_mesh`` defaults to ``Explicit`` axes,
under which a gather from a vocab-sharded embedding table has no
unambiguous output sharding and the train step fails to trace.

Mesh shapes (TPU v5e pods):
  * single-pod: (16, 16)    axes (data, model)   — 256 chips
  * multi-pod:  (2, 16, 16) axes (pod, data, model) — 512 chips

Axis order is outermost-first so DP gradient reductions decompose
hierarchically: reduce-scatter within a pod over 'data' (ICI), then the
small cross-pod all-reduce over 'pod' (DCN).
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...],
              devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """A mesh of ``Auto`` axes over ``devices`` (default: all of them)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: Optional[int] = None) -> Mesh:
    """(data, model) mesh over whatever devices this host has."""
    n = jax.device_count()
    model = model or 1
    if n % model:
        raise ValueError(f"model-parallel degree {model} does not divide "
                         f"the {n} device(s) of this host")
    return make_mesh((n // model, model), ("data", "model"))
