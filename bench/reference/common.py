"""What the references share: the key a seed gives, the matrix product in
the precision asked for, and the norms of one tree's leaves.

The references import nothing of the program.  They rebuild every
weight from the seed themselves (see each family's ``layer_weights``),
so a fault in the program's weights cannot hide in both.
"""
from __future__ import annotations

import json
from typing import Dict

import jax
import jax.numpy as jnp

#: float32 products at full float32 precision: on a TPU the default
#: runs float32 matmuls in bfloat16 passes.
HIGHEST = jax.lax.Precision.HIGHEST

#: The largest finite value of float8_e4m3fn.
_E4M3_MAX = 448.0


class Frozen(dict):
    """A configuration that can be a static argument of ``jax.jit``."""

    def __hash__(self):
        return hash(json.dumps(self, sort_keys=True))


def seed_key(seed: int) -> jax.Array:
    """The key of ``seed``.  ``jax.random.PRNGKey`` keeps only the low 32
    bits of a seed; the high bits are folded in, so seeds that differ
    above bit 31 give different weights."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def fp8(x: jax.Array) -> jax.Array:
    """``x`` rounded to float8_e4m3fn under one scale for the tensor (its
    largest magnitude maps to 448), back in float32.  The gradient passes
    straight through."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _E4M3_MAX
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def matmul(a: jax.Array, b: jax.Array, precision: str) -> jax.Array:
    """``a @ b`` in float32 (``"f32"``), or with both operands rounded to
    fp8 first (``"fp8"``: the control, one step below the bfloat16 the
    configurations state)."""
    if precision == "fp8":
        a, b = fp8(a), fp8(b)
    elif precision != "f32":
        raise ValueError(f"unknown precision {precision!r}")
    return jnp.matmul(a, b, precision=HIGHEST)


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def leaf_norms(tree) -> Dict[str, jax.Array]:
    """``{'/'-joined key path: l2 norm}`` of every leaf."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {"/".join(str(getattr(k, "key", k)) for k in path):
            jnp.sqrt(jnp.sum(jnp.square(leaf.astype(jnp.float32))))
            for path, leaf in flat}
