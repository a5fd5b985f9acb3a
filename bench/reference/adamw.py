"""AdamW with decoupled weight decay (Loshchilov & Hutter,
arXiv:1711.05101), bias-corrected moments, a global-norm clip upstream
and a linear-warmup cosine schedule, in float32.

Weight decay applies to every leaf of two or more axes as it is stored.
With the layers stacked on a leading axis, that takes in the per-layer
norm scales and state-space constants, which the usual recipe exempts:
a departure of the program, followed here so that the comparison holds
it to what it does (PERF.md lists it for a later change).
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp


def init(params) -> Dict:
    def zeros():
        return jax.tree_util.tree_map(jnp.zeros_like, params)

    return {"params": params, "m": zeros(), "v": zeros(),
            "count": jnp.zeros((), jnp.int32)}


def schedule(opt: Dict, count: jax.Array) -> jax.Array:
    t = count.astype(jnp.float32)
    warm, total = opt["warmup_steps"], opt["total_steps"]
    frac = jnp.clip((t - warm) / max(total - warm, 1), 0.0, 1.0)
    lo = opt["min_lr_ratio"]
    cosine = lo + (1 - lo) * 0.5 * (1 + jnp.cos(math.pi * frac))
    return opt["lr"] * jnp.where(t < warm, t / max(warm, 1), cosine)


def clip_by_global_norm(grads, max_norm: float):
    leaves = jax.tree_util.tree_leaves(grads)
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return jax.tree_util.tree_map(lambda g: g * scale, grads), norm


def update(opt: Dict, grads, state: Dict) -> Dict:
    count = state["count"] + 1
    lr = schedule(opt, count)
    b1, b2 = opt["b1"], opt["b2"]
    c1 = 1 - b1 ** count.astype(jnp.float32)
    c2 = 1 - b2 ** count.astype(jnp.float32)
    m = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g,
                               state["m"], grads)
    v = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g,
                               state["v"], grads)

    def step(p, m, v):
        d = (m / c1) / (jnp.sqrt(v / c2) + opt["eps"])
        if p.ndim >= 2:
            d = d + opt["weight_decay"] * p
        return p - lr * d

    params = jax.tree_util.tree_map(step, state["params"], m, v)
    return {"params": params, "m": m, "v": v, "count": count}
