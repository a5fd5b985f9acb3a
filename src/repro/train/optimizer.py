"""AdamW + schedules in pure JAX (optax is not available offline).

State layout mirrors optax: ``{"m": tree, "v": tree, "count": i32[]}`` so
checkpoints stay tool-agnostic.  All moments are fp32 regardless of param
dtype; weight decay is decoupled (AdamW); a global-norm clip runs upstream.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10000
    min_lr_ratio: float = 0.1


def warmup_cosine(cfg: AdamWConfig) -> Callable[[jax.Array], jax.Array]:
    def schedule(step):
        step = step.astype(jnp.float32)
        warm = step / jnp.maximum(cfg.warmup_steps, 1)
        prog = (step - cfg.warmup_steps) / jnp.maximum(
            cfg.total_steps - cfg.warmup_steps, 1)
        prog = jnp.clip(prog, 0.0, 1.0)
        cos = cfg.min_lr_ratio + (1 - cfg.min_lr_ratio) * 0.5 * (
            1 + jnp.cos(jnp.pi * prog))
        return cfg.lr * jnp.where(step < cfg.warmup_steps, warm, cos)
    return schedule


@jax.named_scope("optimizer")
def clip_by_global_norm(grads, max_norm: float):
    leaves = jax.tree_util.tree_leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                         for g in leaves))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(gnorm, 1e-9))
    return jax.tree_util.tree_map(
        lambda g: (g.astype(jnp.float32) * scale).astype(g.dtype), grads), gnorm


def adamw_init(params) -> Dict[str, Any]:
    zeros = lambda t: jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), t)
    return {"m": zeros(params), "v": zeros(params),
            "count": jnp.zeros((), jnp.int32)}


@jax.named_scope("optimizer")
def adamw_update(cfg: AdamWConfig, grads, opt_state, params,
                 schedule: Optional[Callable] = None):
    """One AdamW step.  Returns (new_params, new_opt_state, lr)."""
    count = opt_state["count"] + 1
    lr = (schedule or warmup_cosine(cfg))(count)
    b1, b2 = cfg.b1, cfg.b2
    bc1 = 1 - b1 ** count.astype(jnp.float32)
    bc2 = 1 - b2 ** count.astype(jnp.float32)

    def upd(g, m, v, p):
        gf = g.astype(jnp.float32)
        m_new = b1 * m + (1 - b1) * gf
        v_new = b2 * v + (1 - b2) * jnp.square(gf)
        mhat = m_new / bc1
        vhat = v_new / bc2
        step = mhat / (jnp.sqrt(vhat) + cfg.eps)
        if p.ndim >= 2:                   # no decay on norms/biases/scalars
            step = step + cfg.weight_decay * p.astype(jnp.float32)
        p_new = p.astype(jnp.float32) - lr * step
        return p_new.astype(p.dtype), m_new, v_new

    flat = jax.tree_util.tree_map(upd, grads, opt_state["m"],
                                  opt_state["v"], params)
    # unzip the 3-tuples
    p_new = jax.tree_util.tree_map(lambda t: t[0], flat,
                                   is_leaf=lambda x: isinstance(x, tuple))
    m_new = jax.tree_util.tree_map(lambda t: t[1], flat,
                                   is_leaf=lambda x: isinstance(x, tuple))
    v_new = jax.tree_util.tree_map(lambda t: t[2], flat,
                                   is_leaf=lambda x: isinstance(x, tuple))
    return p_new, {"m": m_new, "v": v_new, "count": count}, lr
