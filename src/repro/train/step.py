"""Train-step factory: loss → grads → clip → AdamW, as one SPMD program.

The returned ``train_step(state, batch)`` is pjit-compatible: all
distribution comes from in/out shardings supplied by the launch layer.
Gradient accumulation (microbatching) is a ``lax.scan`` over batch slices so
compute/comm overlap falls out of XLA's scheduler: the all-reduce of
microbatch k overlaps the backward of microbatch k+1.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.api import ModelApi
from .optimizer import (AdamWConfig, adamw_init, adamw_update,
                        clip_by_global_norm, warmup_cosine)

TrainState = Dict[str, Any]      # {"params", "opt": {m,v,count}, "step"}


def make_init_fn(api: ModelApi, opt_cfg: AdamWConfig
                 ) -> Callable[[jax.Array], TrainState]:
    def init_fn(key) -> TrainState:
        params = api.init(key)
        return {"params": params, "opt": adamw_init(params),
                "step": jnp.zeros((), jnp.int32)}
    return init_fn


def _split_microbatches(batch: Dict[str, Any], n: int) -> Dict[str, Any]:
    """[B, ...] → [n, B/n, ...] per leaf (positions [3,B,S] handled)."""
    def split(x):
        if x.ndim >= 3 and x.shape[0] == 3:          # M-RoPE positions
            return x.reshape(3, n, x.shape[1] // n,
                             *x.shape[2:]).swapaxes(0, 1)
        return x.reshape(n, x.shape[0] // n, *x.shape[1:])
    return jax.tree_util.tree_map(split, batch)


def make_train_step(api: ModelApi, opt_cfg: AdamWConfig,
                    num_microbatches: int = 1, grad_specs=None):
    """``grad_specs``: optional PartitionSpec tree for the gradient
    accumulator.  CRITICAL at scale: a replicated-over-data accumulator
    forces XLA to ALL-REDUCE the full gradients once per microbatch
    (observed 507 GB/device/step on jamba train_4k, 16 microbatches).
    Zero-sharded (ZeRO-style) accumulation turns each microbatch's sync
    into a reduce-scatter at 1/|data| the bytes — ~16x less gradient
    traffic."""
    schedule = warmup_cosine(opt_cfg)

    def loss_fn(params, batch):
        loss, metrics = api.loss(params, batch)
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def _constrain_grads(g):
        if grad_specs is None:
            return g
        return jax.tree_util.tree_map(
            lambda a, sp: jax.lax.with_sharding_constraint(a, sp), g,
            grad_specs)

    def train_step(state: TrainState, batch: Dict[str, Any]
                   ) -> Tuple[TrainState, Dict[str, jax.Array]]:
        params = state["params"]
        if num_microbatches > 1:
            micro = _split_microbatches(batch, num_microbatches)

            def accum(carry, mb):
                gsum, lsum = carry
                (l, _m), g = grad_fn(params, mb)
                # constrain THE GRADIENT (not the sum): the partitioner
                # then lowers the pending batch-psum directly into a
                # reduce-scatter instead of all-reduce + slice
                g = _constrain_grads(g)
                gsum = _constrain_grads(jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(jnp.float32), gsum, g))
                return (gsum, lsum + l), None

            g0 = _constrain_grads(jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params))
            (grads, loss), _ = lax.scan(accum, (g0, jnp.zeros(())), micro)
            grads = jax.tree_util.tree_map(
                lambda g: g / num_microbatches, grads)
            loss = loss / num_microbatches
            metrics = {"nll": loss, "aux": jnp.zeros(())}
        else:
            (loss, metrics), grads = grad_fn(params, batch)

        grads, gnorm = clip_by_global_norm(grads, opt_cfg.grad_clip)
        new_params, new_opt, lr = adamw_update(
            opt_cfg, grads, state["opt"], params, schedule)
        new_state = {"params": new_params, "opt": new_opt,
                     "step": state["step"] + 1}
        out_metrics = {"loss": loss, "grad_norm": gnorm, "lr": lr,
                       **{k: v for k, v in metrics.items()}}
        return new_state, out_metrics

    return train_step


def make_eval_step(api: ModelApi):
    def eval_step(params, batch):
        loss, metrics = api.loss(params, batch)
        return {"loss": loss, **metrics}
    return eval_step
