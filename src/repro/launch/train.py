"""Distributed training driver.

``python -m repro.launch.train --arch llama3.2-1b --steps 200 ...``

Production loop: deterministic resumable data pipeline → pjit'd train step
(microbatched, remat, logical sharding rules) → async checkpoints with
keep-k GC → preemption-safe SIGTERM handling → straggler watchdog → elastic
restart via resharded restore.  On this container the mesh spans local CPU
devices; the identical code path drives the 512-chip production mesh (the
dry-run proves those programs compile).
"""
from __future__ import annotations

import argparse
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import CheckpointManager
from repro.core.logging import get_logger
from repro.data import DataConfig, make_pipeline
from repro.distributed import partition as part
from repro.distributed.logical import default_rules, logical_rules
from repro.distributed.straggler import StragglerWatchdog
from repro.launch.mesh import make_host_mesh
from repro.models import build, get_config
from repro.models.config import ModelConfig
from repro.train import AdamWConfig, make_train_step
from repro.train.step import make_init_fn

log = get_logger("train")


def _sharding(mesh, spec_tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def sharded_train_fns(cfg: ModelConfig, opt_cfg: AdamWConfig, mesh,
                      microbatches: int = 1):
    """The train state layout and the jitted programs ``train`` runs on
    ``mesh``: parameters split by the logical rules, AdamW moments
    ZeRO-sharded over ``data``, the state donated to each step.

    Returns ``(state_structs, state_shardings, init, step)``; call
    ``init(key)`` and ``step(state, batch)`` inside ``with mesh,
    logical_rules(default_rules(cfg, mesh))``.
    """
    api = build(cfg)
    init_fn = make_init_fn(api, opt_cfg)
    state_structs = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    params = state_structs["params"]
    zero = part.zero_shard_specs(cfg, params, mesh)
    state_specs = {"params": part.param_specs(cfg, params, mesh),
                   "opt": {"m": zero, "v": zero, "count": P()},
                   "step": P()}
    state_shardings = _sharding(mesh, state_specs)
    init = jax.jit(init_fn, out_shardings=state_shardings)
    step = jax.jit(
        make_train_step(api, opt_cfg, num_microbatches=microbatches),
        in_shardings=(state_shardings, None),
        out_shardings=(state_shardings, None),
        donate_argnums=(0,))
    return state_structs, state_shardings, init, step


def train(arch: str, steps: int = 100, global_batch: int = 8,
          seq_len: int = 256, lr: float = 3e-4, microbatches: int = 1,
          ckpt_dir: Optional[str] = None, ckpt_every: int = 50,
          model_parallel: int = 1, reduced: bool = True,
          log_every: int = 10, seed: int = 0,
          halt_at: Optional[int] = None,
          overrides: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """``halt_at``: stop early (simulated preemption) while keeping the
    ``steps``-horizon LR schedule — resume must continue it exactly."""
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    cfg = cfg.override(**(overrides or {}))
    mesh = make_host_mesh(model=model_parallel)
    rules = default_rules(cfg, mesh)
    opt_cfg = AdamWConfig(lr=lr, total_steps=steps,
                          warmup_steps=max(steps // 20, 5))

    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=seq_len,
                          global_batch=global_batch, seed=seed)

    state_structs, state_shardings, init, step_fn = sharded_train_fns(
        cfg, opt_cfg, mesh, microbatches)

    ckpt = CheckpointManager(ckpt_dir, save_interval=ckpt_every) \
        if ckpt_dir else None

    with mesh, logical_rules(rules):
        if ckpt and ckpt.latest_step() is not None:
            host_state, start = ckpt.restore_or_init(
                state_structs, lambda: None)
            state = jax.device_put(host_state, state_shardings)
            log.info("resumed at step %d", start)
        else:
            state = init(jax.random.PRNGKey(seed))
            start = 0

        if ckpt:
            latest: Dict[str, Any] = {"step": start, "state": state}
            ckpt.install_signal_handler(
                lambda: (latest["step"], latest["state"]))

        watchdog = StragglerWatchdog(num_hosts=jax.process_count())
        pipe = make_pipeline(data_cfg, start_step=start)
        losses = []
        t_start = time.perf_counter()
        for step, batch in pipe:
            if step >= steps or (halt_at is not None and step >= halt_at):
                break
            t0 = time.perf_counter()
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            if cfg.family in ("audio", "encdec"):
                batch["frames"] = jnp.zeros(
                    (batch["tokens"].shape[0], cfg.enc_seq, cfg.d_model),
                    jnp.float32)
            state, metrics = step_fn(state, batch)
            loss = float(metrics["loss"])
            losses.append(loss)
            dt = time.perf_counter() - t0
            watchdog.record_step(np.asarray([dt]))
            if step % log_every == 0 or step == steps - 1:
                log.info("step %d loss %.4f (%.0f tok/s)", step, loss,
                         global_batch * seq_len / dt)
            if ckpt:
                latest = {"step": step + 1, "state": state}
                ckpt.maybe_save(step + 1, state)
        if ckpt:
            ckpt.wait()
        if hasattr(pipe, "close"):
            pipe.close()

    total = time.perf_counter() - t_start
    return {"losses": losses,
            "first_loss": losses[0] if losses else None,
            "last_loss": losses[-1] if losses else None,
            "steps": len(losses), "seconds": total,
            "tokens_per_s": len(losses) * global_batch * seq_len / total}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="train")
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--full-size", action="store_true",
                    help="use the full config (default: reduced)")
    args = ap.parse_args(argv)
    out = train(args.arch, steps=args.steps, global_batch=args.global_batch,
                seq_len=args.seq_len, lr=args.lr,
                microbatches=args.microbatches, ckpt_dir=args.ckpt_dir,
                ckpt_every=args.ckpt_every,
                model_parallel=args.model_parallel,
                reduced=not args.full_size)
    log.info("done: %s", out)
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
