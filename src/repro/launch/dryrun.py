import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (including repro.*):
# jax locks the device count at first initialization, and the dry-run needs
# 512 placeholder host devices to build the production mesh.  Smoke tests
# and benchmarks never import this module, so they see 1 device.

"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture × input-shape × mesh) cell:
  1. build the production mesh (16×16 single-pod / 2×16×16 multi-pod);
  2. construct ShapeDtypeStruct stand-ins for every model input (no
     allocation — full-size configs never touch device memory);
  3. ``jax.jit(step, in_shardings, out_shardings).lower(...).compile()``;
  4. print ``memory_analysis()`` (proves it fits) and ``cost_analysis()``
     (FLOPs/bytes for §Roofline), parse collective bytes from the HLO;
  5. append the cell's record to a results JSON.

Usage:
  python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k
  python -m repro.launch.dryrun --arch all --shape all --multi-pod
"""
import argparse
import json
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.distributed import partition as part
from repro.distributed.logical import default_rules, logical_rules
from repro.launch import inputs as inp
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.models import build, get_config, list_archs
from repro.models.config import ModelConfig
from repro.roofline.analysis import analyze_compiled, model_flops
from repro.train import AdamWConfig, make_train_step
from repro.train.step import make_init_fn

RESULTS_DEFAULT = "results/dryrun"


# ---------------------------------------------------------------------------
# per-shape config adjustments (baseline implementation policy, recorded)
# ---------------------------------------------------------------------------

def tune_config(cfg: ModelConfig, shape: str, overrides: Dict[str, Any]
                ) -> ModelConfig:
    """Baseline numerics/memory policy for full-scale lowering.

    remat=full + seq-chunked loss for training; these are the *paper-
    faithful baseline* settings — §Perf hillclimbing changes them per-cell
    and records deltas.
    """
    tuned: Dict[str, Any] = {}
    kind = inp.SHAPES[shape].kind
    if kind == "train":
        tuned.update(remat="full", loss_chunk=1024)
    if kind == "prefill":
        tuned.update(loss_chunk=0)
    tuned.update({k: v for k, v in overrides.items()
                  if k not in ("microbatches", "param_mode", "dp_layout", "no_grad_spec")})
    return cfg.override(**tuned)


def auto_param_mode(cfg: ModelConfig, mesh) -> str:
    """fsdp when fp32 params per device (TP-only) would exceed ~2 GiB."""
    m = part.axis_size(mesh, "model")
    per_dev = cfg.num_params() * 4 / m
    return "fsdp" if per_dev > 2 * 2**30 else "tp"


def microbatches_for(cfg: ModelConfig, shape: str, mesh) -> int:
    """Bound the remat residual stack (L × B_loc × S × d × 2B) to ~1 GiB.

    Empirically (llama3.2-1b train_4k, 16×16): mb=1 → 14.7 GiB temp,
    mb=4 → 3.9 GiB — the residual stack dominates training memory once
    remat=full and the flash custom-VJP are in place.
    """
    sh = inp.SHAPES[shape]
    if sh.kind != "train":
        return 1
    dp = part.dp_size(mesh)
    b_loc = max(sh.global_batch // dp, 1)
    layers = cfg.num_layers + cfg.num_enc_layers
    resid = layers * b_loc * sh.seq_len * cfg.d_model * 2
    # unshardable heads (whisper/qwen2-vl: 12 H on a 16-way axis) leave
    # attention activations replicated across 'model' — budget tighter
    m = part.axis_size(mesh, "model")
    if cfg.num_heads % m != 0 and cfg.family != "ssm":
        resid *= 4
    mb = 1
    while resid / mb > 1 * 2**30 and mb < b_loc:
        mb *= 2
    return mb


# ---------------------------------------------------------------------------
# lowering helpers
# ---------------------------------------------------------------------------

def _sharding(mesh, spec_tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def lower_cell(arch: str, shape: str, *, multi_pod: bool = False,
               overrides: Optional[Dict[str, Any]] = None,
               donate: bool = True):
    """Lower+compile one (arch × shape × mesh) cell.  Returns (compiled,
    meta dict)."""
    overrides = overrides or {}
    if overrides.get("dp_layout"):
        # §Perf re-mesh experiment: same 256/512 chips, logical axes
        # (data=256, model=1) — pure DP+ZeRO, no TP activation psums.
        mshape = (2, 256, 1) if multi_pod else (256, 1)
        axes = ("pod", "data", "model") if multi_pod else ("data", "model")
        mesh = make_mesh(mshape, axes)
        mesh_name = ("pod2x256x1" if multi_pod else "pod256x1")
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
        mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    chips = int(len(jax.devices()) if multi_pod else 256)
    cfg = tune_config(get_config(arch), shape, overrides)
    ok, why = inp.shape_applicable(cfg, shape)
    if not ok:
        return None, {"arch": arch, "shape": shape, "mesh": mesh_name,
                      "status": "skip", "reason": why}
    api = build(cfg)
    sh = inp.SHAPES[shape]
    kind = sh.kind

    param_structs = jax.eval_shape(api.init, jax.ShapeDtypeStruct((2,),
                                                                  jnp.uint32))
    mode = overrides.get("param_mode") or auto_param_mode(cfg, mesh)
    if mode == "fsdp":
        pspecs = part.zero_shard_specs(cfg, param_structs, mesh)
    else:
        pspecs = part.param_specs(cfg, param_structs, mesh)

    t0 = time.perf_counter()
    if kind == "train":
        mb = int(overrides.get("microbatches") or
                 microbatches_for(cfg, shape, mesh))
        opt_cfg = AdamWConfig()
        grad_specs = None
        if mb > 1 and not overrides.get("no_grad_spec"):
            grad_specs = part.zero_shard_specs(cfg, param_structs, mesh)
        train_step = make_train_step(api, opt_cfg, num_microbatches=mb,
                                     grad_specs=grad_specs)
        state_structs = jax.eval_shape(make_init_fn(api, opt_cfg),
                                       jax.ShapeDtypeStruct((2,), jnp.uint32))
        opt_specs = {
            "m": part.zero_shard_specs(cfg, param_structs, mesh),
            "v": part.zero_shard_specs(cfg, param_structs, mesh),
            "count": P(),
        }
        state_specs = {"params": pspecs, "opt": opt_specs, "step": P()}
        batch_structs = inp.input_specs(cfg, shape)
        batch_specs = part.input_specs_tree(cfg, batch_structs, mesh)
        with mesh, logical_rules(default_rules(cfg, mesh)):
            jitted = jax.jit(
                train_step,
                in_shardings=(_sharding(mesh, state_specs),
                              _sharding(mesh, batch_specs)),
                out_shardings=(_sharding(mesh, state_specs), None),
                donate_argnums=(0,) if donate else ())
            lowered = jitted.lower(state_structs, batch_structs)
            compiled = lowered.compile()
        extra = {"microbatches": mb}
        tokens = sh.global_batch * sh.seq_len
    elif kind == "prefill":
        serve_params = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16),
            param_structs)
        cache_structs = jax.eval_shape(
            lambda: api.init_cache(sh.global_batch, sh.seq_len))
        cspecs = part.cache_specs(cfg, cache_structs, mesh)
        batch_structs = inp.input_specs(cfg, shape)
        batch_specs = part.input_specs_tree(cfg, batch_structs, mesh)

        def prefill_step(params, batch, cache):
            return api.prefill(params, batch, cache)

        with mesh, logical_rules(default_rules(cfg, mesh)):
            jitted = jax.jit(
                prefill_step,
                in_shardings=(_sharding(mesh, pspecs),
                              _sharding(mesh, batch_specs),
                              _sharding(mesh, cspecs)),
                out_shardings=(None, _sharding(mesh, cspecs)),
                donate_argnums=(2,) if donate else ())
            lowered = jitted.lower(serve_params, batch_structs,
                                   cache_structs)
            compiled = lowered.compile()
        extra = {}
        tokens = sh.global_batch * sh.seq_len
    else:  # decode
        serve_params = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16),
            param_structs)
        cache_structs = jax.eval_shape(
            lambda: api.init_cache(sh.global_batch, sh.seq_len))
        cspecs = part.cache_specs(cfg, cache_structs, mesh)
        tok_struct = jax.ShapeDtypeStruct((sh.global_batch, 1), jnp.int32)
        tok_spec = (P(part.batch_axes(mesh), None)
                    if sh.global_batch % part.dp_size(mesh) == 0 else P())

        def decode(params, tokens, cache):
            return api.decode_step(params, tokens, cache)

        with mesh, logical_rules(default_rules(cfg, mesh)):
            jitted = jax.jit(
                decode,
                in_shardings=(_sharding(mesh, pspecs_bf16(pspecs)),
                              NamedSharding(mesh, tok_spec),
                              _sharding(mesh, cspecs)),
                out_shardings=(None, _sharding(mesh, cspecs)),
                donate_argnums=(2,) if donate else ())
            lowered = jitted.lower(serve_params, tok_struct, cache_structs)
            compiled = lowered.compile()
        extra = {}
        tokens = sh.global_batch * 1

    compile_s = time.perf_counter() - t0
    mflops = model_flops(cfg, tokens, kind)
    terms = analyze_compiled(compiled, arch, shape, mesh_name, chips, mflops)
    from repro.roofline.hlo import cpu_widening_artifact_bytes
    artifact = cpu_widening_artifact_bytes(compiled.as_text())
    mem = compiled.memory_analysis()
    temp = getattr(mem, "temp_size_in_bytes", 0) or 0
    args_b = getattr(mem, "argument_size_in_bytes", 0) or 0
    meta = {
        "arch": arch, "shape": shape, "mesh": mesh_name, "status": "ok",
        "kind": kind, "chips": chips, "compile_s": round(compile_s, 1),
        "param_mode": mode,
        "tokens": tokens,
        "memory": {
            "argument_bytes": args_b,
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": temp,
            # CPU backend widens scan-carried bf16 buffers to f32 (no
            # native bf16); the TPU executable keeps them bf16.  The
            # TPU-corrected peak removes those f32 twins.
            "cpu_widening_artifact_bytes": artifact,
            "peak_bytes": temp + args_b,
            "tpu_peak_bytes": temp + args_b - artifact,
        },
        "roofline": terms.to_dict(),
        **extra,
    }
    return compiled, meta


def pspecs_bf16(pspecs):
    return pspecs     # specs are dtype-independent; hook kept for clarity


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def run_cells(archs, shapes, multi_pod: bool, out_dir: str,
              overrides: Optional[Dict[str, Any]] = None,
              tag: str = "") -> int:
    os.makedirs(out_dir, exist_ok=True)
    failures = 0
    for arch in archs:
        for shape in shapes:
            mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
            suffix = f"-{tag}" if tag else ""
            path = os.path.join(
                out_dir, f"{arch}--{shape}--{mesh_name}{suffix}.json")
            if os.path.exists(path):
                print(f"[dryrun] SKIP (cached) {path}")
                continue
            print(f"[dryrun] {arch} × {shape} × {mesh_name} ...",
                  flush=True)
            try:
                compiled, meta = lower_cell(arch, shape,
                                            multi_pod=multi_pod,
                                            overrides=overrides)
                if meta["status"] == "ok":
                    mem = meta["memory"]
                    print(f"  compiled in {meta['compile_s']}s; "
                          f"args={_gb(mem['argument_bytes'])} "
                          f"temp={_gb(mem['temp_bytes'])} "
                          f"dominant={meta['roofline']['dominant']}")
                else:
                    print(f"  SKIP: {meta['reason']}")
            except Exception as e:  # noqa: BLE001
                failures += 1
                meta = {"arch": arch, "shape": shape,
                        "mesh": mesh_name, "status": "fail",
                        "error": f"{type(e).__name__}: {e}",
                        "traceback": traceback.format_exc(limit=8)}
                print(f"  FAIL: {type(e).__name__}: {e}")
            with open(path, "w") as f:
                json.dump(meta, f, indent=2, default=str)
    return failures


def _gb(x) -> str:
    return "n/a" if x is None else f"{x / 2**30:.2f}GiB"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="dryrun")
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default=RESULTS_DEFAULT)
    ap.add_argument("--tag", default="", help="suffix for experiment files")
    ap.add_argument("--override", action="append", default=[],
                    help="cfg override key=value (e.g. remat=none)")
    args = ap.parse_args(argv)
    archs = list(list_archs()) if args.arch == "all" else args.arch.split(",")
    shapes = (list(inp.SHAPES) if args.shape == "all"
              else args.shape.split(","))
    overrides: Dict[str, Any] = {}
    for ov in args.override:
        k, v = ov.split("=", 1)
        try:
            v = json.loads(v)
        except json.JSONDecodeError:
            pass
        overrides[k] = v
    return run_cells(archs, shapes, args.multi_pod, args.out,
                     overrides=overrides, tag=args.tag)


if __name__ == "__main__":
    sys.exit(main())
