"""Prove that the program's main paths run on a TPU, in one process.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips: the sharded train step only

With one chip it runs four phases through the entry points a user calls:

  * run stage — ``python -m repro run --jobs 1`` over every builtin scope;
    no instance may end ``error`` or ``crashed``;
  * kernels   — each Pallas op compiles to a Mosaic kernel
    (``tpu_custom_call``), so none runs in interpret mode on the chip;
  * serve     — ``repro.launch.serve.serve_demo`` serves qwen3-1.7b at its
    published widths and all 28 layers; the logits of prefill plus cached
    decode match the model's full forward pass;
  * train     — ``repro.launch.train.train`` takes a few AdamW steps of
    qwen3-1.7b at its published widths, cut to 4 of its 28 layers so
    weights, gradients and optimizer state fit one chip.

``--chips 4`` runs only the train step sharded over a 2x2 (data, model)
mesh and the same step on one of the chips, and compares them.

Everything runs in this process: a chip belongs to one process at a
time.  Each phase prints one line; any failed phase ends the script
non-zero.  The last line of a passing run is one JSON object naming the
device as JAX reports it.  Without a TPU the script exits non-zero before
running anything.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ARCH = "qwen3-1.7b"
TRAIN_LAYERS = 4        # of 28: weights + grads + AdamW state ~8 GB at f32
TRAIN_BATCH = 2         # x 1,024 tokens; batch 4 comes to ~16.7 GB
TRAIN_SEQ = 1024

# Logit agreement of prefill + cached decode with the full forward pass,
# as max |cached - full| over max |full|.  Both paths compute in bf16
# (relative step 2^-8, about 0.4%) and round activations to bf16 at
# different points over 28 layers, so a few steps of difference are
# expected; 5e-2 is about a dozen steps, while a wrong cache entry or
# position moves logits by a relative O(1).
LOGIT_RTOL = 5e-2

# The sharded step against the one-chip step: the tolerances of
# tests/test_multidevice.py's train-step equivalence test.
LOSS_ATOL = 2e-3
PARAM_ATOL = 2e-3


class PhaseFailed(RuntimeError):
    """A phase ran and its output was wrong."""


def _peak_bytes() -> str:
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "n/a" if peak is None else f"{peak / 1e9:.2f} GB"


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def run_stage(results_dir: str, min_time: float = 0.01, scopes=None) -> str:
    """``repro run --jobs 1`` in this process over the builtin scopes (or
    ``scopes``); fails if the run fails or any instance ended error or
    crashed."""
    from repro.core.main import main as repro_main
    from repro.core.orchestrate import failed_instances

    os.makedirs(results_dir, exist_ok=True)
    out = os.path.join(results_dir, "last_run.json")
    argv = ["run", "--jobs", "1", "--benchmark_min_time", str(min_time),
            "--results-dir", results_dir, "--benchmark_out", out]
    for scope in scopes or ():
        argv += ["--enable-scope", scope]
    rc = repro_main(argv)
    with open(out) as f:
        doc = json.load(f)
    failed = failed_instances(doc)
    names = {r.get("run_name") or r["name"] for r in doc["benchmarks"]}
    skipped = sorted({r["name"] for r in doc["benchmarks"]
                      if r.get("skipped")})
    print(f"run stage: {len(names)} instance(s); skipped: "
          f"{', '.join(skipped) or 'none'}", flush=True)
    if failed:
        raise PhaseFailed(f"{len(failed)} instance(s) ended error or "
                          f"crashed: {', '.join(failed)}")
    if rc != 0:
        raise PhaseFailed(f"repro run exited {rc}")
    return f"{len(names) - len(skipped)} instance(s) ran, none failed"


def kernels() -> str:
    """Each Pallas op, through its public wrapper, compiles to a Mosaic
    kernel at one main-path shape."""
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.histogram import histogram
    from repro.kernels.matmul import matmul
    from repro.kernels.rmsnorm import rmsnorm
    from repro.kernels.ssd_scan import ssd

    bf16, f32 = jnp.bfloat16, jnp.float32
    S = jax.ShapeDtypeStruct
    q = S((1, 2048, 16, 128), bf16)
    b, l, h, p, n = 1, 512, 48, 64, 128          # mamba2-780m widths
    cases = {
        "matmul": (lambda x, y: matmul(x, y),
                   S((4096, 4096), bf16), S((4096, 4096), bf16)),
        "flash_attention": (lambda q, k, v: flash_attention(q, k, v),
                            q, q, q),
        "rmsnorm": (lambda x, s: rmsnorm(x, s),
                    S((8192, 2048), bf16), S((2048,), f32)),
        "ssd_scan": (lambda *a: ssd(*a), S((b, l, h, p), bf16),
                     S((b, l, h), f32), S((h,), f32), S((b, l, 1, n), bf16),
                     S((b, l, 1, n), bf16), S((h,), f32)),
        "histogram": (lambda x: histogram(x, 256), S((1 << 20,), jnp.int32)),
    }
    interpreted = []
    for name, (fn, *args) in cases.items():
        text = jax.jit(fn).lower(*args).compile().as_text()
        if "tpu_custom_call" not in text:
            interpreted.append(name)
    if interpreted:
        raise PhaseFailed(f"no Mosaic kernel in the program of "
                          f"{', '.join(interpreted)}: interpret mode")
    return f"{', '.join(cases)} compiled to tpu_custom_call"


def cached_logits_error(engine, req) -> float:
    """Max |cached - full| / max |full| over one served request.

    The cached path is the engine's: a bucket-padded one-row prefill,
    then ragged decode steps over the KV cache, feeding the tokens the
    request generated.  The reference is one forward pass over the same
    tokens."""
    api, params, scfg = engine.api, engine.params, engine.cfg
    prompt, out = list(req.prompt), list(req.output)
    n = len(prompt)
    bucket = min(b for b in scfg.prompt_buckets if b >= n)
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :n] = prompt
    cache = api.init_cache(1, scfg.max_len, scfg.cache_dtype)
    logits, cache = jax.jit(api.prefill)(params, {"tokens": toks}, cache,
                                         logit_pos=n - 1)
    cache = dict(cache, pos=jnp.asarray([n], jnp.int32))
    cached = [logits[0, -1]]
    decode = jax.jit(api.decode_step)
    for tok in out[:-1]:
        logits, cache = decode(params, jnp.asarray([[tok]], jnp.int32),
                               cache)
        cached.append(logits[0, 0])
    seq = np.asarray([prompt + out[:-1]], np.int32)
    full, _ = jax.jit(api.logits)(params, {"tokens": seq})
    full = np.asarray(full[0, n - 1:], np.float32)
    cached = np.asarray(jnp.stack(cached), np.float32)
    return float(np.max(np.abs(cached - full)) / np.max(np.abs(full)))


def serve(arch: str = ARCH, reduced: bool = False, n_requests: int = 8,
          max_tokens: int = 16) -> str:
    """Serve ``n_requests`` to completion; check every request returned
    its tokens and one request's cached-decode logits."""
    from repro.launch.serve import serve_demo

    res = serve_demo(arch, n_requests=n_requests, max_tokens=max_tokens,
                     max_batch=4, reduced=reduced)
    done = res["requests"]
    if len(done) != n_requests:
        raise PhaseFailed(f"{len(done)} of {n_requests} requests finished")
    short = [r.uid for r in done if len(r.output) != max_tokens]
    if short:
        raise PhaseFailed(f"requests {short} returned fewer than "
                          f"{max_tokens} tokens")
    err = cached_logits_error(res["engine"], done[0])
    if not err <= LOGIT_RTOL:
        raise PhaseFailed(f"cached-decode logits differ from the full "
                          f"forward pass by {err:.3g} (limit {LOGIT_RTOL})")
    cfg = res["engine"].api.cfg
    return (f"{arch} d_model {cfg.d_model} x {cfg.num_layers} layers: "
            f"{len(done)} requests x {max_tokens} tokens, "
            f"{res['stats']['throughput_tok_s']:.1f} tok/s; cached vs full "
            f"logits {err:.3g} (limit {LOGIT_RTOL})")


def train(arch: str = ARCH, reduced: bool = False, layers: int = TRAIN_LAYERS,
          batch: int = TRAIN_BATCH, seq: int = TRAIN_SEQ,
          steps: int = 3) -> str:
    """A few AdamW steps; every loss finite."""
    from repro.launch.train import train as run_train
    from repro.models import get_config

    full = get_config(arch).num_layers
    out = run_train(arch, steps=steps, global_batch=batch, seq_len=seq,
                    reduced=reduced, overrides={"num_layers": layers},
                    log_every=1)
    losses = out["losses"]
    if len(losses) != steps or not all(np.isfinite(losses)):
        raise PhaseFailed(f"losses {losses} (want {steps} finite)")
    return (f"{arch} depth cut to {layers} of {full} layers, batch "
            f"{batch} x {seq}: losses "
            f"{', '.join(f'{v:.4f}' for v in losses)}")


def _bytes_per_device(tree) -> dict:
    per = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        for shard in leaf.addressable_shards:
            per[shard.device.id] = per.get(shard.device.id, 0) \
                + shard.data.nbytes
    return per


def sharded_train_step(arch: str = ARCH, reduced: bool = False,
                       layers: int = TRAIN_LAYERS, batch: int = TRAIN_BATCH,
                       seq: int = TRAIN_SEQ) -> str:
    """One train step on a 2x2 (data, model) mesh of four devices and on
    a 1x1 mesh of the first; losses and updated parameters agree."""
    from repro.distributed.logical import default_rules, logical_rules
    from repro.launch.mesh import make_mesh
    from repro.launch.train import sharded_train_fns
    from repro.models import get_config
    from repro.train import AdamWConfig

    cfg = get_config(arch)
    cfg = (cfg.reduced() if reduced else cfg).override(num_layers=layers)
    opt = AdamWConfig(lr=3e-4, total_steps=100, warmup_steps=5)
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(batch, seq), dtype=np.int32)
    devices = jax.devices()[:4]
    meshes = {"2x2": make_mesh((2, 2), ("data", "model"), devices=devices),
              "1 chip": make_mesh((1, 1), ("data", "model"),
                                  devices=devices[:1])}
    loss, params, lines = {}, {}, []
    # one layout at a time: the one-chip state alone nearly fills a chip
    for name, mesh in meshes.items():
        _, _, init, step = sharded_train_fns(cfg, opt, mesh)
        with mesh, logical_rules(default_rules(cfg, mesh)):
            state = init(jax.random.PRNGKey(0))
            held = _bytes_per_device(state)
            state, metrics = step(state, {"tokens": jnp.asarray(tokens)})
            loss[name] = float(metrics["loss"])
        params[name] = jax.device_get(state["params"])
        del state, metrics
        gc.collect()
        lines.append(f"{name}: state bytes per device " + ", ".join(
            f"{d}: {b / 1e9:.3f} GB" for d, b in sorted(held.items())))
    for line in lines:
        print(f"sharded train step: {line}", flush=True)
    d_loss = abs(loss["2x2"] - loss["1 chip"])
    d_param = max(float(np.max(np.abs(np.asarray(a, np.float32)
                                      - np.asarray(b, np.float32))))
                  for a, b in zip(jax.tree_util.tree_leaves(params["2x2"]),
                                  jax.tree_util.tree_leaves(
                                      params["1 chip"])))
    if not (d_loss < LOSS_ATOL and d_param < PARAM_ATOL):
        raise PhaseFailed(f"2x2 vs 1 chip: loss diff {d_loss:.3g} (limit "
                          f"{LOSS_ATOL}), max param diff {d_param:.3g} "
                          f"(limit {PARAM_ATOL})")
    return (f"{arch} {layers} layers, batch {batch} x {seq}: loss 2x2 "
            f"{loss['2x2']:.5f} vs 1 chip {loss['1 chip']:.5f} (diff "
            f"{d_loss:.3g}); max param diff {d_param:.3g}")


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="chip_smoke.py",
                                 description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded train step over four "
                         "chips against one of them")
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 2

    if args.chips == 4:
        phases = [("sharded train step", sharded_train_step)]
    else:
        results = os.path.join(ROOT, "results", "chip_smoke")
        phases = [("run stage", lambda: run_stage(results)),
                  ("kernels", kernels),
                  ("serve", serve),
                  ("train", train)]
    ok = True
    for name, phase in phases:
        t0 = time.perf_counter()
        try:
            detail = phase()
            status = "ok"
        except Exception as e:  # noqa: BLE001 - report, run the rest
            traceback.print_exc()
            detail, status, ok = f"{type(e).__name__}: {e}", "FAILED", False
        gc.collect()
        print(f"phase {name}: {status} in {time.perf_counter() - t0:.1f} s, "
              f"device peak {_peak_bytes()}; {detail}", flush=True)
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
