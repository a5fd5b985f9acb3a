"""Small cells for the CPU tests: the tiny configurations beside this
file, with traffic and limits of their own."""
from __future__ import annotations

import copy
import json
import os

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

#: The bf16 peak that the readers divide by; the CPU has no entry in
#: bench/peaks.json.
PEAKS = {"bf16_flops": 197e12}

OPTIMIZER = {"lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
             "weight_decay": 0.1, "grad_clip": 1.0, "warmup_steps": 10,
             "total_steps": 1000, "min_lr_ratio": 0.1}


def model(name: str) -> dict:
    with open(os.path.join(DATA, f"{name}.json")) as f:
        return json.load(f)


def serve_cell() -> dict:
    return {"name": "tiny-serve", "config": "tiny-transformer",
            "kind": "serve", "chips": 1, "rate": 20.0, "preroll": 2,
            "trace": {"start_s": 0.5, "seconds": 0.5},
            "check": {"samples": 4, "logit_gap": 0.01},
            "config_file": model("tiny-transformer"),
            "mix": {"kind": "serve", "arrival": "poisson", "pool_seed": 3,
                    "prompt": {"median": 24, "sigma": 0.6, "min": 8,
                               "max": 60},
                    "output": {"median": 8, "sigma": 0.5, "min": 4,
                               "max": 16}}}


def train_cell(config: str = "tiny-transformer") -> dict:
    return {"name": "tiny-train", "config": config, "kind": "train",
            "chips": 1, "mesh": [1, 1], "global_batch": 2,
            "program": {"remat": "full", "loss_chunk": 32},
            "optimizer": dict(OPTIMIZER),
            "trace": {"start_step": 1, "steps": 2},
            "check": {"loss_gap": 2e-4, "grad_gap_mean": 4e-3,
                      "change_gap_mean": 1e-3},
            "config_file": model(config),
            "mix": {"kind": "train", "seq_len": 64, "mean_doc_len": 16}}


BENCHMARK = {
    "end_to_end": [
        {"name": "serve_tok_s", "unit": "tokens/s",
         "workloads": ["tiny-serve"]},
        {"name": "itl_p99_ms", "unit": "ms", "workloads": ["tiny-serve"]},
        {"name": "train_tok_s", "unit": "tokens/s",
         "workloads": ["tiny-train"]},
        {"name": "setup_s", "unit": "s"}],
    "per_layer": [
        {"name": "prefill_mfu.chat", "unit": "%", "moves": "itl_p99_ms",
         "workloads": ["tiny-serve"]},
        {"name": "idle_share.chat", "unit": "%", "moves": "itl_p99_ms",
         "workloads": ["tiny-serve"]},
        {"name": "data_wait_ms.train", "unit": "ms", "moves": "train_tok_s",
         "workloads": ["tiny-train"]},
        {"name": "mfu.train", "unit": "%", "moves": "train_tok_s",
         "workloads": ["tiny-train"]}]}


def benchmark() -> dict:
    return copy.deepcopy(BENCHMARK)
