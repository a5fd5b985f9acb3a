"""A decoder-only transformer's published keys, as the program's config.

Every size comes from the configuration file; ``program`` in the file
adds the program's own settings (attention chunking), which change no
mathematics.
"""
from __future__ import annotations

from typing import Dict


def program_config(model: Dict, **settings):
    from repro.models.config import ModelConfig

    d, H = model["hidden_size"], model["num_attention_heads"]
    return ModelConfig(
        name=model["name"], family="dense",
        num_layers=model["num_hidden_layers"], d_model=d, num_heads=H,
        num_kv_heads=model["num_key_value_heads"],
        head_dim=model.get("head_dim") or d // H,
        d_ff=model["intermediate_size"], vocab_size=model["vocab_size"],
        rope_theta=float(model["rope_theta"]),
        norm_eps=model["rms_norm_eps"],
        tie_embeddings=bool(model.get("tie_word_embeddings", False)),
        **{**model.get("program", {}), **settings})
