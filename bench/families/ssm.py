"""A Mamba-2 language model's published keys, as the program's config.

Every size comes from the configuration file: the model's own keys and,
under ``mamba2``, the settings of the published ``Mamba2`` module that
its ``ssm_cfg`` leaves at their defaults.  The program follows those
settings without taking them as options (``MAMBA2_MODULE``), so a file
that states others is refused here rather than run as something it is
not.  ``program`` in the file adds the program's own settings (the SSD
chunk), which change no mathematics.
"""
from __future__ import annotations

from typing import Dict


def padded_vocab(model: Dict) -> int:
    """Rows of the embedding and output head: the vocabulary rounded up
    to ``pad_vocab_size_multiple``."""
    m = model.get("pad_vocab_size_multiple", 1)
    return -(-model["vocab_size"] // m) * m


def program_config(model: Dict, **settings):
    from repro.models.config import ModelConfig
    from repro.models.layers import MAMBA2_MODULE

    m = model["mamba2"]
    differ = {k: (m.get(k), v) for k, v in MAMBA2_MODULE.items()
              if m.get(k) != v}
    if differ or model["d_intermediate"] or not m["rmsnorm"]:
        raise ValueError(
            f"{model['name']}: the program's Mamba-2 block cannot run these "
            f"settings (stated, followed): {differ}, d_intermediate "
            f"{model['d_intermediate']}, rmsnorm {m['rmsnorm']}")
    return ModelConfig(
        name=model["name"], family="ssm", num_layers=model["n_layer"],
        d_model=model["d_model"], num_heads=1, num_kv_heads=1,
        head_dim=m["headdim"], d_ff=0, vocab_size=padded_vocab(model),
        norm_eps=model["norm_epsilon"],
        tie_embeddings=bool(model["tie_embeddings"]),
        ssm_state=m["d_state"], ssm_expand=m["expand"],
        ssm_head_dim=m["headdim"], ssm_conv=m["d_conv"],
        ssm_groups=m["ngroups"],
        **{**model.get("program", {}), **settings})
