"""Partition rules: map every parameter/activation/cache leaf to a
PartitionSpec on the (pod, data, model) production mesh.

Strategy (Megatron-style TP × DP, EP for MoE, sequence-sharding for caches):

  * batch dims           → ('pod','data') (DP; pod composes hierarchically)
  * attention q/o        → heads on 'model' when H % model == 0, else
                           replicated (whisper/qwen2-vl have 12 heads on a
                           16-way axis; attention then parallelizes over
                           batch only — recorded as waste in §Roofline)
  * attention k/v        → 'model' when K % model == 0 (MHA-ish configs),
                           else replicated (GQA kv-head replication — the
                           standard Megatron treatment when TP > kv_heads)
  * MLP ff dim           → 'model'
  * MoE expert dim       → 'model' (EP: 64/16 = 4 experts per device)
  * Mamba d_inner/heads  → 'model' (SSD heads are embarrassingly parallel)
  * embeddings           → vocab on 'model' when divisible, else d_model
  * KV cache             → kv-heads on 'model' when divisible, else
                           *sequence* on 'model' (flash-decode style); batch
                           on ('pod','data')
  * SSM state            → heads on 'model', batch on DP
  * optimizer state      → param spec + 'data' on the largest free dim
                           (ZeRO-1 style; see zero_shard_specs)

All rules check divisibility against the actual mesh shape and fall back to
replication — a config can never fail to shard, it can only shard worse
(visible in the roofline, never a crash).
"""
from __future__ import annotations

from typing import Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# ---------------------------------------------------------------------------
# mesh helpers
# ---------------------------------------------------------------------------

def axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.shape else 1

def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    return ("pod", "data") if "pod" in mesh.shape else ("data",)

def dp_size(mesh: Mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in batch_axes(mesh)]))

def batch_spec(mesh: Mesh, ndim: int, batch_dim: int = 0) -> P:
    spec = [None] * ndim
    spec[batch_dim] = batch_axes(mesh)
    return P(*spec)


def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


# ---------------------------------------------------------------------------
# parameter rules
# ---------------------------------------------------------------------------

def _spec_with(ndim: int, dim: int, axis: str) -> P:
    spec: list = [None] * ndim
    spec[dim % ndim] = axis
    return P(*spec)


def param_rule(cfg, name: str, shape: Tuple[int, ...], mesh: Mesh) -> P:
    """One leaf → PartitionSpec.  ``name`` is the '/'-joined tree path."""
    m = axis_size(mesh, "model")
    nd = len(shape)
    leaf = name.rsplit("/", 1)[-1]
    H, K = cfg.num_heads, cfg.num_kv_heads
    heads_ok = H % m == 0
    kv_ok = K % m == 0
    div = lambda dim: shape[dim % nd] % m == 0

    if "embed" in name and leaf == "table":
        if div(-2):                      # vocab
            return _spec_with(nd, -2, "model")
        # non-divisible vocab (whisper 51865): replicate.
        # Sharding d_model instead trips the SPMD partitioner on the
        # token-gather inside the microbatch loop (observed: whisper
        # train_4k, "slice dim size 768 > dynamic slice dimension 48").
        return P()
    if leaf == "pos_embed" or "pos_embed" in name:
        # replicated: d-sharding here propagates onto the token-embedding
        # gather (x = embed + pos_embed) and trips the SPMD partitioner
        return P()

    # attention
    if leaf == "wq":
        return _spec_with(nd, -1, "model") if heads_ok and div(-1) else P()
    if leaf in ("wk", "wv"):
        return _spec_with(nd, -1, "model") if kv_ok and div(-1) else P()
    if leaf == "wo":
        return _spec_with(nd, -2, "model") if heads_ok and div(-2) else P()

    # MoE: expert dim is always third-from-last ([.., E, d, f] / [.., E, f, d])
    if ("/moe" in name or name.startswith("moe")) and "shared" not in name:
        if leaf == "router":
            return P()
        if leaf in ("w_up", "w_gate", "w_down") and nd >= 3:
            E = shape[-3]
            if E % m == 0:
                return _spec_with(nd, -3, "model")
            return _spec_with(nd, -1, "model") if div(-1) else P()
        # shared-expert MLP falls through to the dense rules below

    # dense MLP
    if leaf in ("w_up", "w_gate"):
        return _spec_with(nd, -1, "model") if div(-1) else P()
    if leaf == "w_down":
        return _spec_with(nd, -2, "model") if div(-2) else P()

    # mamba2
    if leaf in ("w_z", "w_x"):
        return _spec_with(nd, -1, "model") if div(-1) else P()
    if leaf in ("w_B", "w_C", "conv_B", "conv_C", "conv_B_bias",
                "conv_C_bias"):
        return P()
    if leaf == "w_dt":
        return _spec_with(nd, -1, "model") if div(-1) else P()
    if leaf in ("conv_x", "conv_x_bias"):
        return _spec_with(nd, -1, "model") if div(-1) else P()
    if leaf in ("A_log", "D", "dt_bias"):
        return _spec_with(nd, -1, "model") if div(-1) else P()
    if leaf == "out_proj":
        return _spec_with(nd, -2, "model") if div(-2) else P()
    if "mamba" in name and leaf == "scale":     # gated-norm over d_inner
        return _spec_with(nd, -1, "model") if div(-1) else P()

    # norms / scalars / anything small
    return P()


def param_specs(cfg, params_tree, mesh: Mesh):
    """Tree of PartitionSpec matching a params (or eval_shape) tree."""
    def rule(path, leaf):
        return param_rule(cfg, _path_str(path), tuple(leaf.shape), mesh)
    return jax.tree_util.tree_map_with_path(rule, params_tree)


# ---------------------------------------------------------------------------
# optimizer-state rules (ZeRO-1 style)
# ---------------------------------------------------------------------------

def zero_shard_specs(cfg, params_tree, mesh: Mesh, axis: str = "data"):
    """Param spec + ``axis`` on the largest still-unsharded divisible dim.

    Applied to AdamW m/v (and optionally fp32 masters): optimizer state is
    additionally sharded over the data axis, cutting its per-device memory
    by |data| — the ZeRO-1 trick, expressed purely as shardings.
    """
    d = axis_size(mesh, axis)

    def rule(path, leaf):
        spec = list(param_rule(cfg, _path_str(path), tuple(leaf.shape), mesh))
        spec += [None] * (len(leaf.shape) - len(spec))
        best, best_size = None, 0
        for i, s in enumerate(spec):
            if s is None and leaf.shape[i] % d == 0 and leaf.shape[i] > best_size:
                best, best_size = i, leaf.shape[i]
        if best is not None and best_size > 1:
            spec[best] = axis
        return P(*spec)

    return jax.tree_util.tree_map_with_path(rule, params_tree)


# ---------------------------------------------------------------------------
# activation / input / cache rules
# ---------------------------------------------------------------------------

def input_specs_tree(cfg, batch_tree, mesh: Mesh):
    """Shardings for a training/prefill input batch (by leaf name)."""
    b = batch_axes(mesh)
    dp = dp_size(mesh)

    def rule(path, leaf):
        name = _path_str(path)
        nd = len(leaf.shape)
        if name == "positions" and nd == 3:      # M-RoPE [3,B,S]
            return P(None, b, None) if leaf.shape[1] % dp == 0 else P()
        if nd == 0 or leaf.shape[0] % dp != 0:   # e.g. batch=1 long-context
            return P(*([None] * nd))
        return P(b, *([None] * (nd - 1)))

    return jax.tree_util.tree_map_with_path(rule, batch_tree)


def cache_specs(cfg, cache_tree, mesh: Mesh):
    """Shardings for a KV/SSM cache tree (see module docstring)."""
    m = axis_size(mesh, "model")
    dp = dp_size(mesh)
    b = batch_axes(mesh)
    kv_ok = cfg.num_kv_heads % m == 0

    def rule(path, leaf):
        name = _path_str(path)
        nd = len(leaf.shape)
        if nd == 0:                                        # pos scalar
            return P()
        if name.rsplit("/", 1)[-1] in ("k", "v", "xk", "xv"):
            # [L, B, S, K, hd] (dense/encdec) or [nb, B, S, K, hd] (hybrid)
            bax = b if leaf.shape[1] % dp == 0 else None
            if kv_ok:
                return P(None, bax, None, "model", None)
            if leaf.shape[2] % m == 0:
                return P(None, bax, "model", None, None)   # sequence shard
            return P(None, bax, None, None, None)
        if "state" in name:
            # [L, B, H, P, N] or [nb, n_ssm, B, H, P, N]
            hdim = nd - 3
            spec = [None] * nd
            if leaf.shape[hdim - 1] % dp == 0:
                spec[hdim - 1] = b
            if leaf.shape[hdim] % m == 0:
                spec[hdim] = "model"
            return P(*spec)
        if "conv" in name:
            # [L, B, k-1, C] or [nb, n_ssm, B, k-1, C]
            spec = [None] * nd
            if leaf.shape[nd - 3] % dp == 0:
                spec[nd - 3] = b
            if leaf.shape[-1] % m == 0 and leaf.shape[-1] >= m:
                spec[-1] = "model"
            return P(*spec)
        return P()

    return jax.tree_util.tree_map_with_path(rule, cache_tree)


def shardings_of(specs_tree, mesh: Mesh):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs_tree,
        is_leaf=lambda x: isinstance(x, P))
