"""Jamba-style hybrid: Mamba+attention 1:7 interleave with interleaved MoE.

Layer pattern (period ``attn_every`` = 8): attention at block-local index
``attn_offset`` (4), Mamba elsewhere; MoE MLP on odd layers, dense on even.
Jamba uses no positional encoding (the SSM layers carry position), so
``use_rope=False``.

Parameters are organized as *superblocks*: the layer stacks inside one
period are stacked across periods and driven by one ``lax.scan`` — the same
compile-size trick as the dense transformer, despite the mixed layer types.
The per-type KV/SSM caches avoid the 8x memory waste a uniform [L,...] KV
cache would cost on a model where only 1 in 8 layers is attention.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from . import layers as L
from .config import ModelConfig

Params = Dict[str, Any]


def _pattern(cfg: ModelConfig) -> List[Tuple[str, bool]]:
    """Block-local sublayer pattern: [(mixer, is_moe), ...] of length P."""
    P = cfg.attn_every
    out = []
    for j in range(P):
        mixer = "attn" if j % P == cfg.attn_offset else "ssm"
        out.append((mixer, cfg.is_moe_layer(j)))
    return out


def _counts(cfg: ModelConfig) -> Tuple[int, int, int, int]:
    pat = _pattern(cfg)
    n_ssm = sum(m == "ssm" for m, _ in pat)
    n_attn = len(pat) - n_ssm
    n_moe = sum(moe for _, moe in pat)
    n_dense = len(pat) - n_moe
    return n_ssm, n_attn, n_dense, n_moe


def init(cfg: ModelConfig, key) -> Params:
    assert cfg.num_layers % cfg.attn_every == 0, (cfg.num_layers,
                                                  cfg.attn_every)
    nb = cfg.num_layers // cfg.attn_every
    pat = _pattern(cfg)
    keys = jax.random.split(key, cfg.num_layers * 2 + 2)

    def init_superblock(b: int) -> Params:
        mamba, attn, dense, moe = [], [], [], []
        ln1, ln2 = [], []
        for j, (mixer, is_moe) in enumerate(pat):
            gi = b * cfg.attn_every + j
            k1, k2 = keys[2 * gi], keys[2 * gi + 1]
            ln1.append(L.init_rmsnorm(cfg.d_model)["scale"])
            ln2.append(L.init_rmsnorm(cfg.d_model)["scale"])
            if mixer == "ssm":
                mamba.append(L.init_mamba2(k1, cfg))
            else:
                attn.append(L.init_attention(k1, cfg.d_model, cfg.num_heads,
                                             cfg.num_kv_heads, cfg.hd,
                                             cfg.qk_norm))
            if is_moe:
                moe.append(L.init_moe(k2, cfg.d_model,
                                      cfg.moe_num_experts,
                                      cfg.moe_d_ff or cfg.d_ff,
                                      cfg.moe_num_shared, cfg.act))
            else:
                dense.append(L.init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.act))
        stack = lambda xs: jax.tree_util.tree_map(
            lambda *a: jnp.stack(a), *xs) if xs else {}
        return {
            "mamba": stack(mamba), "attn": stack(attn),
            "mlp": stack(dense), "moe": stack(moe),
            "ln1": jnp.stack(ln1), "ln2": jnp.stack(ln2),
        }

    blocks = [init_superblock(b) for b in range(nb)]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *blocks)
    return {
        "embed": L.init_embed(keys[-1], cfg.vocab_size, cfg.d_model),
        "blocks": stacked,
        "final_norm": L.init_rmsnorm(cfg.d_model),
        "unembed": {"table": L.embed_init(keys[-2],
                                          (cfg.vocab_size, cfg.d_model))},
    }


def unembed_table(params: Params) -> jax.Array:
    return (params.get("unembed") or params["embed"])["table"]


def _superblock(cfg: ModelConfig, p: Params, x: jax.Array,
                positions: jax.Array, collect: bool):
    """Apply one period of sublayers.  Returns (x, aux, caches)."""
    pat = _pattern(cfg)
    aux_total = jnp.zeros((), jnp.float32)
    i_ssm = i_attn = i_dense = i_moe = 0
    kv = None
    states, tails = [], []
    at = lambda tree, i: jax.tree_util.tree_map(lambda a: a[i], tree)
    for j, (mixer, is_moe) in enumerate(pat):
        h = L.rms_norm({"scale": p["ln1"][j]}, x, cfg.norm_eps)
        if mixer == "ssm":
            pm = at(p["mamba"], i_ssm)
            i_ssm += 1
            if collect:
                y, st, tl = L.mamba2_block(pm, h, cfg, return_state=True)
                states.append(st)
                tails.append(tl)
            else:
                y = L.mamba2_block(pm, h, cfg)
        else:
            y, kv = L.attention(at(p["attn"], i_attn), h, positions, cfg)
            i_attn += 1
        x = x + y
        h = L.rms_norm({"scale": p["ln2"][j]}, x, cfg.norm_eps)
        if is_moe:
            m, aux = L.moe_layer(at(p["moe"], i_moe), h, cfg)
            i_moe += 1
            aux_total = aux_total + aux
        else:
            m = L.mlp(at(p["mlp"], i_dense), h, cfg.act)
            i_dense += 1
        x = x + m
    caches = None
    if collect:
        caches = {"kv": kv,
                  "state": jnp.stack(states),      # [n_ssm,B,H,P,N]
                  "conv": jax.tree_util.tree_map(
                      lambda *a: jnp.stack(a), *tails)}  # {x,B,C} [n_ssm,...]
    return x, aux_total, caches


def hidden(cfg: ModelConfig, params: Params, batch: Dict[str, Any],
           collect: bool = False):
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = L.embed(params["embed"], tokens, jnp.dtype(cfg.dtype))
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

    def block(x, p):
        x, aux, caches = _superblock(cfg, p, x, positions, collect)
        return x, (aux, caches)

    if cfg.remat == "full":
        block = jax.checkpoint(block)
    x, (aux, caches) = lax.scan(block, x, params["blocks"])
    x = L.rms_norm(params["final_norm"], x, cfg.norm_eps)
    return x, jnp.sum(aux), caches


def logits(cfg: ModelConfig, params: Params, batch: Dict[str, Any]):
    h, aux, _ = hidden(cfg, params, batch)
    return L.unembed(unembed_table(params), h,
                     jnp.dtype(cfg.logits_dtype)), aux


def loss(cfg: ModelConfig, params: Params, batch: Dict[str, Any]):
    h, aux, _ = hidden(cfg, params, batch)
    labels = batch.get("labels")
    if labels is None:
        labels = jnp.concatenate([batch["tokens"][:, 1:],
                                  batch["tokens"][:, -1:]], axis=1)
    nll = L.chunked_loss(unembed_table(params), h, labels,
                         cfg.loss_chunk, jnp.dtype(cfg.logits_dtype))
    return nll + 0.01 * aux, {"nll": nll, "aux": aux}


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> Dict[str, Any]:
    nb = cfg.num_layers // cfg.attn_every
    n_ssm, n_attn, _, _ = _counts(cfg)
    H, P, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    di, gn = cfg.ssm_d_inner, cfg.ssm_groups * cfg.ssm_state
    km1 = cfg.ssm_conv - 1
    return {
        "k": jnp.zeros((nb, batch, max_len, cfg.num_kv_heads, cfg.hd), dtype),
        "v": jnp.zeros((nb, batch, max_len, cfg.num_kv_heads, cfg.hd), dtype),
        "state": jnp.zeros((nb, n_ssm, batch, H, P, N), jnp.float32),
        "conv": {"x": jnp.zeros((nb, n_ssm, batch, km1, di), dtype),
                 "B": jnp.zeros((nb, n_ssm, batch, km1, gn), dtype),
                 "C": jnp.zeros((nb, n_ssm, batch, km1, gn), dtype)},
        "pos": jnp.zeros((), jnp.int32),
    }


def prefill(cfg: ModelConfig, params: Params, batch: Dict[str, Any],
            cache: Dict[str, Any]):
    h, _aux, caches = hidden(cfg, params, batch, collect=True)
    k, v = caches["kv"]
    S = batch["tokens"].shape[1]
    out_cache = dict(cache)
    out_cache["k"] = L.write_kv(cache["k"], k, 0)
    out_cache["v"] = L.write_kv(cache["v"], v, 0)
    out_cache["state"] = caches["state"].astype(cache["state"].dtype)
    out_cache["conv"] = jax.tree_util.tree_map(
        lambda t, c: t.astype(c.dtype), caches["conv"], cache["conv"])
    out_cache["pos"] = jnp.asarray(S, jnp.int32)
    out = L.unembed(unembed_table(params), h[:, -1:],
                    jnp.dtype(cfg.logits_dtype))
    return out, out_cache


def decode_step(cfg: ModelConfig, params: Params, tokens: jax.Array,
                cache: Dict[str, Any]):
    B = tokens.shape[0]
    pos = cache["pos"]
    x = L.embed(params["embed"], tokens, jnp.dtype(cfg.dtype))
    positions = L.decode_positions(pos, B, cfg.mrope_sections)
    pat = _pattern(cfg)
    at = lambda tree, i: jax.tree_util.tree_map(lambda a: a[i], tree)

    def block(carry, inp):
        x, kv = carry
        p, sb, st, cv = inp
        i_ssm = i_attn = i_dense = i_moe = 0
        st_new, cv_new = [], []
        for j, (mixer, is_moe) in enumerate(pat):
            h = L.rms_norm({"scale": p["ln1"][j]}, x, cfg.norm_eps)
            if mixer == "ssm":
                pm = at(p["mamba"], i_ssm)
                tail_i = jax.tree_util.tree_map(lambda a: a[i_ssm], cv)
                y, s_n, t_n = L.mamba2_decode_step(
                    pm, h, cfg, ssm_state=st[i_ssm], conv_tail=tail_i)
                st_new.append(s_n.astype(st.dtype))
                cv_new.append(jax.tree_util.tree_map(
                    lambda a, b: a.astype(b.dtype), t_n, tail_i))
                i_ssm += 1
            else:
                # one attention layer a period: the cache's layer sb
                y, kv = L.attention_decode(at(p["attn"], i_attn), h, kv, sb,
                                           pos, None, positions, cfg)
                i_attn += 1
            x = x + y
            h = L.rms_norm({"scale": p["ln2"][j]}, x, cfg.norm_eps)
            if is_moe:
                m, _ = L.moe_layer(at(p["moe"], i_moe), h, cfg)
                i_moe += 1
            else:
                m = L.mlp(at(p["mlp"], i_dense), h, cfg.act)
                i_dense += 1
            x = x + m
        return (x, kv), (jnp.stack(st_new),
                         jax.tree_util.tree_map(lambda *a: jnp.stack(a),
                                                *cv_new))

    nb = cfg.num_layers // cfg.attn_every
    (x, (k_new, v_new)), (st_new, cv_new) = lax.scan(
        block, (x, (cache["k"], cache["v"])),
        (params["blocks"], jnp.arange(nb), cache["state"], cache["conv"]))
    x = L.rms_norm(params["final_norm"], x, cfg.norm_eps)
    out = L.unembed(unembed_table(params), x, jnp.dtype(cfg.logits_dtype))
    return out, {"k": k_new, "v": v_new, "state": st_new, "conv": cv_new,
                 "pos": pos + 1}
