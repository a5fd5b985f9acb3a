"""Decoder-only transformer LM — dense, MoE, and VLM-stub variants.

Covers llama3.2-1b, qwen3-1.7b, internlm2-1.8b, stablelm-12b (dense),
moonshot-v1-16b-a3b, deepseek-moe-16b (MoE), qwen2-vl-2b (VLM backbone with
M-RoPE and stubbed vision embeddings).

Layers are stacked along a leading axis and driven by ``lax.scan`` so the
HLO is one while-loop regardless of depth — this is what keeps the
512-device dry-run compile tractable and the remat policy uniform.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from . import layers as L
from .config import ModelConfig

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_block(key, cfg: ModelConfig, moe: bool) -> Params:
    k1, k2 = jax.random.split(key)
    p: Params = {
        "ln1": L.init_rmsnorm(cfg.d_model),
        "ln2": L.init_rmsnorm(cfg.d_model),
        "attn": L.init_attention(k1, cfg.d_model, cfg.num_heads,
                                 cfg.num_kv_heads, cfg.hd, cfg.qk_norm),
    }
    if moe:
        p["moe"] = L.init_moe(k2, cfg.d_model, cfg.moe_num_experts,
                              cfg.moe_d_ff or cfg.d_ff,
                              cfg.moe_num_shared, cfg.act)
    else:
        p["mlp"] = L.init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.act)
    return p


def init(cfg: ModelConfig, key) -> Params:
    keys = jax.random.split(key, cfg.num_layers + 2)
    moe = cfg.moe_num_experts > 0
    blocks = [_init_block(keys[i], cfg, moe and cfg.is_moe_layer(i))
              for i in range(cfg.num_layers)]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *blocks)
    p: Params = {
        "embed": L.init_embed(keys[-1], cfg.vocab_size, cfg.d_model),
        "blocks": stacked,
        "final_norm": L.init_rmsnorm(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        p["unembed"] = {"table": L.embed_init(keys[-2],
                                              (cfg.vocab_size, cfg.d_model))}
    return p


def unembed_table(params: Params) -> jax.Array:
    return (params.get("unembed") or params["embed"])["table"]


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------

def _block_apply(cfg: ModelConfig, p: Params, x: jax.Array,
                 positions: jax.Array, collect_kv: bool):
    """One transformer block.  Returns (x, aux, (k, v) | None)."""
    h = L.rms_norm(p["ln1"], x, cfg.norm_eps)
    with jax.named_scope("attention"):
        q, k, v = L._qkv(p["attn"], h, cfg.num_heads, cfg.num_kv_heads,
                         cfg.hd, cfg.qk_norm, cfg.norm_eps)
        q = L.apply_rope(q, positions, cfg.rope_theta, cfg.mrope_sections,
                         cfg.use_rope)
        k = L.apply_rope(k, positions, cfg.rope_theta, cfg.mrope_sections,
                         cfg.use_rope)
        if cfg.attn_impl == "naive":
            o = L.naive_attention(q, k, v, causal=True)
        else:
            o = L.flash_attention_xla(q, k, v, causal=True,
                                      chunk_q=cfg.attn_chunk_q,
                                      chunk_k=cfg.attn_chunk_k,
                                      causal_skip=cfg.causal_skip)
        B, S = x.shape[:2]
        o = o.reshape(B, S, cfg.num_heads * cfg.hd) @ \
            p["attn"]["wo"].astype(x.dtype)
    x = x + o

    h = L.rms_norm(p["ln2"], x, cfg.norm_eps)
    if "moe" in p:
        m, aux = L.moe_layer(p["moe"], h, cfg)
    else:
        m, aux = L.mlp(p["mlp"], h, cfg.act), jnp.zeros((), jnp.float32)
    x = x + m
    return x, aux, ((k, v) if collect_kv else None)


def _maybe_remat(fn, cfg: ModelConfig):
    if cfg.remat == "full":
        return jax.checkpoint(fn)
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    return fn


def _embed_inputs(cfg: ModelConfig, params: Params, batch: Dict[str, Any]
                  ) -> Tuple[jax.Array, jax.Array]:
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = L.embed(params["embed"], tokens, jnp.dtype(cfg.dtype))
    if cfg.frontend == "vision_patches" and "vision_embeds" in batch:
        # stubbed multimodal merge: precomputed patch embeddings replace
        # the token embeddings at masked positions (qwen2-vl style)
        ve = batch["vision_embeds"].astype(x.dtype)
        x = jnp.where(batch["vision_mask"][..., None], ve, x)
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        if cfg.mrope_sections:
            positions = jnp.broadcast_to(positions[None], (3, B, S))
    return x, positions


def hidden(cfg: ModelConfig, params: Params, batch: Dict[str, Any],
           collect_kv: bool = False):
    """Run the block stack.  Returns (h, aux, kv|None).

    kv (prefill): (k, v) stacked [L, B, S, K, hd].
    """
    x, positions = _embed_inputs(cfg, params, batch)

    def block(x, p):
        x, aux, kv = _block_apply(cfg, p, x, positions, collect_kv)
        return x, (aux, kv)

    block = _maybe_remat(block, cfg)
    if cfg.scan_layers:
        x, (aux, kv) = lax.scan(block, x, params["blocks"])
        aux = jnp.sum(aux)
    else:
        auxs, ks, vs = [], [], []
        for i in range(cfg.num_layers):
            p_i = jax.tree_util.tree_map(lambda a: a[i], params["blocks"])
            x, (a, kv_i) = block(x, p_i)
            auxs.append(a)
            if collect_kv:
                ks.append(kv_i[0])
                vs.append(kv_i[1])
        aux = jnp.sum(jnp.stack(auxs))
        kv = (jnp.stack(ks), jnp.stack(vs)) if collect_kv else None
    x = L.rms_norm(params["final_norm"], x, cfg.norm_eps)
    return x, aux, kv


def logits(cfg: ModelConfig, params: Params, batch: Dict[str, Any]):
    h, aux, _ = hidden(cfg, params, batch)
    out = L.unembed(unembed_table(params), h, jnp.dtype(cfg.logits_dtype))
    return out, aux


def loss(cfg: ModelConfig, params: Params, batch: Dict[str, Any]):
    """Next-token cross-entropy (+ MoE aux), seq-chunked when configured."""
    h, aux, _ = hidden(cfg, params, batch)
    labels = batch.get("labels")
    if labels is None:
        labels = jnp.concatenate([batch["tokens"][:, 1:],
                                  batch["tokens"][:, -1:]], axis=1)
    nll = L.chunked_loss(unembed_table(params), h, labels,
                         cfg.loss_chunk, jnp.dtype(cfg.logits_dtype))
    return nll + 0.01 * aux, {"nll": nll, "aux": aux}


# ---------------------------------------------------------------------------
# serving: cache / prefill / decode
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> Dict[str, Any]:
    K, hd, Ln = cfg.num_kv_heads, cfg.hd, cfg.num_layers
    return {
        "k": jnp.zeros((Ln, batch, max_len, K, hd), dtype),
        "v": jnp.zeros((Ln, batch, max_len, K, hd), dtype),
        "pos": jnp.zeros((), jnp.int32),
    }


def prefill(cfg: ModelConfig, params: Params, batch: Dict[str, Any],
            cache: Dict[str, Any], logit_pos=None):
    """Process the prompt; fill the cache; return last-position logits.

    ``logit_pos``: position whose logits to return (traced scalar ok) —
    the serve engine passes len(prompt)-1 for right-padded prompts.
    """
    h, _aux, kv = hidden(cfg, params, batch, collect_kv=True)
    k, v = kv                                       # [L,B,S,K,hd]
    S = k.shape[2]
    cache = dict(cache)
    cache["k"] = lax.dynamic_update_slice_in_dim(
        cache["k"], k.astype(cache["k"].dtype), 0, axis=2)
    cache["v"] = lax.dynamic_update_slice_in_dim(
        cache["v"], v.astype(cache["v"].dtype), 0, axis=2)
    cache["pos"] = jnp.asarray(S, jnp.int32)
    if logit_pos is None:
        h_last = h[:, -1:]
    else:
        h_last = lax.dynamic_slice_in_dim(h, logit_pos, 1, axis=1)
    out = L.unembed(unembed_table(params), h_last,
                    jnp.dtype(cfg.logits_dtype))
    return out, cache


def decode_step(cfg: ModelConfig, params: Params, tokens: jax.Array,
                cache: Dict[str, Any]):
    """One decode step.  tokens [B,1] → (logits [B,1,V], updated cache)."""
    B = tokens.shape[0]
    pos = cache["pos"]
    x = L.embed(params["embed"], tokens, jnp.dtype(cfg.dtype))
    positions = jnp.broadcast_to(pos[None, None], (B, 1))
    if cfg.mrope_sections:
        positions = jnp.broadcast_to(positions[None], (3, B, 1))

    def block(x, inp):
        p, k_c, v_c = inp
        h = L.rms_norm(p["ln1"], x, cfg.norm_eps)
        with jax.named_scope("attention"):
            q, k, v = L._qkv(p["attn"], h, cfg.num_heads, cfg.num_kv_heads,
                             cfg.hd, cfg.qk_norm, cfg.norm_eps)
            q = L.apply_rope(q, positions, cfg.rope_theta,
                             cfg.mrope_sections, cfg.use_rope)
            k = L.apply_rope(k, positions, cfg.rope_theta,
                             cfg.mrope_sections, cfg.use_rope)
            k_c = lax.dynamic_update_slice_in_dim(
                k_c, k.astype(k_c.dtype), pos, axis=1)
            v_c = lax.dynamic_update_slice_in_dim(
                v_c, v.astype(v_c.dtype), pos, axis=1)
            o = L.decode_attention(q, k_c, v_c, pos + 1)
            o = o.reshape(B, 1, cfg.num_heads * cfg.hd) @ \
                p["attn"]["wo"].astype(x.dtype)
        x = x + o
        h = L.rms_norm(p["ln2"], x, cfg.norm_eps)
        if "moe" in p:
            m, _ = L.moe_layer(p["moe"], h, cfg)
        else:
            m = L.mlp(p["mlp"], h, cfg.act)
        return x + m, (k_c, v_c)

    x, (k_new, v_new) = lax.scan(
        block, x, (params["blocks"], cache["k"], cache["v"]))
    x = L.rms_norm(params["final_norm"], x, cfg.norm_eps)
    out = L.unembed(unembed_table(params), x, jnp.dtype(cfg.logits_dtype))
    cache = {"k": k_new, "v": v_new, "pos": pos + 1}
    return out, cache


def decode_step_ragged(cfg: ModelConfig, params: Params, tokens: jax.Array,
                       cache: Dict[str, Any]):
    """Decode with PER-ROW positions — the continuous-batching path.

    ``cache['pos']`` is [B]: each slot writes its k/v at its own offset
    (scatter) and masks to its own prefix.  Used by the serve engine where
    slots hold requests admitted at different times; the uniform-batch
    ``decode_step`` remains the production multi-pod path (per-row scatter
    onto a sequence-sharded cache would defeat the cache sharding).

    The stacked caches ride the layer scan's carry and each layer scatters
    its new keys and values into them in place (as the scan's sliced input
    and stacked output instead, every layer's slab would be written back
    whole each step).  The serve engine donates the cache, so nothing
    copies it.
    """
    B = tokens.shape[0]
    pos = cache["pos"]                                   # [B]
    bidx = jnp.arange(B)
    x = L.embed(params["embed"], tokens, jnp.dtype(cfg.dtype))
    positions = pos[:, None]
    if cfg.mrope_sections:
        positions = jnp.broadcast_to(positions[None], (3, B, 1))

    def block(carry, inp):
        x, k_all, v_all = carry
        p, i = inp
        h = L.rms_norm(p["ln1"], x, cfg.norm_eps)
        with jax.named_scope("attention"):
            q, k, v = L._qkv(p["attn"], h, cfg.num_heads, cfg.num_kv_heads,
                             cfg.hd, cfg.qk_norm, cfg.norm_eps)
            q = L.apply_rope(q, positions, cfg.rope_theta,
                             cfg.mrope_sections, cfg.use_rope)
            k = L.apply_rope(k, positions, cfg.rope_theta,
                             cfg.mrope_sections, cfg.use_rope)
            k_all = k_all.at[i, bidx, pos].set(k[:, 0].astype(k_all.dtype))
            v_all = v_all.at[i, bidx, pos].set(v[:, 0].astype(v_all.dtype))
            o = L.decode_attention(q, k_all[i], v_all[i], pos + 1)
            o = o.reshape(B, 1, cfg.num_heads * cfg.hd) @ \
                p["attn"]["wo"].astype(x.dtype)
        x = x + o
        h = L.rms_norm(p["ln2"], x, cfg.norm_eps)
        if "moe" in p:
            m, _ = L.moe_layer(p["moe"], h, cfg)
        else:
            m = L.mlp(p["mlp"], h, cfg.act)
        return (x + m, k_all, v_all), None

    (x, k_new, v_new), _ = lax.scan(
        block, (x, cache["k"], cache["v"]),
        (params["blocks"], jnp.arange(cache["k"].shape[0])))
    x = L.rms_norm(params["final_norm"], x, cfg.norm_eps)
    out = L.unembed(unembed_table(params), x, jnp.dtype(cfg.logits_dtype))
    return out, {"k": k_new, "v": v_new, "pos": pos + 1}
