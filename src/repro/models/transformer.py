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
# the block; forward (train / prefill)
# ---------------------------------------------------------------------------

def _block(cfg: ModelConfig, p: Params, x: jax.Array, attend):
    """One transformer block: norm, attention, residual, norm, MLP or MoE,
    residual.  ``attend(p_attn, h)`` is the attention sublayer, returning
    (out, kv).  Returns (x, aux, kv)."""
    o, kv = attend(p["attn"], L.rms_norm(p["ln1"], x, cfg.norm_eps))
    x = x + o
    h = L.rms_norm(p["ln2"], x, cfg.norm_eps)
    if "moe" in p:
        m, aux = L.moe_layer(p["moe"], h, cfg)
    else:
        m, aux = L.mlp(p["mlp"], h, cfg.act), jnp.zeros((), jnp.float32)
    return x + m, aux, kv


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
        x, aux, kv = _block(cfg, p, x, lambda pa, h: L.attention(
            pa, h, positions, cfg))
        return x, (aux, kv if collect_kv else None)

    x, (aux, kv) = lax.scan(_maybe_remat(block, cfg), x, params["blocks"])
    aux = jnp.sum(aux)
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
    h, _aux, (k, v) = hidden(cfg, params, batch, collect_kv=True)
    cache = dict(cache)
    cache["k"] = L.write_kv(cache["k"], k, 0)
    cache["v"] = L.write_kv(cache["v"], v, 0)
    cache["pos"] = jnp.asarray(batch["tokens"].shape[1], jnp.int32)
    if logit_pos is None:
        h_last = h[:, -1:]
    else:
        h_last = lax.dynamic_slice_in_dim(h, logit_pos, 1, axis=1)
    out = L.unembed(unembed_table(params), h_last,
                    jnp.dtype(cfg.logits_dtype))
    return out, cache


def write_slot(pool_cache: Dict[str, Any], row_cache: Dict[str, Any],
               slot):
    """Write a one-row cache (a prefill's, its ``pos`` ``[1]``) into slot
    ``slot`` of a pool cache whose ``pos`` is ``[B]``.  ``slot`` is an int
    or a traced scalar: one compiled program serves every slot.  A one-slot
    pool's keys and values are replaced whole."""
    def put(pool, row):
        if pool.shape == row.shape:
            return row
        return lax.dynamic_update_slice_in_dim(
            pool, row.astype(pool.dtype), slot, axis=1)
    k = put(pool_cache["k"], row_cache["k"])
    pos = pool_cache["pos"].at[slot].set(row_cache["pos"][0])
    return {"k": k, "pos": pos, "v": put(pool_cache["v"], row_cache["v"])}


def decode_step(cfg: ModelConfig, params: Params, tokens: jax.Array,
                cache: Dict[str, Any]):
    """One decode step.  tokens [B,1] → (logits [B,1,V], updated cache).

    ``cache['pos']`` is a scalar, every row at one position, or ``[B]``,
    each row at its own: the serve engine's slots hold requests admitted
    at different times.  The stacked caches ride the layer scan's carry,
    and each layer writes its new keys and values into them in place (as
    the scan's sliced input and stacked output instead, every layer's slab
    would be written back whole each step).  The serve engine donates the
    cache, so nothing copies it.
    """
    B = tokens.shape[0]
    pos = cache["pos"]
    rows = L.kv_rows(pos, B)
    x = L.embed(params["embed"], tokens, jnp.dtype(cfg.dtype))
    positions = L.decode_positions(pos, B, cfg.mrope_sections)

    def block(carry, inp):
        x, kv = carry
        p, i = inp
        x, _, kv = _block(cfg, p, x, lambda pa, h: L.attention_decode(
            pa, h, kv, i, pos, rows, positions, cfg))
        return (x, kv), None

    (x, (k, v)), _ = lax.scan(block, (x, (cache["k"], cache["v"])),
                              (params["blocks"], jnp.arange(cfg.num_layers)))
    x = L.rms_norm(params["final_norm"], x, cfg.norm_eps)
    out = L.unembed(unembed_table(params), x, jnp.dtype(cfg.logits_dtype))
    return out, {"k": k, "v": v, "pos": pos + 1}
