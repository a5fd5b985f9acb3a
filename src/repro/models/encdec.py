"""Whisper-style encoder-decoder backbone (audio family).

The conv/mel frontend is a STUB per the assignment brief: ``input_specs``
supplies precomputed frame embeddings ``frames [B, Se, d]``; the encoder
consumes them directly (adding sinusoidal positions).  The decoder is a
standard causal transformer with learned positions and cross-attention.
Whisper uses LayerNorm + GELU and no rotary embedding — driven by the
config (norm="layernorm", act="gelu", use_rope=False).
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from . import layers as L
from .config import ModelConfig

Params = Dict[str, Any]


def _norm(cfg: ModelConfig):
    if cfg.norm == "layernorm":
        return L.init_layernorm, L.layer_norm
    return L.init_rmsnorm, L.rms_norm


def sinusoids(length: int, channels: int) -> jax.Array:
    """Whisper's sinusoidal position embedding."""
    log_timescale = math.log(10000.0) / (channels // 2 - 1)
    inv = jnp.exp(-log_timescale * jnp.arange(channels // 2,
                                              dtype=jnp.float32))
    scaled = jnp.arange(length, dtype=jnp.float32)[:, None] * inv[None]
    return jnp.concatenate([jnp.sin(scaled), jnp.cos(scaled)], axis=1)


def init(cfg: ModelConfig, key) -> Params:
    init_n, _ = _norm(cfg)
    n_total = cfg.num_enc_layers + cfg.num_layers
    keys = jax.random.split(key, 2 * n_total + 4)
    d = cfg.d_model

    def enc_block(i):
        return {
            "ln1": init_n(d), "ln2": init_n(d),
            "attn": L.init_attention(keys[2 * i], d, cfg.num_heads,
                                     cfg.num_kv_heads, cfg.hd),
            "mlp": L.init_mlp(keys[2 * i + 1], d, cfg.d_ff, cfg.act),
        }

    def dec_block(i):
        j = cfg.num_enc_layers + i
        k1, k2 = keys[2 * j], keys[2 * j + 1]
        ks = jax.random.split(k1, 2)
        return {
            "ln1": init_n(d), "ln_x": init_n(d), "ln2": init_n(d),
            "attn": L.init_attention(ks[0], d, cfg.num_heads,
                                     cfg.num_kv_heads, cfg.hd),
            "cross": L.init_attention(ks[1], d, cfg.num_heads,
                                      cfg.num_kv_heads, cfg.hd),
            "mlp": L.init_mlp(k2, d, cfg.d_ff, cfg.act),
        }

    stack = lambda blocks: jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *blocks)
    return {
        "embed": L.init_embed(keys[-1], cfg.vocab_size, d),
        "pos_embed": L.embed_init(keys[-2], (cfg.max_seq, d)),
        "enc_blocks": stack([enc_block(i)
                             for i in range(cfg.num_enc_layers)]),
        "dec_blocks": stack([dec_block(i) for i in range(cfg.num_layers)]),
        "enc_norm": init_n(d),
        "final_norm": init_n(d),
    }


def unembed_table(params: Params) -> jax.Array:
    return params["embed"]["table"]      # whisper ties embeddings


# ---------------------------------------------------------------------------
# encoder
# ---------------------------------------------------------------------------

def encode(cfg: ModelConfig, params: Params, frames: jax.Array) -> jax.Array:
    """frames [B, Se, d] (precomputed frontend stub) → encoder states."""
    _, norm_f = _norm(cfg)
    B, Se, d = frames.shape
    x = frames.astype(jnp.dtype(cfg.dtype)) + \
        sinusoids(Se, d).astype(jnp.dtype(cfg.dtype))[None]

    def block(x, p):
        h = norm_f(p["ln1"], x, cfg.norm_eps)
        x = x + L.attention(p["attn"], h, None, cfg, causal=False)[0]
        h = norm_f(p["ln2"], x, cfg.norm_eps)
        return x + L.mlp(p["mlp"], h, cfg.act), None

    if cfg.remat == "full":
        block = jax.checkpoint(block)
    x, _ = lax.scan(block, x, params["enc_blocks"])
    return norm_f(params["enc_norm"], x, cfg.norm_eps)


# ---------------------------------------------------------------------------
# decoder
# ---------------------------------------------------------------------------

def _cross_kv(cfg: ModelConfig, p_cross: Params, enc: jax.Array):
    B, Se, _ = enc.shape
    k = (enc @ p_cross["wk"].astype(enc.dtype)).reshape(
        B, Se, cfg.num_kv_heads, cfg.hd)
    v = (enc @ p_cross["wv"].astype(enc.dtype)).reshape(
        B, Se, cfg.num_kv_heads, cfg.hd)
    return k, v


def _decoder(cfg: ModelConfig, params: Params, tokens: jax.Array,
             enc: jax.Array, collect_kv: bool = False):
    """Teacher-forced decoder pass.  Returns (h, kv|None)."""
    _, norm_f = _norm(cfg)
    B, S = tokens.shape
    x = L.embed(params["embed"], tokens, jnp.dtype(cfg.dtype))
    x = x + params["pos_embed"][:S].astype(x.dtype)[None]
    Se = enc.shape[1]
    ckx = L.pick_chunk(Se, cfg.attn_chunk_k)

    def block(x, p):
        h = norm_f(p["ln1"], x, cfg.norm_eps)
        o, (k, v) = L.attention(p["attn"], h, None, cfg)
        x = x + o
        # cross-attention
        h = norm_f(p["ln_x"], x, cfg.norm_eps)
        qx = (h @ p["cross"]["wq"].astype(x.dtype)).reshape(
            B, S, cfg.num_heads, cfg.hd)
        kx, vx = _cross_kv(cfg, p["cross"], enc)
        ox = L.flash_attention_xla(qx, kx, vx, causal=False,
                                   chunk_q=cfg.attn_chunk_q, chunk_k=ckx)
        x = x + ox.reshape(B, S, -1) @ p["cross"]["wo"].astype(x.dtype)
        h = norm_f(p["ln2"], x, cfg.norm_eps)
        x = x + L.mlp(p["mlp"], h, cfg.act)
        return x, ((k, v, kx, vx) if collect_kv else None)

    if cfg.remat == "full":
        block = jax.checkpoint(block)
    x, kv = lax.scan(block, x, params["dec_blocks"])
    x = norm_f(params["final_norm"], x, cfg.norm_eps)
    return x, kv


def hidden(cfg: ModelConfig, params: Params, batch: Dict[str, Any],
           collect_kv: bool = False):
    enc = encode(cfg, params, batch["frames"])
    h, kv = _decoder(cfg, params, batch["tokens"], enc, collect_kv)
    return h, jnp.zeros((), jnp.float32), kv


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
    return nll, {"nll": nll, "aux": aux}


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> Dict[str, Any]:
    K, hd, Ln = cfg.num_kv_heads, cfg.hd, cfg.num_layers
    Se = cfg.enc_seq
    return {
        "k": jnp.zeros((Ln, batch, max_len, K, hd), dtype),
        "v": jnp.zeros((Ln, batch, max_len, K, hd), dtype),
        "xk": jnp.zeros((Ln, batch, Se, K, hd), dtype),
        "xv": jnp.zeros((Ln, batch, Se, K, hd), dtype),
        "pos": jnp.zeros((), jnp.int32),
    }


def prefill(cfg: ModelConfig, params: Params, batch: Dict[str, Any],
            cache: Dict[str, Any]):
    h, _aux, kv = hidden(cfg, params, batch, collect_kv=True)
    k, v, xk, xv = kv
    S = batch["tokens"].shape[1]
    cache = dict(cache)
    cache["k"] = L.write_kv(cache["k"], k, 0)
    cache["v"] = L.write_kv(cache["v"], v, 0)
    cache["xk"] = xk.astype(cache["xk"].dtype)
    cache["xv"] = xv.astype(cache["xv"].dtype)
    cache["pos"] = jnp.asarray(S, jnp.int32)
    out = L.unembed(unembed_table(params), h[:, -1:],
                    jnp.dtype(cfg.logits_dtype))
    return out, cache


def decode_step(cfg: ModelConfig, params: Params, tokens: jax.Array,
                cache: Dict[str, Any]):
    _, norm_f = _norm(cfg)
    B = tokens.shape[0]
    pos = cache["pos"]
    x = L.embed(params["embed"], tokens, jnp.dtype(cfg.dtype))
    x = x + lax.dynamic_slice_in_dim(params["pos_embed"], pos, 1,
                                     axis=0).astype(x.dtype)[None, 0]

    def block(carry, inp):
        x, kv = carry
        p, i, xk, xv = inp
        h = norm_f(p["ln1"], x, cfg.norm_eps)
        o, kv = L.attention_decode(p["attn"], h, kv, i, pos, None, None, cfg)
        x = x + o
        h = norm_f(p["ln_x"], x, cfg.norm_eps)
        qx = (h @ p["cross"]["wq"].astype(x.dtype)).reshape(
            B, 1, cfg.num_heads, cfg.hd)
        ox = L.naive_attention(qx, xk, xv, causal=False)
        x = x + ox.reshape(B, 1, -1) @ p["cross"]["wo"].astype(x.dtype)
        h = norm_f(p["ln2"], x, cfg.norm_eps)
        x = x + L.mlp(p["mlp"], h, cfg.act)
        return (x, kv), None

    (x, (k_new, v_new)), _ = lax.scan(
        block, (x, (cache["k"], cache["v"])),
        (params["dec_blocks"], jnp.arange(cfg.num_layers), cache["xk"],
         cache["xv"]))
    x = norm_f(params["final_norm"], x, cfg.norm_eps)
    out = L.unembed(unembed_table(params), x, jnp.dtype(cfg.logits_dtype))
    cache = dict(cache, k=k_new, v=v_new, pos=pos + 1)
    return out, cache
