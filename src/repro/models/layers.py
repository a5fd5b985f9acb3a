"""Neural building blocks — pure-JAX, functional, scan-friendly.

Everything operates on parameter *dicts* (pytrees) produced by the matching
``init_*`` functions so layers can be stacked along a leading axis and driven
by ``jax.lax.scan`` (compact HLO — essential for the 512-device dry-run).

Conventions:
  * activations ``[B, S, ...]``; weights stored fp32 at init, cast to the
    compute dtype by callers (mixed-precision policy lives in repro.train);
  * attention heads layout ``[B, S, H, D]``;
  * GQA with ``K`` kv heads: ``H % K == 0``; K may be smaller than the TP
    axis, in which case kv projections are replicated (see
    repro.distributed.partition).
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.distributed.logical import active_rules, constrain

Params = Dict[str, Any]

# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def dense_init(key, shape, scale: Optional[float] = None) -> jax.Array:
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return jax.random.normal(key, shape, jnp.float32) * scale


def embed_init(key, shape) -> jax.Array:
    return jax.random.normal(key, shape, jnp.float32) * 0.02


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def init_rmsnorm(d: int) -> Params:
    return {"scale": jnp.ones((d,), jnp.float32)}


def rms_norm(p: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)
    return out.astype(dt)


def pick_chunk(S: int, target: int = 512) -> int:
    """Largest divisor of S that is ≤ target (flash chunking for odd S)."""
    best = 1
    for c in range(1, min(S, target) + 1):
        if S % c == 0:
            best = c
    return best


def init_layernorm(d: int) -> Params:
    return {"scale": jnp.ones((d,), jnp.float32),
            "bias": jnp.zeros((d,), jnp.float32)}


def layer_norm(p: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    out = out * p["scale"] + p["bias"]
    return out.astype(dt)


# ---------------------------------------------------------------------------
# rotary position embedding (standard + M-RoPE)
# ---------------------------------------------------------------------------

def rope_freqs(hd: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               mrope_sections: Tuple[int, ...] = (),
               enabled: bool = True) -> jax.Array:
    if not enabled:
        return x
    return _apply_rope(x, positions, theta, mrope_sections)


def _apply_rope(x: jax.Array, positions: jax.Array, theta: float,
                mrope_sections: Tuple[int, ...] = ()) -> jax.Array:
    """Rotate ``x [B,S,H,D]`` by position.

    ``positions``: ``[B,S]`` for standard RoPE, or ``[3,B,S]`` for M-RoPE
    (qwen2-vl): the D/2 frequency channels are split into
    ``mrope_sections`` groups (t, h, w), each rotated by its own position
    stream.  Text tokens carry identical t/h/w positions, which makes
    M-RoPE collapse to standard RoPE — a property tested in
    tests/test_models.py.
    """
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # [D/2]
    if mrope_sections:
        assert positions.ndim == 3, "M-RoPE needs [3,B,S] positions"
        assert sum(mrope_sections) == hd // 2, (mrope_sections, hd)
        # select, per frequency channel, which position stream drives it
        sec_id = jnp.repeat(
            jnp.arange(len(mrope_sections)),
            jnp.asarray(mrope_sections), total_repeat_length=hd // 2)
        pos = positions.astype(jnp.float32)             # [3,B,S]
        # angle[b,s,c] = pos[sec_id[c],b,s] * freqs[c]
        pos_per_chan = jnp.take(pos, sec_id, axis=0)    # [C,B,S]
        angle = jnp.einsum("cbs,c->bsc", pos_per_chan, freqs)
    else:
        pos = positions.astype(jnp.float32)             # [B,S]
        angle = pos[..., None] * freqs                  # [B,S,D/2]
    cos = jnp.cos(angle)[:, :, None, :]                 # [B,S,1,D/2]
    sin = jnp.sin(angle)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin,
                           x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def init_attention(key, d: int, H: int, K: int, hd: int,
                   qk_norm: bool = False) -> Params:
    ks = jax.random.split(key, 4)
    p: Params = {
        "wq": dense_init(ks[0], (d, H * hd)),
        "wk": dense_init(ks[1], (d, K * hd)),
        "wv": dense_init(ks[2], (d, K * hd)),
        "wo": dense_init(ks[3], (H * hd, d)),
    }
    if qk_norm:
        p["q_norm"] = init_rmsnorm(hd)
        p["k_norm"] = init_rmsnorm(hd)
    return p


def _qkv(p: Params, x: jax.Array, H: int, K: int, hd: int,
         qk_norm: bool, eps: float) -> Tuple[jax.Array, jax.Array, jax.Array]:
    B, S, _ = x.shape
    q = constrain((x @ p["wq"].astype(x.dtype)).reshape(B, S, H, hd),
                  "batch", None, "heads", None)
    k = constrain((x @ p["wk"].astype(x.dtype)).reshape(B, S, K, hd),
                  "batch", None, "kv_heads", None)
    v = constrain((x @ p["wv"].astype(x.dtype)).reshape(B, S, K, hd),
                  "batch", None, "kv_heads", None)
    if qk_norm:
        q = rms_norm(p["q_norm"], q, eps)
        k = rms_norm(p["k_norm"], k, eps)
    return q, k, v


def repeat_kv(k: jax.Array, H: int) -> jax.Array:
    """GQA: repeat kv heads to H ([B,S,K,D] → [B,S,H,D]).

    The Megatron treatment when TP > kv_heads: kv projections are
    replicated and each device takes the repeats its q-heads need — keeps
    every attention einsum sharded cleanly on one head dim.  The flash
    (train, prefill) paths always repeat; ``decode_attention`` repeats only
    when the bound rules shard ``heads`` but not ``kv_heads``, and
    otherwise reads the cache once per kv head.
    """
    K = k.shape[2]
    if K == H:
        return k
    return jnp.repeat(k, H // K, axis=2)


def naive_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True,
                    q_offset: int | jax.Array = 0,
                    kv_len: Optional[jax.Array] = None) -> jax.Array:
    """Reference attention, GQA-aware.  q [B,Sq,H,D], k/v [B,Sk,K,D].

    ``q_offset``: absolute position of q[0] (for decode: cache length).
    ``kv_len``: valid prefix length of k/v (rest is padding to ignore).
    """
    B, Sq, H, D = q.shape
    kr = repeat_kv(k, H).astype(jnp.float32)
    vr = repeat_kv(v, H).astype(jnp.float32)
    s = jnp.einsum("bqhd,bshd->bhqs", q.astype(jnp.float32),
                   kr) / math.sqrt(D)
    s = constrain(s, "batch", "heads", None, None)
    q_pos = jnp.arange(Sq)[:, None] + q_offset
    k_pos = jnp.arange(k.shape[1])[None, :]
    mask = jnp.ones((Sq, k.shape[1]), bool)
    if causal:
        mask &= k_pos <= q_pos
    if kv_len is not None:
        mask &= k_pos < kv_len
    s = jnp.where(mask[None, None], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    w = jnp.where(jnp.isnan(w), 0.0, w)   # fully-masked rows
    o = jnp.einsum("bhqs,bshd->bqhd", w, vr)
    return o.astype(q.dtype)


def _chunk_pairs(Sq: int, Sk: int, cq: int, ck: int, causal: bool,
                 causal_skip: bool):
    """Static (python-int) chunk-pair schedule."""
    nq, nk = Sq // cq, Sk // ck
    if causal and causal_skip:
        # schedule only lower-triangular chunk pairs: ~2x fewer FLOPs than
        # masking a full quadratic sweep (beyond-paper lever, §Perf)
        off = (Sk - Sq) // ck
        return [(i, j) for i in range(nq) for j in range(0, i + off + 1)]
    return [(i, j) for i in range(nq) for j in range(nk)]


def _split_pairs(Sq, Sk, cq, ck, causal, causal_skip):
    """(off-diagonal pairs, diagonal pairs) for the two-scan schedule."""
    pairs = _chunk_pairs(Sq, Sk, cq, ck, causal, causal_skip)
    diag, offd = [], []
    for i, j in pairs:
        # masking needed iff the k-chunk straddles the diagonal: some k
        # position exceeds the chunk's smallest absolute q position
        last_k = j * ck + ck - 1
        first_q_abs = i * cq + (Sk - Sq)
        if causal and last_k > first_q_abs:
            diag.append((i, j))
        else:
            offd.append((i, j))
    return offd, diag


def _flash_fwd_scan(q, kr, vr, causal, cq, ck, causal_skip):
    """Online-softmax over chunk pairs.  q [B,Sq,H,D]; kr/vr [B,Sk,H,D].

    Flash-v2-style schedule (beyond-paper lever):
      * causal pairs split into OFF-DIAGONAL (no mask, no -inf selects —
        ~(nq-1)/nq of all pairs) and DIAGONAL scans (masked);
      * dots consume the INPUT dtype with fp32 accumulation
        (``preferred_element_type``) — bf16 activations hit the MXU
        natively with no fp32 operand copies; fp32 inputs stay exact.

    Returns (out fp32 [B,Sq,H,D], lse [B,H,Sq]).
    """
    B, Sq, H, D = q.shape
    Sk = kr.shape[1]
    # fold the softmax scale into q ONCE ([B,Sq,H,D], tiny) instead of a
    # full pass over every [cq,ck] scores tile (−1 scores pass; §Perf A2)
    qs = q * jnp.asarray(1.0 / math.sqrt(D), q.dtype)

    m0 = jnp.full((B, H, Sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    # acc kept in dot-native [B,H,Sq,D] layout: no per-pair transposes
    a0 = jnp.zeros((B, H, Sq, D), jnp.float32)

    def body(carry, ij, masked):
        m, l, acc = carry
        i, j = ij
        qc = lax.dynamic_slice_in_dim(qs, i * cq, cq, axis=1)
        kc = lax.dynamic_slice_in_dim(kr, j * ck, ck, axis=1)
        vc = lax.dynamic_slice_in_dim(vr, j * ck, ck, axis=1)
        s = jnp.einsum("bqhd,bshd->bhqs", qc, kc,
                       preferred_element_type=jnp.float32)
        s = constrain(s, "batch", "heads", None, None)
        if masked:
            q_pos = i * cq + jnp.arange(cq)[:, None] + (Sk - Sq)
            k_pos = j * ck + jnp.arange(ck)[None, :]
            s = jnp.where((k_pos <= q_pos)[None, None], s, -jnp.inf)
        mc = lax.dynamic_slice_in_dim(m, i * cq, cq, axis=2)
        lc = lax.dynamic_slice_in_dim(l, i * cq, cq, axis=2)
        ac = lax.dynamic_slice_in_dim(acc, i * cq, cq, axis=2)
        m_new = jnp.maximum(mc, jnp.max(s, axis=-1))
        if masked:
            m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
            p = jnp.exp(s - m_safe[..., None]).astype(vc.dtype)
            p = jnp.where(jnp.isneginf(s), 0.0, p)
            corr = jnp.exp(mc - m_new)
            corr = jnp.where(jnp.isneginf(mc), 0.0, corr)
        else:
            # p emitted directly in v's dtype (bf16 in production): the
            # PV dot reads half the bytes and hits the MXU natively
            p = jnp.exp(s - m_new[..., None]).astype(vc.dtype)
            corr = jnp.exp(mc - m_new)
            corr = jnp.where(jnp.isneginf(mc), 0.0, corr)
        l_new = lc * corr + jnp.sum(p.astype(jnp.float32), axis=-1)
        pv = jnp.einsum("bhqs,bshd->bhqd", p, vc,
                        preferred_element_type=jnp.float32)
        ac = ac * corr[..., None] + pv
        m = lax.dynamic_update_slice_in_dim(m, m_new, i * cq, axis=2)
        l = lax.dynamic_update_slice_in_dim(l, l_new, i * cq, axis=2)
        acc = lax.dynamic_update_slice_in_dim(acc, ac, i * cq, axis=2)
        return (m, l, acc), None

    offd, diag = _split_pairs(Sq, Sk, cq, ck, causal, causal_skip)
    carry = (m0, l0, a0)
    if offd:
        xs = (jnp.asarray([p[0] for p in offd], jnp.int32),
              jnp.asarray([p[1] for p in offd], jnp.int32))
        carry, _ = lax.scan(functools.partial(body, masked=False),
                            carry, xs)
    if diag:
        xs = (jnp.asarray([p[0] for p in diag], jnp.int32),
              jnp.asarray([p[1] for p in diag], jnp.int32))
        carry, _ = lax.scan(functools.partial(body, masked=causal),
                            carry, xs)
    m, l, acc = carry
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = acc / l_safe[..., None]                       # [B,H,Sq,D]
    out = jnp.transpose(out, (0, 2, 1, 3))              # → [B,Sq,H,D] once
    lse = jnp.where(l > 0.0, m + jnp.log(l_safe), jnp.inf)
    return out, lse


def _flash_fwd(q, k, v, causal, cq, ck, causal_skip):
    H = q.shape[2]
    kr, vr = repeat_kv(k, H), repeat_kv(v, H)
    out, lse = _flash_fwd_scan(q, kr, vr, causal, cq, ck, causal_skip)
    return out.astype(q.dtype), lse


def _flash_bwd_scan(q, k, v, out, lse, dout, causal, cq, ck, causal_skip):
    """Recompute-based flash backward (no saved per-pair history)."""
    B, Sq, H, D = q.shape
    kr, vr = repeat_kv(k, H), repeat_kv(v, H)
    Sk = kr.shape[1]
    K = k.shape[2]
    scale = 1.0 / math.sqrt(D)
    # scale folded into small [.,S,H,D] tensors once, never over scores:
    #   s  = (q·scale)·k ;  ds = p·(do'·v − δ') with do' = do·scale
    qs = q * jnp.asarray(scale, q.dtype)
    dos = dout * jnp.asarray(scale, dout.dtype)
    # delta'_i = rowsum(do'_i * out_i)  [B,H,Sq]
    delta = jnp.einsum("bqhd,bqhd->bhq", dos.astype(jnp.float32),
                       out.astype(jnp.float32))

    dq0 = jnp.zeros((B, Sq, H, D), jnp.float32)
    dk0 = jnp.zeros((B, Sk, H, D), jnp.float32)
    dv0 = jnp.zeros((B, Sk, H, D), jnp.float32)

    def body(carry, ij, masked):
        dq, dk, dv = carry
        i, j = ij
        qc = lax.dynamic_slice_in_dim(q, i * cq, cq, axis=1)
        qsc = lax.dynamic_slice_in_dim(qs, i * cq, cq, axis=1)
        kc = lax.dynamic_slice_in_dim(kr, j * ck, ck, axis=1)
        vc = lax.dynamic_slice_in_dim(vr, j * ck, ck, axis=1)
        doc = lax.dynamic_slice_in_dim(dout, i * cq, cq, axis=1)
        dosc = lax.dynamic_slice_in_dim(dos, i * cq, cq, axis=1)
        lse_c = lax.dynamic_slice_in_dim(lse, i * cq, cq, axis=2)
        del_c = lax.dynamic_slice_in_dim(delta, i * cq, cq, axis=2)
        s = jnp.einsum("bqhd,bshd->bhqs", qsc, kc,
                       preferred_element_type=jnp.float32)
        s = constrain(s, "batch", "heads", None, None)
        if masked:
            q_pos = i * cq + jnp.arange(cq)[:, None] + (Sk - Sq)
            k_pos = j * ck + jnp.arange(ck)[None, :]
            s = jnp.where((k_pos <= q_pos)[None, None], s, -jnp.inf)
        p = jnp.exp(s - lse_c[..., None])          # masked → exp(-inf)=0
        if masked:
            p = jnp.where(jnp.isneginf(s), 0.0, p)
        pd = p.astype(doc.dtype)
        dvc = jnp.einsum("bhqs,bqhd->bshd", pd, doc,
                         preferred_element_type=jnp.float32)
        dp = jnp.einsum("bqhd,bshd->bhqs", dosc, vc,
                        preferred_element_type=jnp.float32)
        ds = p * (dp - del_c[..., None])
        dsd = ds.astype(kc.dtype)
        dqc = jnp.einsum("bhqs,bshd->bqhd", dsd, kc,
                         preferred_element_type=jnp.float32)
        dkc = jnp.einsum("bhqs,bqhd->bshd", dsd, qc,
                         preferred_element_type=jnp.float32)
        dq_i = lax.dynamic_slice_in_dim(dq, i * cq, cq, axis=1) + dqc
        dq = lax.dynamic_update_slice_in_dim(dq, dq_i, i * cq, axis=1)
        dk_j = lax.dynamic_slice_in_dim(dk, j * ck, ck, axis=1) + dkc
        dk = lax.dynamic_update_slice_in_dim(dk, dk_j, j * ck, axis=1)
        dv_j = lax.dynamic_slice_in_dim(dv, j * ck, ck, axis=1) + dvc
        dv = lax.dynamic_update_slice_in_dim(dv, dv_j, j * ck, axis=1)
        return (dq, dk, dv), None

    offd, diag = _split_pairs(Sq, Sk, cq, ck, causal, causal_skip)
    carry = (dq0, dk0, dv0)
    if offd:
        xs = (jnp.asarray([p[0] for p in offd], jnp.int32),
              jnp.asarray([p[1] for p in offd], jnp.int32))
        carry, _ = lax.scan(functools.partial(body, masked=False),
                            carry, xs)
    if diag:
        xs = (jnp.asarray([p[0] for p in diag], jnp.int32),
              jnp.asarray([p[1] for p in diag], jnp.int32))
        carry, _ = lax.scan(functools.partial(body, masked=causal),
                            carry, xs)
    (dq, dk, dv) = carry
    if K != H:                                    # fold GQA repeats back
        G = H // K
        dk = dk.reshape(B, Sk, K, G, D).sum(3)
        dv = dv.reshape(B, Sk, K, G, D).sum(3)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention(q, k, v, causal, cq, ck, causal_skip):
    out, _ = _flash_fwd(q, k, v, causal, cq, ck, causal_skip)
    return out


def _flash_vjp_fwd(q, k, v, causal, cq, ck, causal_skip):
    out, lse = _flash_fwd(q, k, v, causal, cq, ck, causal_skip)
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(causal, cq, ck, causal_skip, res, dout):
    q, k, v, out, lse = res
    return _flash_bwd_scan(q, k, v, out, lse, dout, causal, cq, ck,
                           causal_skip)


_flash_attention.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


@jax.named_scope("attention")
def flash_attention_xla(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = True,
                        chunk_q: int = 512, chunk_k: int = 512,
                        causal_skip: bool = True) -> jax.Array:
    """Chunked online-softmax attention in pure XLA with a custom VJP.

    * never materializes [Sq, Sk];
    * backward recomputes per chunk-pair (flash algorithm), so residuals
      are O(S·H·D) — a lax.scan with autodiff would instead save every
      per-pair carry (observed 16 GiB/device on llama train_4k before this
      custom VJP);
    * ``causal_skip`` schedules only lower-triangular chunk pairs.

    The TPU fast path is the Pallas kernel in repro.kernels.flash_attention;
    this XLA formulation is what the 512-device dry-run compiles (Pallas
    does not lower to the CPU backend).
    """
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    cq, ck = min(chunk_q, Sq), min(chunk_k, Sk)
    assert Sq % cq == 0 and Sk % ck == 0, (Sq, cq, Sk, ck)
    return _flash_attention(q, k, v, causal, cq, ck, causal_skip)


@jax.named_scope("attention")
def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     cache_len: jax.Array) -> jax.Array:
    """Single-token attention against a (padded) KV cache.

    q [B,1,H,D]; caches [B,Smax,K,D]; cache_len: valid prefix (includes the
    token just written).  Softmax over the padded axis is masked.

    Grouped-query form (scope ``gqa_grouped``): q is viewed as
    [B,1,K,H//K,D] and contracted against the caches as stored, so each
    kv head's bf16 cache is read once for its H // K query heads, never
    repeated to H heads.  Head h reads kv head h // (H // K), as
    ``jnp.repeat`` maps it.  Taken with no rules bound (the serve engine on
    one chip) or with ``kv_heads`` sharded.  Only when the bound rules
    shard ``heads`` but not ``kv_heads`` (TP > K) are the caches repeated
    to H heads first, the Megatron treatment of ``repeat_kv`` (scope
    ``gqa_repeated``).  With K == H both forms are the same work.

    Cache-dtype-native: scores/outputs accumulate in fp32 via
    ``preferred_element_type`` but the cache operands are NEVER converted —
    a ``cache.astype(f32)`` here gets hoisted out of the layer scan by
    XLA's loop-widening pass, materializing the whole multi-GiB cache in
    fp32 (observed +12 GiB/device on moonshot decode_32k).

    Numerics mirror ``_flash_fwd_scan`` op-for-op (scale folded into q in
    the cache dtype; probabilities rounded to the value dtype BEFORE the
    normalizing sum; out = pv / l): decode must reproduce the prefill
    path's rounding, otherwise ulp-level drift in the hidden state flips
    near-tied MoE router choices and decode diverges from teacher forcing
    (observed on deepseek-moe-16b: a top-2 gate at 0.506/0.494 flipped at
    layer 0, 0.41 logit error downstream).
    """
    B, _, H, D = q.shape
    rules = active_rules() or {}
    repeated = (rules.get("heads") is not None
                and rules.get("kv_heads") is None)
    cl = jnp.asarray(cache_len)
    if cl.ndim == 1:                      # ragged: per-row valid prefix [B]
        cl = cl[:, None, None, None, None]
    with jax.named_scope("gqa_repeated" if repeated else "gqa_grouped"):
        # barrier: without it, the CPU backend legalizes the bf16 dot below
        # as convert(f32)+dot, and LICM hoists the convert of the *whole
        # stacked cache* out of the layer scan (+12 GiB/device observed).
        # On TPU the dot is native bf16 and the barrier is free.
        k_cache, v_cache = lax.optimization_barrier((k_cache, v_cache))
        if repeated:
            k_cache, v_cache = repeat_kv(k_cache, H), repeat_kv(v_cache, H)
        S, Kv = k_cache.shape[1], k_cache.shape[2]
        qs = q.astype(k_cache.dtype) * jnp.asarray(1.0 / math.sqrt(D),
                                                   k_cache.dtype)
        qg = qs.reshape(B, 1, Kv, H // Kv, D)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k_cache,
                       preferred_element_type=jnp.float32)
        s = constrain(s, "batch", "heads" if repeated else "kv_heads",
                      None, None, None)
        s = jnp.where(jnp.arange(S) < cl, s, -jnp.inf)
        m = jnp.max(s, axis=-1)
        m_safe = jnp.where(jnp.isneginf(m), 0.0, m)
        p = jnp.exp(s - m_safe[..., None]).astype(v_cache.dtype)
        p = jnp.where(jnp.isneginf(s), 0.0, p)
        l = jnp.sum(p.astype(jnp.float32), axis=-1)
        pv = jnp.einsum("bkgqs,bskd->bkgqd", p, v_cache,
                        preferred_element_type=jnp.float32)
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o = jnp.transpose(pv / l_safe[..., None], (0, 3, 1, 2, 4))
        return o.reshape(B, 1, H, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# the attention sublayer and the KV cache
# ---------------------------------------------------------------------------

@jax.named_scope("attention")
def attention(p: Params, x: jax.Array, positions: Optional[jax.Array], cfg,
              causal: bool = True):
    """Self-attention over whole sequences (train, prefill): projections,
    rope, the kernel ``cfg.attn_impl`` names, ``wo``.  x [B,S,d] →
    (out [B,S,d], (k, v)), the rotated keys and values a prefill caches."""
    B, S = x.shape[:2]
    H, hd = cfg.num_heads, cfg.hd
    q, k, v = _qkv(p, x, H, cfg.num_kv_heads, hd, cfg.qk_norm, cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta, cfg.mrope_sections,
                   cfg.use_rope)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.mrope_sections,
                   cfg.use_rope)
    if cfg.attn_impl == "naive":
        o = naive_attention(q, k, v, causal=causal)
    else:
        o = flash_attention_xla(q, k, v, causal=causal,
                                chunk_q=pick_chunk(S, cfg.attn_chunk_q),
                                chunk_k=pick_chunk(S, cfg.attn_chunk_k),
                                causal_skip=cfg.causal_skip)
    return o.reshape(B, S, H * hd) @ p["wo"].astype(x.dtype), (k, v)


def decode_positions(pos: jax.Array, B: int,
                     mrope_sections: Tuple[int, ...] = ()) -> jax.Array:
    """Rope positions of the one token each of B rows decodes: ``pos`` is
    a scalar (every row at one position) or ``[B]`` (each row at its own)."""
    positions = (pos[:, None] if pos.ndim
                 else jnp.broadcast_to(pos[None, None], (B, 1)))
    if mrope_sections:
        positions = jnp.broadcast_to(positions[None], (3, B, 1))
    return positions


def kv_rows(pos: jax.Array, B: int) -> Optional[jax.Array]:
    """The rows a ``[B]`` ``pos`` writes one by one (``write_kv``); None for
    a scalar ``pos``, which writes them all at once.  Made once a step,
    before the layer scan, as the rope positions are."""
    return jnp.arange(B) if pos.ndim else None


def write_kv(cache: jax.Array, new: jax.Array, pos, layer=None,
             rows: Optional[jax.Array] = None) -> jax.Array:
    """Write keys or values into a stacked cache ``[L,B,S,K,hd]``, the one
    function that knows that layout.

    With ``layer`` None, ``new`` is every layer's ``[L,B,s,K,hd]`` from
    position ``pos`` on (prefill).  Otherwise it is one token a row of
    layer ``layer``, ``[B,1,K,hd]``: a scalar ``pos`` writes every row
    there with one ``dynamic_update_slice`` (no scatter, so a
    sequence-sharded cache stays sharded); a ``[B]`` ``pos`` writes row
    ``rows[b]`` at ``pos[b]`` (``kv_rows``), a scatter."""
    if layer is None:
        return lax.dynamic_update_slice_in_dim(
            cache, new.astype(cache.dtype), pos, axis=2)
    if rows is None:
        return lax.dynamic_update_slice(
            cache, new[None].astype(cache.dtype), (layer, 0, pos, 0, 0))
    return cache.at[layer, rows, pos].set(new[:, 0].astype(cache.dtype))


@jax.named_scope("attention")
def attention_decode(p: Params, x: jax.Array, kv, layer, pos: jax.Array,
                     rows: Optional[jax.Array],
                     positions: Optional[jax.Array], cfg):
    """Self-attention of one new token a row (decode): projections, rope at
    ``positions`` (``decode_positions``), the write of its keys and values
    at ``pos`` into layer ``layer`` of the stacked caches ``kv = (k, v)``,
    attention over the layer's first ``pos + 1`` positions, ``wo``.
    x [B,1,d] → (out [B,1,d], kv)."""
    B = x.shape[0]
    H, hd = cfg.num_heads, cfg.hd
    q, k, v = _qkv(p, x, H, cfg.num_kv_heads, hd, cfg.qk_norm, cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta, cfg.mrope_sections,
                   cfg.use_rope)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.mrope_sections,
                   cfg.use_rope)
    k_all = write_kv(kv[0], k, pos, layer, rows)
    v_all = write_kv(kv[1], v, pos, layer, rows)
    o = decode_attention(q, k_all[layer], v_all[layer], pos + 1)
    return o.reshape(B, 1, H * hd) @ p["wo"].astype(x.dtype), (k_all, v_all)


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GeLU)
# ---------------------------------------------------------------------------

def init_mlp(key, d: int, ff: int, act: str = "silu") -> Params:
    ks = jax.random.split(key, 3)
    p = {"w_up": dense_init(ks[0], (d, ff)),
         "w_down": dense_init(ks[1], (ff, d))}
    if act == "silu":
        p["w_gate"] = dense_init(ks[2], (d, ff))
    return p


@jax.named_scope("mlp")
def mlp(p: Params, x: jax.Array, act: str = "silu") -> jax.Array:
    up = constrain(x @ p["w_up"].astype(x.dtype), "batch", None, "ff")
    if act == "silu":
        gate = jax.nn.silu(x @ p["w_gate"].astype(x.dtype))
        h = gate * up
    else:
        h = jax.nn.gelu(up)
    return constrain(h @ p["w_down"].astype(x.dtype), "batch", None, None)


# ---------------------------------------------------------------------------
# Mixture of Experts (capacity-based scatter dispatch; einsum reference)
# ---------------------------------------------------------------------------

def init_moe(key, d: int, E: int, ff: int, n_shared: int,
             act: str = "silu") -> Params:
    ks = jax.random.split(key, 5)
    p: Params = {
        "router": dense_init(ks[0], (d, E), scale=0.02),
        "w_up": dense_init(ks[1], (E, d, ff)),
        "w_down": dense_init(ks[2], (E, ff, d)),
    }
    if act == "silu":
        p["w_gate"] = dense_init(ks[3], (E, d, ff))
    if n_shared:
        p["shared"] = init_mlp(ks[4], d, ff * n_shared, act)
    return p


def _router(p: Params, x: jax.Array, top_k: int
            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Return (gates [...,k], expert_idx [...,k], aux_loss scalar)."""
    logits = (x.astype(jnp.float32) @ p["router"])          # [..., E]
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = lax.top_k(probs, top_k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    # Switch-style load-balance loss: E * mean_e(frac_tokens_e * mean_prob_e)
    E = probs.shape[-1]
    onehot = jax.nn.one_hot(idx[..., 0], E, dtype=jnp.float32)
    frac = jnp.mean(onehot.reshape(-1, E), axis=0)
    mprob = jnp.mean(probs.reshape(-1, E), axis=0)
    aux = E * jnp.sum(frac * mprob)
    return gates, idx, aux


def moe_capacity(tokens_per_group: int, E: int, top_k: int,
                 capacity_factor: float) -> int:
    c = int(math.ceil(tokens_per_group * top_k / E * capacity_factor))
    return max(8, -(-c // 8) * 8)          # ≥8 and multiple of 8 (layout)


def moe_scatter(p: Params, x: jax.Array, *, top_k: int,
                capacity_factor: float, act: str = "silu",
                n_shared: int = 0) -> Tuple[jax.Array, jax.Array]:
    """Capacity-based MoE with scatter dispatch (the production path).

    x: [B, S, d].  Groups are sequences (S > 1) or the whole batch (decode).
    Tokens beyond an expert's capacity are dropped (standard capacity-based
    routing); capacity_factor controls the drop rate.

    Expert weights [E, d, ff] shard E over the 'model' axis (EP); the
    scatter/gather across the token→expert layout change is where XLA
    inserts the all-to-all.
    """
    B, S, d = x.shape
    E = p["w_up"].shape[0]
    decode = S == 1
    xg = x.reshape(1, B, d) if decode else x                # [G, T, d]
    G, T, _ = xg.shape
    C = moe_capacity(T, E, top_k, capacity_factor)

    gates, idx, aux = _router(p, xg, top_k)                 # [G,T,k]
    flat_e = idx.reshape(G, T * top_k)                      # [G, Tk]
    gate_flat = gates.reshape(G, T * top_k)
    # position of each assignment within its expert (first-come-first-served)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)     # [G,Tk,E]
    pos_in_e = (jnp.cumsum(onehot, axis=1) - 1)
    pos_in_e = jnp.sum(pos_in_e * onehot, axis=-1)          # [G,Tk]
    keep = pos_in_e < C
    pos_c = jnp.where(keep, pos_in_e, C - 1)

    x_rep = jnp.repeat(xg, top_k, axis=1)                   # [G,Tk,d]
    x_rep = jnp.where(keep[..., None], x_rep, 0)
    gidx = jnp.arange(G)[:, None]
    buf = jnp.zeros((G, E, C, d), x.dtype)
    buf = buf.at[gidx, flat_e, pos_c].add(x_rep)            # dispatch
    # the token→expert layout change: E goes to the EP ('model') axis here,
    # which is where XLA inserts the all-to-all
    buf = constrain(buf, None, "experts", None, None)

    # expert FFN: [G,E,C,d] x [E,d,f]
    up = jnp.einsum("gecd,edf->gecf", buf, p["w_up"].astype(x.dtype))
    if act == "silu":
        gt = jnp.einsum("gecd,edf->gecf", buf, p["w_gate"].astype(x.dtype))
        h = jax.nn.silu(gt) * up
    else:
        h = jax.nn.gelu(up)
    out_buf = jnp.einsum("gecf,efd->gecd", h, p["w_down"].astype(x.dtype))
    out_buf = constrain(out_buf, None, "experts", None, None)

    y_tok = out_buf[gidx, flat_e, pos_c]                    # gather back
    y_tok = y_tok * (gate_flat * keep)[..., None].astype(x.dtype)
    y = jnp.sum(y_tok.reshape(G, T, top_k, d), axis=2)      # combine
    y = y.reshape(B, S, d)
    if n_shared:
        y = y + mlp(p["shared"], x, act)
    return y, aux


def moe_einsum(p: Params, x: jax.Array, *, top_k: int,
               capacity_factor: float, act: str = "silu",
               n_shared: int = 0) -> Tuple[jax.Array, jax.Array]:
    """Reference MoE: dense one-hot dispatch/combine einsums (Mesh-TF style).

    O(T·E·C) memory — only used for small shapes and as the oracle the
    scatter path is tested against.
    """
    B, S, d = x.shape
    E = p["w_up"].shape[0]
    decode = S == 1
    xg = x.reshape(1, B, d) if decode else x
    G, T, _ = xg.shape
    C = moe_capacity(T, E, top_k, capacity_factor)

    gates, idx, aux = _router(p, xg, top_k)
    # dispatch[g,t,e,c] — position via per-expert cumsum over (t,k) order
    flat_e = idx.reshape(G, T * top_k)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
    pos = jnp.sum((jnp.cumsum(onehot, axis=1) - 1) * onehot, -1)
    keep = pos < C
    disp = (jax.nn.one_hot(flat_e, E, dtype=xg.dtype)[..., None]
            * jax.nn.one_hot(jnp.where(keep, pos, C), C + 1,
                             dtype=xg.dtype)[..., None, :-1])  # [G,Tk,E,C]
    comb = disp * gates.reshape(G, T * top_k)[..., None, None]
    disp = disp.reshape(G, T, top_k, E, C).sum(2)
    comb = comb.reshape(G, T, top_k, E, C).sum(2)

    buf = jnp.einsum("gtec,gtd->gecd", disp, xg)
    up = jnp.einsum("gecd,edf->gecf", buf, p["w_up"].astype(x.dtype))
    if act == "silu":
        gt = jnp.einsum("gecd,edf->gecf", buf, p["w_gate"].astype(x.dtype))
        h = jax.nn.silu(gt) * up
    else:
        h = jax.nn.gelu(up)
    out_buf = jnp.einsum("gecf,efd->gecd", h, p["w_down"].astype(x.dtype))
    y = jnp.einsum("gtec,gecd->gtd", comb, out_buf).reshape(B, S, d)
    if n_shared:
        y = y + mlp(p["shared"], x, act)
    return y, aux


def moe_shard_map(p: Params, x: jax.Array, cfg, rules
                  ) -> Tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE via shard_map — the production dispatch.

    Each model-axis rank owns E/|model| experts.  Activations are already
    replicated across 'model' (they're only batch-sharded), so every rank
    routes all of its tokens, scatters ONLY the assignments that target a
    local expert into a small [G, E_loc, C, d] buffer, runs its experts,
    and the per-rank partial outputs are psum'd — the same all-reduce
    shape TP pays for a dense MLP.

    Why not pjit-level scatter: XLA cannot shard a scatter's target dim,
    so the [G, E, C, d] dispatch buffer materializes E-replicated per
    device (observed 2.5 GiB × live-window on jamba prefill_32k).  Here
    the scatter target is E_loc by construction.
    """
    from jax.sharding import PartitionSpec as P
    mesh = rules["mesh"]
    ep_axis = rules["experts"]
    batch = rules["batch"]
    E = p["w_up"].shape[0]
    n_ranks = mesh.shape[ep_axis] if isinstance(ep_axis, str) else 1
    E_loc = E // n_ranks
    top_k = cfg.moe_top_k

    x_spec = P(batch, None, None) if x.shape[0] % _dpsize(mesh, batch) == 0 \
        else P(None, None, None)
    w_specs = {
        "router": P(None, None),
        "w_up": P(ep_axis, None, None),
        "w_down": P(ep_axis, None, None),
    }
    if "w_gate" in p:
        w_specs["w_gate"] = P(ep_axis, None, None)
    weights = {k: p[k] for k in w_specs}

    def local_fn(x_loc, w):
        B, S, d = x_loc.shape
        decode = S == 1
        xg = x_loc.reshape(1, B, d) if decode else x_loc
        G, T, _ = xg.shape
        C = moe_capacity(T, E, top_k, cfg.moe_capacity_factor)
        gates, idx, aux = _router({"router": w["router"]}, xg, top_k)
        rank = lax.axis_index(ep_axis)
        local = idx - rank * E_loc                       # [G,T,k]
        flat_e = local.reshape(G, T * top_k)
        gate_flat = gates.reshape(G, T * top_k)
        # position within expert counted over the GLOBAL expert id so all
        # ranks agree on capacity-based drops
        onehot = jax.nn.one_hot(idx.reshape(G, T * top_k), E,
                                dtype=jnp.int32)
        pos = jnp.sum((jnp.cumsum(onehot, axis=1) - 1) * onehot, -1)
        mine = (flat_e >= 0) & (flat_e < E_loc)
        keep = (pos < C) & mine
        e_c = jnp.where(keep, flat_e, 0)
        pos_c = jnp.where(keep, pos, C - 1)
        x_rep = jnp.repeat(xg, top_k, axis=1)
        x_rep = jnp.where(keep[..., None], x_rep, 0)
        gidx = jnp.arange(G)[:, None]
        buf = jnp.zeros((G, E_loc, C, d), x_loc.dtype)
        buf = buf.at[gidx, e_c, pos_c].add(x_rep)
        up = jnp.einsum("gecd,edf->gecf", buf, w["w_up"].astype(x_loc.dtype))
        if cfg.act == "silu":
            gt = jnp.einsum("gecd,edf->gecf", buf,
                            w["w_gate"].astype(x_loc.dtype))
            hh = jax.nn.silu(gt) * up
        else:
            hh = jax.nn.gelu(up)
        out_buf = jnp.einsum("gecf,efd->gecd", hh,
                             w["w_down"].astype(x_loc.dtype))
        y_tok = out_buf[gidx, e_c, pos_c]
        y_tok = y_tok * (gate_flat * keep)[..., None].astype(x_loc.dtype)
        y = jnp.sum(y_tok.reshape(G, T, top_k, d), axis=2)
        y = lax.psum(y, ep_axis)            # combine across expert ranks
        return y.reshape(B, S, d), aux

    y, aux = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(x_spec, w_specs),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(x, weights)
    if cfg.moe_num_shared:
        y = y + mlp(p["shared"], x, cfg.act)
    return y, aux


def _dpsize(mesh, batch_axes_) -> int:
    if isinstance(batch_axes_, str):
        return mesh.shape[batch_axes_]
    n = 1
    for a in batch_axes_ or ():
        n *= mesh.shape[a]
    return n


@jax.named_scope("mlp")
def moe_layer(p: Params, x: jax.Array, cfg) -> Tuple[jax.Array, jax.Array]:
    from repro.distributed.logical import active_rules
    rules = active_rules()
    E = p["w_up"].shape[0]
    if (rules is not None and rules.get("mesh") is not None
            and isinstance(rules.get("experts"), str)
            and cfg.moe_dispatch == "scatter"
            and E % rules["mesh"].shape[rules["experts"]] == 0):
        return moe_shard_map(p, x, cfg, rules)
    fn = moe_scatter if cfg.moe_dispatch == "scatter" else moe_einsum
    return fn(p, x, top_k=cfg.moe_top_k,
              capacity_factor=cfg.moe_capacity_factor, act=cfg.act,
              n_shared=cfg.moe_num_shared)


# ---------------------------------------------------------------------------
# Mamba2 / SSD block
# ---------------------------------------------------------------------------

#: The published ``Mamba2`` module's settings (state-spaces/mamba,
#: arXiv:2405.21060) that ``init_mamba2`` and ``mamba2_block`` follow:
#: A drawn uniform in ``A_init_range``; dt log-uniform in
#: [``dt_min``, ``dt_max``], floored at ``dt_init_floor``, stored as its
#: softplus inverse; a bias on the conv over x, B and C and none on the
#: projections; D one scalar a head; the gated norm after the gate.
MAMBA2_MODULE = {"A_init_range": [1.0, 16.0], "dt_min": 1e-3, "dt_max": 0.1,
                 "dt_init_floor": 1e-4, "conv_bias": True, "bias": False,
                 "D_has_hdim": False, "norm_before_gate": False}


def init_mamba2(key, cfg) -> Params:
    """Mamba2 weights with *split* projections.

    Upstream fuses (z,x,B,C,dt) into one in_proj and (x,B,C) into one conv.
    We keep them as separate matrices: mathematically identical, but the
    fused layouts concatenate segments whose boundaries are not divisible
    by the 16-way model axis, which would force full replication under TP.
    Split weights let d_inner shard cleanly (see distributed/partition.py).
    A_log, dt_bias, D and the conv biases are drawn as upstream draws them
    (``MAMBA2_MODULE``; the biases as torch's Conv1d default,
    U(±1/sqrt(d_conv))).
    """
    d, di = cfg.d_model, cfg.ssm_d_inner
    H, N, G = cfg.ssm_heads, cfg.ssm_state, cfg.ssm_groups
    k = cfg.ssm_conv
    m = MAMBA2_MODULE
    ks = jax.random.split(key, 14)
    lo, hi = m["A_init_range"]
    A = jax.random.uniform(ks[12], (H,), jnp.float32, lo, hi)
    log_dt = jax.random.uniform(ks[13], (H,), jnp.float32,
                                math.log(m["dt_min"]), math.log(m["dt_max"]))
    dt = jnp.maximum(jnp.exp(log_dt), m["dt_init_floor"])
    bound = 1.0 / math.sqrt(k)
    return {
        "w_z": dense_init(ks[0], (d, di)),
        "w_x": dense_init(ks[1], (d, di)),
        "w_B": dense_init(ks[2], (d, G * N)),
        "w_C": dense_init(ks[3], (d, G * N)),
        "w_dt": dense_init(ks[4], (d, H)),
        "conv_x": dense_init(ks[5], (k, di), scale=0.5),
        "conv_B": dense_init(ks[6], (k, G * N), scale=0.5),
        "conv_C": dense_init(ks[7], (k, G * N), scale=0.5),
        "conv_x_bias": jax.random.uniform(ks[9], (di,), jnp.float32,
                                          -bound, bound),
        "conv_B_bias": jax.random.uniform(ks[10], (G * N,), jnp.float32,
                                          -bound, bound),
        "conv_C_bias": jax.random.uniform(ks[11], (G * N,), jnp.float32,
                                          -bound, bound),
        "A_log": jnp.log(A),
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),     # softplus^-1(dt)
        "norm": init_rmsnorm(di),
        "out_proj": dense_init(ks[8], (di, d)),
    }


def causal_conv1d(w: jax.Array, bias: jax.Array, x: jax.Array,
                  tail: Optional[jax.Array] = None) -> jax.Array:
    """Depthwise causal conv via shift-and-sum, plus bias, then SiLU.
    w [k, C]; bias [C]; x [B, S, C].

    ``tail``: [B, k-1, C] carry-in from previous tokens (decode path).
    """
    k = w.shape[0]
    if tail is None:
        tail = jnp.zeros((x.shape[0], k - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([tail, x], axis=1)        # [B, S+k-1, C]
    S = x.shape[1]
    out = jnp.zeros_like(x, dtype=jnp.float32)
    for i in range(k):
        out = out + xp[:, i:i + S].astype(jnp.float32) * w[i]
    return jax.nn.silu(out + bias.astype(jnp.float32)).astype(x.dtype)


def ssd_reference(x, dt, A, B, C, D, *, init_state=None):
    """Sequential SSD recurrence — the ground-truth oracle.

    x [b,l,h,p]; dt [b,l,h]; A [h] (negative); B,C [b,l,g,n] (g=1); D [h].
    h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t ;  y_t = C_t · h_t + D x_t.
    Returns (y [b,l,h,p], final_state [b,h,p,n]).
    """
    b, l, h, p = x.shape
    n = B.shape[-1]
    h0 = (jnp.zeros((b, h, p, n), jnp.float32) if init_state is None
          else init_state.astype(jnp.float32))

    def step(hprev, inp):
        xt, dtt, Bt, Ct = inp                       # [b,h,p],[b,h],[b,n],[b,n]
        dA = jnp.exp(dtt * A)                       # [b,h]
        dBx = jnp.einsum("bhp,bn,bh->bhpn", xt.astype(jnp.float32),
                         Bt.astype(jnp.float32), dtt)
        hnew = hprev * dA[..., None, None] + dBx
        yt = jnp.einsum("bhpn,bn->bhp", hnew, Ct.astype(jnp.float32))
        return hnew, yt

    xs = (jnp.moveaxis(x, 1, 0), jnp.moveaxis(dt, 1, 0),
          jnp.moveaxis(B[:, :, 0], 1, 0), jnp.moveaxis(C[:, :, 0], 1, 0))
    hfin, ys = lax.scan(step, h0, xs)
    y = jnp.moveaxis(ys, 0, 1) + x.astype(jnp.float32) * D[None, None, :, None]
    return y.astype(x.dtype), hfin


@jax.named_scope("ssd")
def ssd_chunked(x, dt, A, B, C, D, *, chunk: int = 128, init_state=None):
    """Chunked SSD (state-space duality) — the parallel production path.

    Intra-chunk term is attention-like (quadratic in chunk only); inter-chunk
    states pass through a short scan over chunks.  Matches ssd_reference to
    fp32 tolerance (tested).  Returns (y, final_state).

    Which body runs is decided when the program is lowered: on a TPU, the
    fused Pallas kernels of ``repro.kernels.ssd_scan`` (forward and
    backward), where one group of B and C, a chunk that is a multiple of
    8, head sizes the kernels tile, a state that fits their VMEM
    (``plan``) and a mesh of one device allow them;
    elsewhere, and on every other platform, the XLA body
    (``_ssd_chunked_xla``), which is the kernels' oracle in the tests.
    """
    b, l, h, p = x.shape
    Q = min(chunk, l)
    if l % Q:
        # pad tail with dt=0 tokens: zero decay-rate and zero input, so the
        # final state is unaffected; padded y rows are sliced off below
        pad = Q - l % Q
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
        y, hfin = ssd_chunked(x, dt, A, B, C, D, chunk=chunk,
                              init_state=init_state)
        return y[:, :l], hfin
    h0 = (jnp.zeros((b, h, p, B.shape[-1]), jnp.float32)
          if init_state is None else init_state.astype(jnp.float32))
    xla = functools.partial(_ssd_chunked_xla, chunk=Q)
    # imported here: the kernel package imports this module (its oracle)
    from repro.kernels.ssd_scan.kernel import plan, ssd_scan
    if (B.shape[2] != 1 or not _on_one_device()
            or plan(h, p, B.shape[-1], Q) is None):
        return xla(x, dt, A, B, C, D, h0)
    return lax.platform_dependent(
        x, dt, A, B, C, D, h0, default=xla,
        tpu=functools.partial(ssd_scan, chunk=Q))


def _on_one_device() -> bool:
    """Whether the program being traced runs on one device: no mesh
    bound, or a mesh of one (a kernel is not split over a mesh)."""
    rules = active_rules()
    mesh = rules.get("mesh") if rules else None
    return mesh is None or mesh.size == 1


def _ssd_chunked_xla(x, dt, A, B, C, D, h0, *, chunk: int):
    """``ssd_chunked``'s XLA body: the length a multiple of ``chunk``,
    the initial state ``h0`` float32."""
    b, l, h, p = x.shape
    n = B.shape[-1]
    Q = chunk
    nc = l // Q
    xf = x.astype(jnp.float32).reshape(b, nc, Q, h, p)
    dtf = dt.astype(jnp.float32).reshape(b, nc, Q, h)
    Bf = B[:, :, 0].astype(jnp.float32).reshape(b, nc, Q, n)
    Cf = C[:, :, 0].astype(jnp.float32).reshape(b, nc, Q, n)

    a = dtf * A[None, None, None, :]                 # [b,nc,Q,h] (negative)
    a_cs = jnp.cumsum(a, axis=2)                     # inclusive
    a_tot = a_cs[:, :, -1]                           # [b,nc,h]

    # intra-chunk: y_q += sum_{k<=q} exp(a_cs_q - a_cs_k) (C_q·B_k) dt_k x_k
    cb = jnp.einsum("bcqn,bckn->bcqk", Cf, Bf)       # [b,nc,Q,Q]
    # the segment sums are masked to -inf above the diagonal before exp, as
    # upstream's segsum: there they are large and positive, and an exp that
    # overflowed to inf would meet the mask's zero in the backward pass
    # (inf * 0 = NaN)
    mask = jnp.tril(jnp.ones((Q, Q), bool))
    seg = a_cs[:, :, :, None, :] - a_cs[:, :, None, :, :]
    decay = jnp.exp(jnp.where(mask[None, None, :, :, None], seg, -jnp.inf))
    w = cb[..., None] * decay                        # [b,nc,Q,Q,h]
    y_intra = jnp.einsum("bcqkh,bckh,bckhp->bcqhp", w, dtf, xf)

    # chunk states: S_c = sum_k exp(a_tot - a_cs_k) dt_k B_k x_k → [b,nc,h,p,n]
    edecay = jnp.exp(a_tot[:, :, None, :] - a_cs)    # [b,nc,Q,h]
    states = jnp.einsum("bckh,bckh,bckhp,bckn->bchpn",
                        edecay, dtf, xf, Bf)

    def carry(hprev, inp):
        s_c, atot_c = inp                            # [b,h,p,n], [b,h]
        hnew = hprev * jnp.exp(atot_c)[:, :, None, None] + s_c
        return hnew, hprev                           # emit state *entering* c

    hfin, h_in = lax.scan(carry, h0,
                          (jnp.moveaxis(states, 1, 0),
                           jnp.moveaxis(a_tot, 1, 0)))
    h_in = jnp.moveaxis(h_in, 0, 1)                  # [b,nc,h,p,n]

    # inter-chunk: y_q += C_q · h_in * exp(a_cs_q)
    y_inter = jnp.einsum("bcqn,bcqh,bchpn->bcqhp", Cf, jnp.exp(a_cs), h_in)

    y = (y_intra + y_inter).reshape(b, l, h, p)
    y = y + x.astype(jnp.float32) * D[None, None, :, None]
    return y.astype(x.dtype), hfin


@jax.named_scope("mixer")
def mamba2_block(p: Params, x: jax.Array, cfg, *,
                 ssm_state=None, conv_tail=None, return_state: bool = False):
    """Full Mamba2 sublayer.  x [B,S,d] → y [B,S,d] (+ cache updates).

    ``conv_tail``: dict {x,B,C} of [B, k-1, ·] carry-ins (or None).
    """
    B_, S, d = x.shape
    di, H = cfg.ssm_d_inner, cfg.ssm_heads
    N, G, P = cfg.ssm_state, cfg.ssm_groups, cfg.ssm_head_dim
    z = constrain(x @ p["w_z"].astype(x.dtype), "batch", None, "inner")
    xin = constrain(x @ p["w_x"].astype(x.dtype), "batch", None, "inner")
    Bc = x @ p["w_B"].astype(x.dtype)
    Cc = x @ p["w_C"].astype(x.dtype)
    dt_raw = constrain(x @ p["w_dt"].astype(x.dtype),
                       "batch", None, "ssm_heads")
    km1 = cfg.ssm_conv - 1
    new_tail = ({"x": xin[:, -km1:], "B": Bc[:, -km1:], "C": Cc[:, -km1:]}
                if return_state else None)
    tails = conv_tail or {"x": None, "B": None, "C": None}
    xin = causal_conv1d(p["conv_x"], p["conv_x_bias"], xin, tail=tails["x"])
    Bc = causal_conv1d(p["conv_B"], p["conv_B_bias"], Bc, tail=tails["B"])
    Cc = causal_conv1d(p["conv_C"], p["conv_C_bias"], Cc, tail=tails["C"])

    xh = constrain(xin.reshape(B_, S, H, P), "batch", None, "ssm_heads",
                   None)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    Bm = Bc.reshape(B_, S, G, N)
    Cm = Cc.reshape(B_, S, G, N)
    y, final_state = ssd_chunked(xh, dt, A, Bm, Cm, p["D"],
                                 chunk=cfg.ssm_chunk, init_state=ssm_state)
    y = y.reshape(B_, S, di)
    y = rms_norm(p["norm"], y * jax.nn.silu(z), cfg.norm_eps)
    out = y @ p["out_proj"].astype(x.dtype)
    if return_state:
        return out, final_state, new_tail
    return out


def _conv_decode(w: jax.Array, bias: jax.Array, tail: jax.Array,
                 new: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """One-token depthwise conv: (out [B,1,C], new_tail [B,k-1,C])."""
    full = jnp.concatenate([tail, new], axis=1)             # [B,k,C]
    out = jax.nn.silu(
        jnp.sum(full.astype(jnp.float32) * w[None], axis=1, keepdims=True)
        + bias.astype(jnp.float32)).astype(new.dtype)
    return out, full[:, 1:]


def mamba2_decode_step(p: Params, x: jax.Array, cfg, *,
                       ssm_state: jax.Array, conv_tail: Dict[str, jax.Array]):
    """Single-token recurrent update.  x [B,1,d]."""
    B_, _, d = x.shape
    di, H = cfg.ssm_d_inner, cfg.ssm_heads
    N, G, P = cfg.ssm_state, cfg.ssm_groups, cfg.ssm_head_dim
    z = x @ p["w_z"].astype(x.dtype)
    dt_raw = x @ p["w_dt"].astype(x.dtype)
    xin, tail_x = _conv_decode(p["conv_x"], p["conv_x_bias"], conv_tail["x"],
                               x @ p["w_x"].astype(x.dtype))
    Bc, tail_B = _conv_decode(p["conv_B"], p["conv_B_bias"], conv_tail["B"],
                              x @ p["w_B"].astype(x.dtype))
    Cc, tail_C = _conv_decode(p["conv_C"], p["conv_C_bias"], conv_tail["C"],
                              x @ p["w_C"].astype(x.dtype))
    new_tail = {"x": tail_x, "B": tail_B, "C": tail_C}

    xh = xin.reshape(B_, H, P)
    dt = jax.nn.softplus(dt_raw[:, 0].astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    Bm = Bc.reshape(B_, G, N)[:, 0]
    Cm = Cc.reshape(B_, G, N)[:, 0]
    dA = jnp.exp(dt * A)                                    # [B,H]
    dBx = jnp.einsum("bhp,bn,bh->bhpn", xh.astype(jnp.float32),
                     Bm.astype(jnp.float32), dt)
    hnew = ssm_state.astype(jnp.float32) * dA[..., None, None] + dBx
    y = jnp.einsum("bhpn,bn->bhp", hnew, Cm.astype(jnp.float32))
    y = y + xh.astype(jnp.float32) * p["D"][None, :, None]
    y = y.reshape(B_, 1, di).astype(x.dtype)
    y = rms_norm(p["norm"], y * jax.nn.silu(z), cfg.norm_eps)
    out = y @ p["out_proj"].astype(x.dtype)
    return out, hnew, new_tail


# ---------------------------------------------------------------------------
# embedding / unembedding / loss
# ---------------------------------------------------------------------------

def init_embed(key, V: int, d: int) -> Params:
    return {"table": embed_init(key, (V, d))}


@jax.named_scope("embed")
def embed(p: Params, tokens: jax.Array, dtype) -> jax.Array:
    return jnp.take(p["table"], tokens, axis=0).astype(dtype)


@jax.named_scope("unembed")
def unembed(table: jax.Array, x: jax.Array, dtype) -> jax.Array:
    return (x @ table.T.astype(x.dtype)).astype(dtype)


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  mask: Optional[jax.Array] = None) -> jax.Array:
    """Mean token NLL.  logits [B,S,V] (any float dtype), labels [B,S]."""
    lf = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


@jax.named_scope("loss")
def chunked_loss(table: jax.Array, x: jax.Array, labels: jax.Array,
                 chunk: int, logits_dtype) -> jax.Array:
    """Cross-entropy without materializing [B,S,V]: scan over S chunks.

    The memory lever for vocab≈150k at long sequence (see §Perf).
    """
    B, S, d = x.shape
    if chunk <= 0 or S <= chunk:
        return cross_entropy(unembed(table, x, logits_dtype), labels)
    assert S % chunk == 0
    nc = S // chunk
    xs = x.reshape(B, nc, chunk, d).swapaxes(0, 1)
    ls = labels.reshape(B, nc, chunk).swapaxes(0, 1)

    def body(tot, inp):
        xc, lc = inp
        logits = unembed(table, xc, logits_dtype)
        lf = logits.astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(lf, axis=-1)
        gold = jnp.take_along_axis(lf, lc[..., None], axis=-1)[..., 0]
        return tot + jnp.sum(lse - gold), None

    tot, _ = lax.scan(body, jnp.zeros((), jnp.float32), (xs, ls))
    return tot / (B * S)
