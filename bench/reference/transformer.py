"""Plain float32 reference of a decoder-only transformer with grouped-query
attention, rotary positions, RMSNorm and a SwiGLU MLP (InternLM2,
arXiv:2403.17297), read from the configuration's published keys, and of
its AdamW training steps.

Departures from the published model, shared with the program: weights
are random from the seed, and the rotary embedding rotates the two
halves of each head (the Hugging Face ``rotate_half`` layout).

Weights are drawn from the seed with the same keys, shapes and scales as
the program's initializer, so program and reference hold the same
numbers without sharing code or arrays.  Serving is scored layer by
layer, one layer's weights at a time.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench.reference import adamw
from bench.reference.common import (Frozen, leaf_norms, matmul, rms_norm,
                                    seed_key)

#: Query positions per block of attention scores.
_Q_BLOCK = 512
#: Positions per checkpointed chunk of the loss.
_LOSS_CHUNK = 1024


def sizes(cfg: Dict) -> Dict[str, int]:
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"d": d, "H": H, "K": cfg["num_key_value_heads"],
            "hd": cfg.get("head_dim") or d // H,
            "ff": cfg["intermediate_size"], "V": cfg["vocab_size"],
            "L": cfg["num_hidden_layers"]}


def _normal(key, shape, scale):
    return jax.random.normal(key, shape, jnp.float32) * scale


def _layer(s: Dict[str, int], key) -> Dict:
    """One layer's weights in the program's layout."""
    d, H, K, hd, ff = s["d"], s["H"], s["K"], s["hd"], s["ff"]
    k1, k2 = jax.random.split(key)
    ka, km = jax.random.split(k1, 4), jax.random.split(k2, 3)
    r = 1 / math.sqrt(d)
    return {"ln1": {"scale": jnp.ones((d,), jnp.float32)},
            "ln2": {"scale": jnp.ones((d,), jnp.float32)},
            "attn": {"wq": _normal(ka[0], (d, H * hd), r),
                     "wk": _normal(ka[1], (d, K * hd), r),
                     "wv": _normal(ka[2], (d, K * hd), r),
                     "wo": _normal(ka[3], (H * hd, d), 1 / math.sqrt(H * hd))},
            "mlp": {"w_up": _normal(km[0], (d, ff), r),
                    "w_down": _normal(km[1], (ff, d), 1 / math.sqrt(ff)),
                    "w_gate": _normal(km[2], (d, ff), r)}}


@functools.partial(jax.jit, static_argnums=(0,))
def _layer_weights(cfg: Frozen, key, i) -> Dict:
    """Layer ``i``'s weights; ``i`` is traced, so one program serves
    every layer."""
    s = sizes(cfg)
    return _layer(s, jax.random.split(key, s["L"] + 2)[i])


def _tables(cfg: Dict, keys) -> Dict:
    s = sizes(cfg)
    p = {"embed": {"table": _normal(keys[-1], (s["V"], s["d"]), 0.02)}}
    if not cfg.get("tie_word_embeddings", False):
        p["unembed"] = {"table": _normal(keys[-2], (s["V"], s["d"]), 0.02)}
    return p


@functools.partial(jax.jit, static_argnums=(0,))
def _table_weights(cfg: Frozen, key) -> Dict:
    return _tables(cfg, jax.random.split(key, sizes(cfg)["L"] + 2))


@functools.partial(jax.jit, static_argnums=(0,))
def init(cfg: Frozen, key) -> Dict:
    """All weights in the program's layout, layers stacked on a leading
    axis."""
    s = sizes(cfg)
    keys = jax.random.split(key, s["L"] + 2)
    layers = [_layer(s, keys[i]) for i in range(s["L"])]
    return {**_tables(cfg, keys),
            "blocks": jax.tree_util.tree_map(lambda *a: jnp.stack(a),
                                             *layers),
            "final_norm": {"scale": jnp.ones((s["d"],), jnp.float32)}}


def unembed_table(p: Dict) -> jax.Array:
    return p.get("unembed", p["embed"])["table"]


def rope(x: jax.Array, theta: float) -> jax.Array:
    """Rotary positions 0..S-1 on ``x [B, S, heads, hd]``."""
    S, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd))
    ang = np.arange(S, dtype=np.float32)[:, None] * freqs[None, :]
    cos = jnp.asarray(np.cos(ang))[None, :, None, :]
    sin = jnp.asarray(np.sin(ang))[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q, k, v):
    """Causal softmax attention.  q [B, S, K, G, hd] (query head h is kv
    head h // G); k, v [B, S, K, hd].  Scores are formed a block of
    queries at a time."""
    S, hd = q.shape[1], q.shape[-1]
    out = []
    for a in range(0, S, _Q_BLOCK):
        qb = q[:, a:a + _Q_BLOCK]
        s = jnp.einsum("bqkgd,bskd->bkgqs", qb, k,
                       precision="highest") / math.sqrt(hd)
        rows = np.arange(a, a + qb.shape[1])[:, None]
        s = jnp.where(rows >= np.arange(S)[None, :], s, -jnp.inf)
        out.append(jnp.einsum("bkgqs,bskd->bqkgd", jax.nn.softmax(s, -1), v,
                              precision="highest"))
    return jnp.concatenate(out, 1)


def block(cfg: Dict, w: Dict, x: jax.Array, precision: str) -> jax.Array:
    """One layer on ``x [B, S, d]``, causal over S."""
    s = sizes(cfg)
    B, S, _ = x.shape
    H, K, hd = s["H"], s["K"], s["hd"]
    eps = cfg["rms_norm_eps"]
    mm = functools.partial(matmul, precision=precision)
    a, m = w["attn"], w["mlp"]
    h = rms_norm(x, w["ln1"]["scale"], eps)
    q = rope(mm(h, a["wq"]).reshape(B, S, H, hd), cfg["rope_theta"])
    k = rope(mm(h, a["wk"]).reshape(B, S, K, hd), cfg["rope_theta"])
    v = mm(h, a["wv"]).reshape(B, S, K, hd)
    o = attention(q.reshape(B, S, K, H // K, hd), k, v)
    x = x + mm(o.reshape(B, S, H * hd), a["wo"])
    h = rms_norm(x, w["ln2"]["scale"], eps)
    return x + mm(jax.nn.silu(mm(h, m["w_gate"])) * mm(h, m["w_up"]),
                  m["w_down"])


_block = jax.jit(block, static_argnums=(0, 3))


# -- training ----------------------------------------------------------------

def loss(cfg: Dict, params: Dict, tokens: jax.Array, labels: jax.Array,
         precision: str) -> jax.Array:
    """Mean next-token cross-entropy over every position of the batch."""
    x = jnp.take(params["embed"]["table"], tokens, axis=0)
    layer = jax.checkpoint(lambda x, w: (block(cfg, w, x, precision), None))
    x, _ = lax.scan(layer, x, params["blocks"])
    x = rms_norm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
    b, S, d = x.shape
    c = math.gcd(S, _LOSS_CHUNK)
    table = unembed_table(params)

    @jax.checkpoint
    def chunk(tot, inp):
        xc, lc = inp
        logits = matmul(xc, table.T, precision)
        gold = jnp.take_along_axis(logits, lc[..., None], -1)[..., 0]
        return tot + jnp.sum(jax.nn.logsumexp(logits, -1) - gold), None

    xs = jnp.moveaxis(x.reshape(b, S // c, c, d), 1, 0)
    ls = jnp.moveaxis(labels.reshape(b, S // c, c), 1, 0)
    tot, _ = lax.scan(chunk, jnp.zeros((), jnp.float32), (xs, ls))
    return tot / (b * S)


@functools.partial(jax.jit, static_argnums=(0, 1, 5), donate_argnums=(2,))
def _step(cfg, opt, state, tokens, labels, precision):
    value, grads = jax.value_and_grad(
        lambda p: loss(cfg, p, tokens, labels, precision))(state["params"])
    grads, _ = adamw.clip_by_global_norm(grads, opt["grad_clip"])
    return adamw.update(opt, grads, state), value, leaf_norms(grads)


@functools.partial(jax.jit, static_argnums=(0,))
def _change(cfg, params, key):
    return leaf_norms(jax.tree_util.tree_map(lambda a, b: a - b, params,
                                             init(cfg, key)))


def train_readings(cfg: Dict, opt: Dict, seed: int,
                   batches: Sequence[Dict], precision: str = "f32") -> Dict:
    """The reference's first steps from the seed's weights: each step's
    loss, the norm of each leaf of the first (clipped) gradient, and the
    norm of each leaf's change over all the steps."""
    cfg, opt = Frozen(cfg), Frozen(opt)
    key = seed_key(seed)
    state = adamw.init(init(cfg, key))
    losses: List[float] = []
    grad = None
    for batch in batches:
        state, value, gnorms = _step(cfg, opt, state,
                                     jnp.asarray(batch["tokens"]),
                                     jnp.asarray(batch["labels"]), precision)
        losses.append(float(value))
        if grad is None:
            grad = {k: float(v) for k, v in gnorms.items()}
    change = {k: float(v) for k, v in
              _change(cfg, state["params"], key).items()}
    return {"loss": losses, "grad": grad, "change": change}


# -- serving -----------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(2,))
def _gaps(h, served, eps, unembed, ctl=None):
    """Per row: how far the served token's reference logit lies below the
    reference's best; and, given control rows, how far the control's
    first choice lies below it."""
    ref = jnp.matmul(rms_norm(h, 1.0, eps), unembed.T, precision="highest")
    best = jnp.max(ref, -1)
    gap = best - jnp.take_along_axis(ref, served[:, None], -1)[:, 0]
    if ctl is None:
        return gap, gap
    lc = matmul(rms_norm(ctl, 1.0, eps), unembed.T, "fp8")
    pick = jnp.argmax(lc, -1)
    return gap, best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]


def served_gaps(cfg: Dict, seed: int,
                served: Sequence[Tuple[Sequence[int], Sequence[int]]],
                control: bool = False, rows: int = 512
                ) -> Tuple[List[float], List[float]]:
    """For each ``(prompt, output)`` that was served greedily: the largest
    gap, over its output tokens, between the reference's best logit and
    the logit of the token served (and, with ``control``, of the token
    that the fp8 control would put first at the same position).

    Every sequence is the prompt plus all output tokens but the last;
    output token j is predicted at position len(prompt) - 1 + j.  The
    final norm's scale is one, as initialized.
    """
    cfg = Frozen(cfg)
    key = seed_key(seed)
    seqs = [list(p) + list(o)[:-1] for p, o in served]
    S = -(-max(len(s) for s in seqs) // 256) * 256   # few distinct shapes
    toks = np.zeros((len(seqs), S), np.int32)
    for r, s in enumerate(seqs):
        toks[r, :len(s)] = s
    tables = _table_weights(cfg, key)
    x = jnp.take(tables["embed"]["table"], jnp.asarray(toks), axis=0)
    unembed = unembed_table(tables)
    del tables
    xc = x if control else None
    for i in range(sizes(cfg)["L"]):
        w = _layer_weights(cfg, key, i)
        x = _block(cfg, w, x, "f32")
        if control:
            xc = _block(cfg, w, xc, "fp8")
        del w
    ref_gap, ctl_gap = [], []
    for r, (p, o) in enumerate(served):
        pos = np.arange(len(p) - 1, len(p) - 1 + len(o))
        g_ref, g_ctl = [], []
        for a in range(0, len(pos), rows):
            sl = pos[a:a + rows]
            tok = jnp.asarray(np.asarray(o[a:a + rows], np.int32))
            g, c = _gaps(x[r, sl], tok, cfg["rms_norm_eps"], unembed,
                         xc[r, sl] if control else None)
            g_ref.append(np.asarray(g))
            g_ctl.append(np.asarray(c))
        ref_gap.append(float(np.max(np.concatenate(g_ref))))
        ctl_gap.append(float(np.max(np.concatenate(g_ctl))))
    return ref_gap, ctl_gap
