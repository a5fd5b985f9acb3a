"""Plain float32 reference of a Mamba-2 language model (Dao & Gu,
"Transformers are SSMs", arXiv:2405.21060, and the published ``Mamba2``
module), read from the configuration's published keys, and of its AdamW
training steps.

A layer is ``x + mixer(rms_norm(x))``; the mixer, on the normed input u:

    z, x, B, C, dt = u W_z, u W_x, u W_B, u W_C, u W_dt
    x, B, C        = silu(causal depthwise conv(x, B, C) + conv bias)
    dt             = softplus(dt + dt_bias);  A = -exp(A_log)
    h_t            = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T    (per head)
    y_t            = h_t C_t + D x_t
    out            = rms_norm(y * silu(z)) W_out

The state-space part runs as that recurrence, one position after another
(in checkpointed segments of positions, so that its gradient fits), and
so shares nothing with the program's chunked form.  Upstream's fused
``in_proj`` is split into its five parts, as the program stores them.

Departures from the published model, shared with the program: weights
are random from the seed, drawn with the program initializer's keys,
shapes and scales (A_log, dt_bias, D and the conv bias as the published
module draws them).  The residual stream is float32 here, as published
(``residual_in_fp32``); the program keeps it in bfloat16, and the
comparison's limits take that in.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench.reference import adamw
from bench.reference.common import (HIGHEST, Frozen, leaf_norms, matmul,
                                    rms_norm, seed_key)

#: Positions per checkpointed segment of the recurrence.
_SEGMENT = 64
#: Positions per checkpointed chunk of the loss.
_LOSS_CHUNK = 1024


def sizes(cfg: Dict) -> Dict[str, int]:
    m = cfg["mamba2"]
    d = cfg["d_model"]
    di = m["expand"] * d
    pad = cfg.get("pad_vocab_size_multiple", 1)
    return {"d": d, "di": di, "P": m["headdim"], "H": di // m["headdim"],
            "N": m["d_state"], "G": m["ngroups"], "K": m["d_conv"],
            "V": -(-cfg["vocab_size"] // pad) * pad, "L": cfg["n_layer"]}


def _normal(key, shape, scale):
    return jax.random.normal(key, shape, jnp.float32) * scale


def _layer(cfg: Dict, key) -> Dict:
    """One layer's weights in the program's layout."""
    s, m = sizes(cfg), cfg["mamba2"]
    d, di, H, GN, K = s["d"], s["di"], s["H"], s["G"] * s["N"], s["K"]
    ks = jax.random.split(key, 14)
    A = jax.random.uniform(ks[12], (H,), jnp.float32, *m["A_init_range"])
    dt = jnp.exp(jax.random.uniform(ks[13], (H,), jnp.float32,
                                    math.log(m["dt_min"]),
                                    math.log(m["dt_max"])))
    dt = jnp.maximum(dt, m["dt_init_floor"])
    bound = 1 / math.sqrt(K)

    def bias(k, n):
        return jax.random.uniform(k, (n,), jnp.float32, -bound, bound)

    r = 1 / math.sqrt(d)
    return {"ln": {"scale": jnp.ones((d,), jnp.float32)},
            "mamba": {
                "w_z": _normal(ks[0], (d, di), r),
                "w_x": _normal(ks[1], (d, di), r),
                "w_B": _normal(ks[2], (d, GN), r),
                "w_C": _normal(ks[3], (d, GN), r),
                "w_dt": _normal(ks[4], (d, H), r),
                "conv_x": _normal(ks[5], (K, di), 0.5),
                "conv_B": _normal(ks[6], (K, GN), 0.5),
                "conv_C": _normal(ks[7], (K, GN), 0.5),
                "conv_x_bias": bias(ks[9], di),
                "conv_B_bias": bias(ks[10], GN),
                "conv_C_bias": bias(ks[11], GN),
                "A_log": jnp.log(A),
                "D": jnp.ones((H,), jnp.float32),
                # softplus(dt_bias) = dt
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "norm": {"scale": jnp.ones((di,), jnp.float32)},
                "out_proj": _normal(ks[8], (di, d), 1 / math.sqrt(di))}}


@functools.partial(jax.jit, static_argnums=(0,))
def init(cfg: Frozen, key) -> Dict:
    """All weights in the program's layout, layers stacked on a leading
    axis."""
    s = sizes(cfg)
    keys = jax.random.split(key, s["L"] + 2)
    layers = [_layer(cfg, keys[i]) for i in range(s["L"])]
    p = {"embed": {"table": _normal(keys[-1], (s["V"], s["d"]), 0.02)},
         "blocks": jax.tree_util.tree_map(lambda *a: jnp.stack(a), *layers),
         "final_norm": {"scale": jnp.ones((s["d"],), jnp.float32)}}
    if not cfg["tie_embeddings"]:
        p["unembed"] = {"table": _normal(keys[-2], (s["V"], s["d"]), 0.02)}
    return p


def unembed_table(p: Dict) -> jax.Array:
    return p.get("unembed", p["embed"])["table"]


def conv(x: jax.Array, w: jax.Array, bias: jax.Array) -> jax.Array:
    """silu(causal depthwise convolution of ``x [B, S, C]`` with
    ``w [K, C]`` + ``bias``); position t sees positions t-K+1..t."""
    K, C = w.shape
    out = lax.conv_general_dilated(
        x, w[:, None, :], window_strides=(1,), padding=[(K - 1, 0)],
        dimension_numbers=("NWC", "WIO", "NWC"), feature_group_count=C,
        precision=HIGHEST)
    return jax.nn.silu(out + bias)


def ssm(x, dt, A, B, C):
    """The recurrence over positions: x [b, S, H, P], dt [b, S, H],
    A [H], B and C [b, S, N] (one group); returns sum_n h_t C_t as
    [b, S, H, P], from a zero state."""
    b, S, H, P = x.shape
    N = B.shape[-1]
    decay = jnp.exp(dt * A)                             # [b, S, H]
    u = x * dt[..., None]

    def step(h, inp):
        a, ut, Bt, Ct = inp
        h = h * a[:, :, None, None] + ut[..., None] * Bt[:, None, None, :]
        return h, jnp.einsum("bhpn,bn->bhp", h, Ct, precision=HIGHEST)

    @jax.checkpoint
    def segment(h, inp):
        return lax.scan(step, h, inp, unroll=8)

    T = math.gcd(S, _SEGMENT)

    def segments(a):
        a = jnp.moveaxis(a, 1, 0)
        return a.reshape(S // T, T, *a.shape[1:])

    _, y = lax.scan(segment, jnp.zeros((b, H, P, N), jnp.float32),
                    tuple(map(segments, (decay, u, B, C))))
    return jnp.moveaxis(y.reshape(S, b, H, P), 0, 1)


def mixer(cfg: Dict, w: Dict, u: jax.Array, precision: str) -> jax.Array:
    """The Mamba-2 mixer on the normed input ``u [b, S, d]``."""
    s = sizes(cfg)
    if s["G"] != 1:
        raise ValueError("the reference holds one group of B and C")
    b, S, _ = u.shape
    H, P = s["H"], s["P"]
    mm = functools.partial(matmul, precision=precision)
    z = mm(u, w["w_z"])
    x = conv(mm(u, w["w_x"]), w["conv_x"], w["conv_x_bias"])
    B = conv(mm(u, w["w_B"]), w["conv_B"], w["conv_B_bias"])
    C = conv(mm(u, w["w_C"]), w["conv_C"], w["conv_C_bias"])
    dt = jax.nn.softplus(mm(u, w["w_dt"]) + w["dt_bias"])
    xh = x.reshape(b, S, H, P)
    y = ssm(xh, dt, -jnp.exp(w["A_log"]), B, C) + xh * w["D"][:, None]
    y = rms_norm(y.reshape(b, S, s["di"]) * jax.nn.silu(z),
                 w["norm"]["scale"], cfg["norm_epsilon"])
    return mm(y, w["out_proj"])


def block(cfg: Dict, w: Dict, x: jax.Array, precision: str) -> jax.Array:
    """One layer on ``x [b, S, d]``, causal over S."""
    u = rms_norm(x, w["ln"]["scale"], cfg["norm_epsilon"])
    return x + mixer(cfg, w["mamba"], u, precision)


# -- training ----------------------------------------------------------------

def loss(cfg: Dict, params: Dict, tokens: jax.Array, labels: jax.Array,
         precision: str) -> jax.Array:
    """Mean next-token cross-entropy over every position of the batch,
    over all rows of the (padded) output head."""
    x = jnp.take(params["embed"]["table"], tokens, axis=0)
    layer = jax.checkpoint(lambda x, w: (block(cfg, w, x, precision), None))
    x, _ = lax.scan(layer, x, params["blocks"])
    x = rms_norm(x, params["final_norm"]["scale"], cfg["norm_epsilon"])
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


@functools.partial(jax.jit, static_argnums=(0, 1, 5))
def _grads(cfg, opt, params, tokens, labels, precision):
    value, grads = jax.value_and_grad(
        lambda p: loss(cfg, p, tokens, labels, precision))(params)
    grads, _ = adamw.clip_by_global_norm(grads, opt["grad_clip"])
    return value, grads, leaf_norms(grads)


_update = jax.jit(adamw.update, static_argnums=(0,), donate_argnums=(2,))


@functools.partial(jax.jit, static_argnums=(0,))
def _change(cfg, params, key):
    return leaf_norms(jax.tree_util.tree_map(lambda a, b: a - b, params,
                                             init(cfg, key)))


def train_readings(cfg: Dict, opt: Dict, seed: int,
                   batches: Sequence[Dict], precision: str = "f32") -> Dict:
    """The reference's first steps from the seed's weights: each step's
    loss, the norm of each leaf of the first (clipped) gradient, and the
    norm of each leaf's change over all the steps.

    AdamW's moments wait in host memory while a gradient is taken, so
    that the backward pass of every layer at 2 x 8,192 positions in
    float32 has the chip's memory beside the weights alone."""
    cfg, opt = Frozen(cfg), Frozen(opt)
    key = seed_key(seed)
    params = init(cfg, key)
    m = jax.tree_util.tree_map(lambda a: np.zeros(a.shape, a.dtype), params)
    v = jax.tree_util.tree_map(np.copy, m)
    count = np.zeros((), np.int32)
    losses: List[float] = []
    grad = None
    for batch in batches:
        value, grads, gnorms = _grads(cfg, opt, params,
                                      jnp.asarray(batch["tokens"]),
                                      jnp.asarray(batch["labels"]),
                                      precision)
        state = _update(opt, grads, {"params": params, "m": m, "v": v,
                                     "count": count})
        params = state["params"]
        m, v, count = jax.device_get((state["m"], state["v"],
                                      state["count"]))
        del state
        losses.append(float(value))
        if grad is None:
            grad = {k: float(g) for k, g in gnorms.items()}
    change = {k: float(c) for k, c in _change(cfg, params, key).items()}
    return {"loss": losses, "grad": grad, "change": change}
