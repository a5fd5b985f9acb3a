"""Operations a decoder-only transformer needs, from the
configuration's published keys.

Counted is what the algorithm needs: a multiply-add is two operations;
causal attention attends to the positions at or before each token and
no more; nothing recomputed, padded or masked out counts.  Norms,
rotary embeddings, softmax and activations are left out (under 1% of
the operations at these widths).
"""
from __future__ import annotations

from typing import Dict

from bench.reference.transformer import sizes


def matmul_params(cfg: Dict) -> int:
    """Weights that a token passes through in matrix products, the output
    head included and the embedding lookup not."""
    s = sizes(cfg)
    d, H, K, hd, ff = s["d"], s["H"], s["K"], s["hd"], s["ff"]
    layer = d * H * hd + 2 * d * K * hd + H * hd * d + 3 * d * ff
    return s["L"] * layer + s["V"] * d


def attention_ops(cfg: Dict, context: int) -> int:
    """Scores and weighted values of one query over ``context`` keys, in
    every layer."""
    s = sizes(cfg)
    return 4 * s["L"] * s["H"] * s["hd"] * context


def train_ops_per_token(cfg: Dict, seq_len: int) -> float:
    """Forward and backward (three times the forward) per token of a
    causal sequence of ``seq_len``: the mean context is (seq_len + 1)/2."""
    fwd = 2 * matmul_params(cfg) + attention_ops(cfg, 1) * (seq_len + 1) / 2
    return 3 * fwd


def prefill_ops(cfg: Dict, n: int) -> int:
    """A prompt of ``n`` real tokens: every token through the layers, the
    logits of the last position only."""
    s = sizes(cfg)
    head = s["V"] * s["d"]
    return (2 * (matmul_params(cfg) - head) * n + 2 * head
            + attention_ops(cfg, 1) * n * (n + 1) // 2)

