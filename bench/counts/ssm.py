"""Operations and bytes a Mamba-2 language model needs, from the
configuration's published keys.

Counted is what the algorithm needs: a multiply-add is two operations;
the SSD scan's chunked form counts the causal half of each chunk's
position pairs and no more; the backward pass is twice the forward;
nothing recomputed, padded or masked out counts.  Norms, the
depthwise convolution, gates, exponentials and activations are left out
(about 1% of the operations at these widths).
"""
from __future__ import annotations

from typing import Dict

from bench.reference.ssm import sizes


def matmul_params(cfg: Dict) -> int:
    """Weights that a token passes through in matrix products: the five
    input projections and the output projection of every layer, and the
    output head (the embedding lookup not)."""
    s = sizes(cfg)
    d, di, GN, H = s["d"], s["di"], s["G"] * s["N"], s["H"]
    layer = d * (2 * di + 2 * GN + H) + di * d
    return s["L"] * layer + s["V"] * d


def ssd_forward_ops(cfg: Dict, seq_len: int, chunk: int) -> float:
    """One layer's SSD scan, forward, over one sequence of ``seq_len`` in
    chunks of ``chunk`` positions: C·B over the causal pairs of each chunk
    (shared by the heads of a group), those pairs' weighted sum of x in
    every head, each chunk's state (x ⊗ B summed over its positions), the
    state passed from chunk to chunk, the state read out by C at every
    position, and the skip D·x."""
    s = sizes(cfg)
    H, P, N, G = s["H"], s["P"], s["N"], s["G"]
    Q = min(chunk, seq_len)
    nc = -(-seq_len // Q)
    pairs = nc * Q * (Q + 1) / 2
    return (pairs * (2 * N * G + 2 * H * P)
            + seq_len * H * 2 * P * N          # chunk states
            + nc * H * 2 * P * N               # state pass
            + seq_len * H * 2 * P * N          # inter-chunk read-out
            + seq_len * H * 2 * P)             # skip


def ssd_ops(cfg: Dict, seq_len: int, chunk: int) -> float:
    """Forward and backward of one sequence's SSD scan in every layer."""
    return 3 * sizes(cfg)["L"] * ssd_forward_ops(cfg, seq_len, chunk)


def ssd_bytes(cfg: Dict, seq_len: int) -> float:
    """Bytes of one sequence's SSD scan in every layer, forward and
    backward: its inputs x (bfloat16), dt (float32), B and C (bfloat16)
    read once and y (bfloat16) written once forward; backward reads them
    and dy and writes a gradient of each input."""
    s = sizes(cfg)
    x = seq_len * s["H"] * s["P"] * 2
    dt = seq_len * s["H"] * 4
    bc = 2 * seq_len * s["G"] * s["N"] * 2
    forward = x + dt + bc + x
    backward = (x + dt + bc + x) + (x + dt + bc)
    return s["L"] * (forward + backward)


def train_ops_per_token(cfg: Dict, seq_len: int) -> float:
    """Forward and backward (three times the forward) per token of a
    sequence of ``seq_len``: the matrix products, and the SSD scan in the
    published module's chunks (``chunk_size``), whatever chunk the
    program runs, so that the yardstick does not move with its setting."""
    s = sizes(cfg)
    chunk = cfg["mamba2"]["chunk_size"]
    ssd = s["L"] * ssd_forward_ops(cfg, seq_len, chunk) / seq_len
    return 3 * (2 * matmul_params(cfg) + ssd)
