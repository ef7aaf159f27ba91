"""Model FLOPs of the dense decoder, from its sizes (``dense.dims``).

Counted as the algorithm needs them: two FLOPs per multiply-add, no
padding, no recomputation.
"""
from __future__ import annotations


def layer_matmul(k: dict) -> float:
    """Matrix-product FLOPs per token of one decoder block."""
    D, H, KV, hd, F = k["D"], k["H"], k["KV"], k["hd"], k["F"]
    return 2.0 * (D * H * hd + 2 * D * KV * hd + H * hd * D + 3 * D * F)


def attention(k: dict, context: float) -> float:
    """Score and value FLOPs per token of one block at ``context`` keys."""
    return 4.0 * k["H"] * k["hd"] * context


def head(k: dict) -> float:
    return 2.0 * k["D"] * k["V"]


def decode_token(k: dict, context: float) -> float:
    """Forward FLOPs of one decoded token attending ``context`` keys."""
    return k["L"] * (layer_matmul(k) + attention(k, context)) + head(k)


def prefill(k: dict, n: int) -> float:
    """Forward FLOPs of an n-token prompt (causal), logits for its last
    token only."""
    return k["L"] * (n * layer_matmul(k)
                     + attention(k, 1.0) * n * (n + 1) / 2) + head(k)

