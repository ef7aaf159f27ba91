"""Model FLOPs of one training step of the dense decoder, from its sizes
(``reference.train.dims``).

Counted as the algorithm needs them, after ``flops.py``: the forward pass
and a backward pass of twice its cost, two FLOPs per multiply-add, causal
attention over the keys each token sees, no padding and no recomputation
(remat's second forward is not model work).  The embedding is a gather
and costs none.
"""
from __future__ import annotations

from bench import flops

#: the device program of a training step, as jax names it: the replicated
#: step (``TransparentTrainer._local_step``), the one the cells run
STEP_PROGRAM = r"jit__local_step"


def sequence(k: dict, n: int) -> float:
    """Forward and backward FLOPs of one causal n-token sequence with a
    loss at every position: 6 x the matrix-product parameters per token
    plus the causal scores and values."""
    forward = n * (k["L"] * flops.layer_matmul(k) + flops.head(k)) \
        + k["L"] * flops.attention(k, 1.0) * n * (n + 1) / 2
    return 3.0 * forward


def step(k: dict, seq_len: int, global_batch: int) -> float:
    """FLOPs of one data-parallel step over the whole global batch."""
    return global_batch * sequence(k, seq_len)
