"""Broadcast operator — the paper's §III-D.1.

MaTEx-TensorFlow guarantees every replica starts from *identical* variables
by broadcasting rank 0's initial model.  The TF scheduler is unordered, so
the paper adds explicit data dependencies to match broadcast buffers; under
JAX/SPMD the dataflow graph provides that ordering for free, and the
broadcast itself is expressed as a masked psum: only the replica at
coordinate 0 along each DP axis contributes, everyone receives the sum.

This is not redundant with same-seed initialization: it makes replica
consistency *unconditional* (e.g. non-deterministic per-host init, restored
checkpoints with host-local corruption, or elastic re-join of a fresh
replica — §checkpoint.elastic).
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp


def _is_rank_zero(axes: Sequence[str]):
    flag = jnp.ones((), jnp.bool_)
    for a in axes:
        flag &= jax.lax.axis_index(a) == 0
    return flag


@jax.named_scope("broadcast")
def broadcast_from_rank0(tree, axes: Sequence[str]):
    """Inside a shard_map manual region: replace every leaf with rank 0's
    (masked psum: only rank 0 contributes, everyone receives the sum)."""
    if not axes:
        return tree
    mask = _is_rank_zero(axes)

    def one(x):
        contrib = jnp.where(mask, x.astype(jnp.float32), 0.0)
        total = jax.lax.psum(contrib, tuple(axes))
        return total.astype(x.dtype)

    return jax.tree.map(one, tree)


def replicas_identical(tree, axes: Sequence[str]):
    """Consistency check: max |x - rank0(x)| over all leaves (0.0 == equal)."""
    if not axes:
        return jnp.zeros((), jnp.float32)
    ref = broadcast_from_rank0(tree, axes)
    diffs = jax.tree.map(
        lambda a, b: jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))),
        tree, ref)
    return jax.tree.reduce(jnp.maximum, diffs, jnp.zeros((), jnp.float32))
