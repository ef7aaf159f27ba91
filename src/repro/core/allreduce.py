"""Gradient reduction strategies — the JAX/TPU mapping of the paper's
MPI_Allreduce operator (§III-D.2).

All strategies are *mathematically identical* (mean over DP replicas); they
differ in collective granularity and schedule, which is what the paper's
"layer-wise ordered all-to-all reduction" is about:

  fused          one flat psum (minimum latency-overhead count)
  layerwise      one psum per parameter tensor, ordered back-to-front —
                 the paper's design; allows overlap with remaining backprop
  bucketed       layerwise coalesced into ~bucket_bytes buckets
  hierarchical   psum over intra-pod "data" axis, then inter-pod "pod" axis
                 (topology-aware; TPU ICI vs cross-pod DCI)
  compressed     bf16 wire format + fp32 error-feedback (beyond-paper)

The ZeRO-1 ``reduce_scatter`` strategy lives in transparent.py because it
fuses with the optimizer update (allreduce ≡ reduce-scatter + all-gather
with the update between the halves).

These run inside a shard_map manual region over ``axes``; gradients are
fp32 trees.
"""
from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _pmean(x, axes):
    if not axes:
        return x
    return jax.lax.pmean(x, tuple(axes))


# ---------------------------------------------------------------------------
# Strategies
# ---------------------------------------------------------------------------

def fused_allreduce(grads, axes: Sequence[str]):
    """Single collective over one concatenated fp32 vector."""
    leaves, treedef = jax.tree.flatten(grads)
    sizes = [l.size for l in leaves]
    flat = jnp.concatenate([l.astype(jnp.float32).reshape(-1) for l in leaves])
    flat = _pmean(flat, axes)
    out, off = [], 0
    for l, n in zip(leaves, sizes):
        out.append(flat[off:off + n].reshape(l.shape))
        off += n
    return jax.tree.unflatten(treedef, out)


def layerwise_allreduce(grads, axes: Sequence[str], reverse: bool = True):
    """One psum per tensor, emitted in reverse tree order (gradients become
    available back-to-front during backprop — the paper's ordered list)."""
    leaves, treedef = jax.tree.flatten(grads)
    order = range(len(leaves) - 1, -1, -1) if reverse else range(len(leaves))
    reduced = [None] * len(leaves)
    for i in order:
        reduced[i] = _pmean(leaves[i].astype(jnp.float32), axes)
    return jax.tree.unflatten(treedef, reduced)


def bucketed_allreduce(grads, axes: Sequence[str], bucket_bytes: int):
    """Coalesce tensors (in reverse order) into ~bucket_bytes fp32 buckets."""
    leaves, treedef = jax.tree.flatten(grads)
    idx = list(range(len(leaves) - 1, -1, -1))
    buckets, cur, cur_bytes = [], [], 0
    for i in idx:
        n = leaves[i].size * 4
        if cur and cur_bytes + n > bucket_bytes:
            buckets.append(cur)
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_bytes += n
    if cur:
        buckets.append(cur)
    reduced = [None] * len(leaves)
    for bucket in buckets:
        flat = jnp.concatenate(
            [leaves[i].astype(jnp.float32).reshape(-1) for i in bucket])
        flat = _pmean(flat, axes)
        off = 0
        for i in bucket:
            n = leaves[i].size
            reduced[i] = flat[off:off + n].reshape(leaves[i].shape)
            off += n
    return jax.tree.unflatten(treedef, reduced)


def hierarchical_allreduce(grads, axes: Sequence[str]):
    """Reduce over the fast intra-pod axis first, then across pods.

    On a ("pod","data") manual region this lowers to two collectives whose
    communicators match the physical topology — the MPI analogue is a
    node-local reduce followed by an inter-node allreduce."""
    inner = [a for a in axes if a != "pod"]
    outer = [a for a in axes if a == "pod"]
    out = grads
    if inner:
        out = jax.tree.map(lambda g: _pmean(g.astype(jnp.float32), inner), out)
    if outer:
        out = jax.tree.map(lambda g: _pmean(g, outer), out)
    return out


def compressed_allreduce(grads, err, axes: Sequence[str]):
    """bf16 wire format with fp32 error feedback (beyond-paper).

    err: fp32 tree of residuals from previous steps (same structure).
    Returns (reduced fp32 grads, new err tree)."""
    # XLA:CPU check-fails on bf16 all-reduce inside partial-manual regions;
    # on CPU we keep the bf16 *quantization* (the dominant error term) but
    # use an fp32 wire so tests/dry-runs compile.  Roofline corrects the
    # wire bytes by /2 for this strategy (see roofline/analysis.py).
    cpu = jax.default_backend() == "cpu"

    def one(g, e):
        g32 = g.astype(jnp.float32) + e
        wire = g32.astype(jnp.bfloat16)
        new_e = g32 - wire.astype(jnp.float32)
        if cpu:
            red = _pmean(wire.astype(jnp.float32), axes)
        else:
            red = _pmean(wire, axes).astype(jnp.float32)
        return red, new_e

    pairs = jax.tree.map(one, grads, err)
    red = jax.tree.map(lambda p: p[0], pairs, is_leaf=lambda x: isinstance(x, tuple))
    new_err = jax.tree.map(lambda p: p[1], pairs, is_leaf=lambda x: isinstance(x, tuple))
    return red, new_err


# ---------------------------------------------------------------------------
# Dispatcher
# ---------------------------------------------------------------------------

@jax.named_scope("allreduce")
def reduce_gradients(grads, strategy: str, axes: Sequence[str],
                     bucket_bytes: int = 32 << 20, err=None):
    """Apply a reduction strategy; returns (grads, new_err_or_None)."""
    if not axes:
        return jax.tree.map(lambda g: g.astype(jnp.float32), grads), err
    if strategy == "fused":
        return fused_allreduce(grads, axes), err
    if strategy == "layerwise":
        return layerwise_allreduce(grads, axes), err
    if strategy == "bucketed":
        return bucketed_allreduce(grads, axes, bucket_bytes), err
    if strategy == "hierarchical":
        return hierarchical_allreduce(grads, axes), err
    if strategy == "compressed":
        assert err is not None, "compressed strategy needs an error-feedback tree"
        return compressed_allreduce(grads, err, axes)
    raise ValueError(f"unknown strategy {strategy!r}")


def init_error_tree(params):
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
