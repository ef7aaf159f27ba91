"""Flash attention TPU kernel (pl.pallas_call + BlockSpec VMEM tiling).

TPU adaptation of the (GPU-origin) FlashAttention schedule: instead of a
warp-level softmax pipeline, blocks are sized for the MXU (128-aligned
q/k tiles), the online-softmax state (m, l, acc) lives in VMEM scratch and
persists across the *sequential* kv-block grid dimension, and causal /
sliding-window skipping is grid-level (``pl.when``) so skipped tiles cost
no MXU cycles.

Layouts: q [BH, Sq, hd] (heads flattened into the grid), k/v [BKV, Sk, hd];
GQA is handled in the BlockSpec index maps (kv row = q row // group).

Grid: (BH, nq, nk) with nk sequential ("arbitrary") — m/l/acc scratch carry.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.common import NEG_INF


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: int,
                  block_q: int, block_k: int, nk: int, seq_k: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * block_q
    k_start = ik * block_k

    # grid-level skip: tile entirely above the causal diagonal or entirely
    # outside the sliding window
    run = True
    if causal:
        run = k_start <= q_start + block_q - 1
    if window:
        run = jnp.logical_and(run,
                              k_start + block_k - 1 > q_start - window)

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                 # [bq, hd]
        k = k_ref[0].astype(jnp.float32)                 # [bk, hd]
        v = v_ref[0].astype(jnp.float32)                 # [bk, hd]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk]

        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = k_pos < seq_k
        if causal:
            mask &= k_pos <= q_pos
        if window:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                            # [bq, bk]
        l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ik == nk - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_bhsd(q, k, v, *, causal: bool = True, window: int = 0,
                         block_q: int = 128, block_k: int = 128,
                         interpret: bool = False):
    """q: [BH, Sq, hd]; k/v: [BKV, Sk, hd]; BH % BKV == 0 (GQA groups).

    Returns [BH, Sq, hd] in q.dtype.
    """
    BH, Sq, hd = q.shape
    BKV, Sk, _ = k.shape
    assert BH % BKV == 0, (BH, BKV)
    G = BH // BKV
    scale = hd ** -0.5
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    nq = pl.cdiv(Sq, block_q)
    nk = pl.cdiv(Sk, block_k)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, nk=nk, seq_k=Sk)

    return pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, block_k, hd), lambda bh, iq, ik: (bh // G, ik, 0)),
            pl.BlockSpec((1, block_k, hd), lambda bh, iq, ik: (bh // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # m
            pltpu.VMEM((block_q, 1), jnp.float32),   # l
            pltpu.VMEM((block_q, hd), jnp.float32),  # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
