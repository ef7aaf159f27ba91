"""Attention substrate: GQA (full / sliding-window / local) + DeepSeek MLA.

Layouts: activations ``[batch, seq, d_model]``; heads ``[batch, seq, heads, head_dim]``.

Three execution paths:
  * ``attention_core``      — chunked online-softmax (double lax.scan), bounded
                              memory at 32k+ contexts; the traced default.
  * ``windowed_attention``  — per-q-block dynamic-slice of the KV range for
                              sliding-window/local attention (subquadratic).
  * ``decode_attend``       — single-step decode against a (ring-buffer) cache.

On TPU the Pallas flash kernel (``repro.kernels.flash_attention``) replaces
``attention_core`` when ``use_pallas=True`` (see transformer.py); the functions
here double as its reference semantics.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import ParamSpec, apply_rope, dot
# the single quantizer for int8 KV pages (layouts depends only on jax, so
# this does not cross the serving layer's import boundary)
from repro.serving.layouts import SCALE_SUFFIX, gather_rows, write_rows

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------

def attention_specs(cfg):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    h, kv = cfg.num_heads, cfg.num_kv_heads
    specs = {
        "wq": ParamSpec((d, h * hd), ("embed", "heads")),
        "wk": ParamSpec((d, kv * hd), ("embed", "kv_heads")),
        "wv": ParamSpec((d, kv * hd), ("embed", "kv_heads")),
        "wo": ParamSpec((h * hd, d), ("heads", "embed2")),
    }
    if cfg.qkv_bias:
        specs["bq"] = ParamSpec((h * hd,), ("heads",), init="zeros")
        specs["bk"] = ParamSpec((kv * hd,), ("kv_heads",), init="zeros")
        specs["bv"] = ParamSpec((kv * hd,), ("kv_heads",), init="zeros")
    return specs


def mla_specs(cfg):
    m = cfg.mla
    d, h = cfg.d_model, cfg.num_heads
    qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
    specs = {
        # compressed kv + shared rope key
        "w_dkv": ParamSpec((d, m.kv_lora_rank + m.qk_rope_head_dim), ("embed", "rank")),
        "kv_norm": ParamSpec((m.kv_lora_rank,), ("rank",), init="zeros"),
        "w_uk": ParamSpec((m.kv_lora_rank, h * m.qk_nope_head_dim), ("rank", "heads")),
        "w_uv": ParamSpec((m.kv_lora_rank, h * m.v_head_dim), ("rank", "heads")),
        "wo": ParamSpec((h * m.v_head_dim, d), ("heads", "embed2")),
    }
    if m.q_lora_rank:
        specs["w_dq"] = ParamSpec((d, m.q_lora_rank), ("embed", "rank"))
        specs["q_norm"] = ParamSpec((m.q_lora_rank,), ("rank",), init="zeros")
        specs["w_uq"] = ParamSpec((m.q_lora_rank, h * qk_dim), ("rank", "heads"))
    else:
        specs["wq"] = ParamSpec((d, h * qk_dim), ("embed", "heads"))
    return specs


# ---------------------------------------------------------------------------
# Chunked online-softmax attention (full / causal)
# ---------------------------------------------------------------------------

def _split_heads(x, n, hd):
    return x.reshape(*x.shape[:-1], n, hd)


def _project_qkv_rope(cfg, p, x, positions):
    """Shared GQA front half: q/k/v projections (+bias), head split, rope.

    x [B,S,D] -> q [B,S,H,hd], k/v [B,S,KV,hd] — used by both the slotted
    (``attention_apply``) and paged (``paged_attention_apply``) paths so
    projection changes can never diverge them.
    """
    hd = cfg.resolved_head_dim
    H, KV = cfg.num_heads, cfg.num_kv_heads
    cd = x.dtype
    q = dot(x, p["wq"], cd)
    k = dot(x, p["wk"], cd)
    v = dot(x, p["wv"], cd)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(cd)
        k = k + p["bk"].astype(cd)
        v = v + p["bv"].astype(cd)
    q = _split_heads(q, H, hd)
    k = _split_heads(k, KV, hd)
    v = _split_heads(v, KV, hd)
    q = apply_rope(q, positions, cfg.rope_theta, cfg.rotary_dim)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.rotary_dim)
    return q, k, v


def _gqa_scores(q, k, scale):
    """q: [B,qb,KV,G,hd]  k: [B,kb,KV,hd]  ->  [B,KV,G,qb,kb] (fp32)."""
    return jnp.einsum(
        "bqkgh,btkh->bkgqt", q, k,
        preferred_element_type=jnp.float32) * scale


def attention_core(q, k, v, q_pos, k_pos, *, causal: bool = True,
                   window: int = 0, q_block: int = 512, kv_block: int = 1024,
                   kv_valid: Optional[jax.Array] = None):
    """Memory-bounded attention via double scan with online softmax.

    q: [B,S,H,hd]; k,v: [B,T,KV,hd]; q_pos: [S] or [B,S]; k_pos: [T] or [B,T].
    window>0 additionally masks keys older than ``window`` positions.
    kv_valid: optional [B,T] bool mask of valid cache slots.
    Returns [B,S,H,hd] in q.dtype.
    """
    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    hd_v = v.shape[-1]                   # may differ from hd (MLA)
    G = H // KV
    scale = hd ** -0.5
    qb = min(q_block, S)
    kb = min(kv_block, T)
    # pad to block multiples
    S_p = -(-S // qb) * qb
    T_p = -(-T // kb) * kb
    if q_pos.ndim == 1:
        q_pos = jnp.broadcast_to(q_pos[None, :], (B, S))
    if k_pos.ndim == 1:
        k_pos = jnp.broadcast_to(k_pos[None, :], (B, T))
    qp = jnp.pad(q, ((0, 0), (0, S_p - S), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, T_p - T), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, T_p - T), (0, 0), (0, 0)))
    qpos_p = jnp.pad(q_pos, ((0, 0), (0, S_p - S)), constant_values=-1)
    kpos_p = jnp.pad(k_pos, ((0, 0), (0, T_p - T)), constant_values=2**30)
    valid_p = (jnp.pad(kv_valid, ((0, 0), (0, T_p - T)), constant_values=False)
               if kv_valid is not None
               else jnp.pad(jnp.ones((B, T), bool), ((0, 0), (0, T_p - T)),
                            constant_values=False))

    nq, nk = S_p // qb, T_p // kb
    qp = qp.reshape(B, nq, qb, KV, G, hd).transpose(1, 0, 2, 3, 4, 5)      # [nq,B,qb,KV,G,hd]
    kp = kp.reshape(B, nk, kb, KV, hd).transpose(1, 0, 2, 3, 4)           # [nk,B,kb,KV,hd]
    vp = vp.reshape(B, nk, kb, KV, hd_v).transpose(1, 0, 2, 3, 4)
    qpos_b = qpos_p.reshape(B, nq, qb).transpose(1, 0, 2)                  # [nq,B,qb]
    kpos_b = kpos_p.reshape(B, nk, kb).transpose(1, 0, 2)
    valid_b = valid_p.reshape(B, nk, kb).transpose(1, 0, 2)

    def q_step(_, q_in):
        q_i, qpos_i = q_in                                                # [B,qb,KV,G,hd], [B,qb]

        def kv_step(carry, kv_in):
            m, l, acc = carry
            k_j, v_j, kpos_j, valid_j = kv_in
            s = _gqa_scores(q_i, k_j, scale)                              # [B,KV,G,qb,kb]
            msk = valid_j[:, None, None, None, :]
            if causal:
                msk = msk & (kpos_j[:, None, None, None, :]
                             <= qpos_i[:, None, None, :, None])
            if window:
                msk = msk & (kpos_j[:, None, None, None, :]
                             > qpos_i[:, None, None, :, None] - window)
            s = jnp.where(msk, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            corr = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgqt,btkh->bkgqh", p.astype(v_j.dtype), v_j,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, KV, G, qb), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, qb), jnp.float32)
        a0 = jnp.zeros((B, KV, G, qb, hd_v), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                      (kp, vp, kpos_b, valid_b))
        out = acc / jnp.maximum(l, 1e-30)[..., None]                       # [B,KV,G,qb,hd]
        return None, out.transpose(0, 3, 1, 2, 4)                          # [B,qb,KV,G,hd]

    _, out = jax.lax.scan(q_step, None, (qp, qpos_b))                      # [nq,B,qb,KV,G,hd]
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, S_p, H, hd_v)[:, :S]
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Sliding-window attention: per-q-block KV slice (subquadratic)
# ---------------------------------------------------------------------------

def windowed_attention(q, k, v, q_pos, k_pos, *, window: int, q_block: int = 512):
    """Causal sliding-window attention; each q block attends a KV slice of
    length ``window + q_block`` ending at the block's last position.

    Shapes as in attention_core.  Assumes q and k cover the same contiguous
    positions (train/prefill self-attention).
    """
    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = hd ** -0.5
    qb = min(q_block, S)
    S_p = -(-S // qb) * qb
    span = window + qb
    if span >= T:  # window covers everything — fall back
        return attention_core(q, k, v, q_pos, k_pos, causal=True, window=window)
    if q_pos.ndim == 1:
        q_pos = jnp.broadcast_to(q_pos[None, :], (B, S))
    if k_pos.ndim == 1:
        k_pos = jnp.broadcast_to(k_pos[None, :], (B, T))
    qp = jnp.pad(q, ((0, 0), (0, S_p - S), (0, 0), (0, 0)))
    qpos_p = jnp.pad(q_pos, ((0, 0), (0, S_p - S)), constant_values=-1)
    nq = S_p // qb
    qp = qp.reshape(B, nq, qb, KV, G, hd).transpose(1, 0, 2, 3, 4, 5)
    qpos_b = qpos_p.reshape(B, nq, qb).transpose(1, 0, 2)
    starts = jnp.arange(nq) * qb + qb - span                               # may be <0; clamped

    def q_step(_, q_in):
        q_i, qpos_i, start = q_in
        start = jnp.clip(start, 0, T - span)
        k_j = jax.lax.dynamic_slice_in_dim(k, start, span, axis=1)         # [B,span,KV,hd]
        v_j = jax.lax.dynamic_slice_in_dim(v, start, span, axis=1)
        kpos_j = jax.lax.dynamic_slice_in_dim(k_pos, start, span, axis=1)  # [B,span]
        s = _gqa_scores(q_i, k_j, scale)                                   # [B,KV,G,qb,span]
        msk = (kpos_j[:, None, None, None, :] <= qpos_i[:, None, None, :, None])
        msk &= (kpos_j[:, None, None, None, :] > qpos_i[:, None, None, :, None] - window)
        s = jnp.where(msk, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bkgqt,btkh->bkgqh", p.astype(v_j.dtype), v_j,
                         preferred_element_type=jnp.float32)
        return None, out.transpose(0, 3, 1, 2, 4)

    _, out = jax.lax.scan(q_step, None, (qp, qpos_b, starts))
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, S_p, H, hd)[:, :S]
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Decode: ring-buffer KV cache
# ---------------------------------------------------------------------------

def init_cache(batch: int, cache_len: int, num_kv: int, head_dim: int, dtype):
    """Cache slots carry their absolute position (-1 = empty) so ring-buffer
    overwrites and windowing need no extra bookkeeping."""
    return {
        "k": jnp.zeros((batch, cache_len, num_kv, head_dim), dtype),
        "v": jnp.zeros((batch, cache_len, num_kv, head_dim), dtype),
        "pos": jnp.full((cache_len,), -1, jnp.int32),
        "index": jnp.zeros((), jnp.int32),       # absolute next position
    }


def cache_specs(batch: int, cache_len: int, num_kv: int, head_dim: int, dtype):
    import numpy as np
    S = jax.ShapeDtypeStruct
    return {
        "k": S((batch, cache_len, num_kv, head_dim), jnp.dtype(dtype)),
        "v": S((batch, cache_len, num_kv, head_dim), jnp.dtype(dtype)),
        "pos": S((cache_len,), jnp.int32),
        "index": S((), jnp.int32),
    }


def cache_update(cache, k_new, v_new):
    """Append one step (k_new/v_new: [B,1,KV,hd]) at ring slot index % len."""
    L = cache["k"].shape[1]
    slot = cache["index"] % L
    k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new.astype(cache["k"].dtype), slot, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new.astype(cache["v"].dtype), slot, axis=1)
    pos = jax.lax.dynamic_update_slice_in_dim(cache["pos"], cache["index"][None], slot, axis=0)
    return {"k": k, "v": v, "pos": pos, "index": cache["index"] + 1}


def decode_attend(q, cache, *, window: int = 0):
    """q: [B,1,H,hd] against the cache. Returns [B,1,H,hd]."""
    B, _, H, hd = q.shape
    KV = cache["k"].shape[2]
    G = H // KV
    scale = hd ** -0.5
    cur = cache["index"] - 1                      # position of the newest token
    kpos = cache["pos"]                           # [L]
    valid = kpos >= 0
    valid &= kpos <= cur
    if window:
        valid &= kpos > cur - window
    q_ = q.reshape(B, 1, KV, G, hd)
    s = _gqa_scores(q_, cache["k"], scale)        # [B,KV,G,1,L]
    s = jnp.where(valid[None, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqt,btkh->bkgqh", p.astype(cache["v"].dtype), cache["v"],
                     preferred_element_type=jnp.float32)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, 1, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# Decode: paged KV cache (global page pool shared by all slots)
# ---------------------------------------------------------------------------

def _paged_window(cfg) -> int:
    """Ring-layout window of a GQA family (0 = contiguous pages)."""
    return cfg.window if cfg.attn_kind in ("swa", "local") else 0


def paged_cache_update(kv, k_new, v_new, page_table, pos, *, window: int = 0):
    """Write one decode step's K/V into the shared page pool.

    kv: {"k","v"}: [P, KV, ps, hd] (one layer's head-major pages);
    k_new/v_new [slots, 1, KV, hd]; page_table [slots, n] int32; pos
    [slots] int32 —
    token t of slot s lands in page ``page_table[s, t // ps]`` at offset
    ``t % ps`` (contiguous), or — ring layout, ``window > 0`` — in cell
    ``(t % window) // ps`` of the slot's ring table, same in-page offset
    (the pool guarantees ``ps | window``).  Slots without a request carry
    an all-trash table (page 0), so their writes clobber only the reserved
    trash page.

    Quantized (int8) pools carry a ``*_scale`` leaf per data leaf; the row
    is quantized once (``layouts.write_rows``) and both the int8 row and
    its per-head scale scatter to the same (page, offset).
    """
    ps = kv["k"].shape[2]
    idx = pos % window if window else pos
    page = jnp.take_along_axis(page_table, (idx // ps)[:, None], axis=1)[:, 0]
    off = pos % ps
    return {**write_rows(kv, "k", page, off, k_new[:, 0]),
            **write_rows(kv, "v", page, off, v_new[:, 0])}


def paged_latent_update(kv, ckv_new, krope_new, page_table, pos):
    """Latent-layout twin of ``paged_cache_update``: kv {"ckv": [P, ps, R],
    "krope": [P, ps, rp]}; ckv_new/krope_new [slots, 1, ·] (MLA decode
    caches the compressed latents, never materialized heads)."""
    ps = kv["ckv"].shape[1]
    page = jnp.take_along_axis(page_table, (pos // ps)[:, None], axis=1)[:, 0]
    off = pos % ps
    return {
        "ckv": kv["ckv"].at[page, off].set(
            ckv_new[:, 0].astype(kv["ckv"].dtype)),
        "krope": kv["krope"].at[page, off].set(
            krope_new[:, 0].astype(kv["krope"].dtype)),
    }


def _chunk_targets(page_ids, start, n_valid, S: int, ps: int,
                   window: int = 0):
    """(page, off) scatter targets for one prefill chunk of S bucket slots:
    token i holds absolute position ``start + i``; bucket padding
    (i >= n_valid) routes to the reserved trash page 0 so the fixed bucket
    shape never scatters garbage into held pages."""
    i = jnp.arange(S)
    pos = start + i
    idx = pos % window if window else pos
    blk = jnp.clip(idx // ps, 0, page_ids.shape[0] - 1)
    page = jnp.where(i < n_valid, page_ids[blk], 0)
    return page, pos % ps


def paged_prefill_write(kv, k_new, v_new, page_ids, start, n_valid, *,
                        window: int = 0):
    """Write one prefill chunk's K/V into the shared page pool.

    kv: {"k","v"}: [P, KV, ps, hd] (one layer's head-major pages);
    k_new/v_new [1, S, KV, hd] (S = padded bucket length); page_ids [n]
    int32 — one
    request's page-table row; start / n_valid traced scalars.  Position
    mapping per ``_chunk_targets`` (contiguous or ring).  Quantized pools
    scatter int8 rows + per-head scales (see ``paged_cache_update``);
    padding rows land harmlessly in the trash page, scales included.
    """
    ps = kv["k"].shape[2]
    page, off = _chunk_targets(page_ids, start, n_valid, k_new.shape[1], ps,
                               window)
    return {**write_rows(kv, "k", page, off, k_new[0]),
            **write_rows(kv, "v", page, off, v_new[0])}


def paged_latent_prefill_write(kv, ckv_new, krope_new, page_ids, start,
                               n_valid):
    """Latent-layout twin of ``paged_prefill_write``: ckv_new [1, S, R],
    krope_new [1, S, rp] into {"ckv", "krope"} pages (contiguous)."""
    ps = kv["ckv"].shape[1]
    page, off = _chunk_targets(page_ids, start, n_valid, ckv_new.shape[1],
                               ps)
    return {
        "ckv": kv["ckv"].at[page, off].set(
            ckv_new[0].astype(kv["ckv"].dtype)),
        "krope": kv["krope"].at[page, off].set(
            krope_new[0].astype(kv["krope"].dtype)),
    }


@jax.named_scope("attention")
def paged_prefill_apply(cfg, p, x, positions, kv, page_ids, start, n_valid,
                        *, use_pallas: bool = False):
    """Prefill-chunk GQA self-attention directly against the page pool.

    x [1, S, D] — one request's chunk, padded to a power-of-two bucket;
    positions = start + arange(S); page_ids [n] the request's page-table
    row.  Contiguous layout: the chunk's K/V are written into the pool
    first (pages covering the cached prefix are *never* written: the chunk
    starts at ``start`` >= prefix length, and padding writes hit the trash
    page), then the chunk's queries attend causally over everything cached
    so far — shared prefix pages, earlier chunks, and the chunk itself.

    Ring layout (sliding-window/local): the chunk's writes *wrap onto*
    cells its own early queries still need, so the ring is consumed as a
    snapshot BEFORE the write and the chunk attends over [snapshot, chunk]
    with ring-arithmetic key positions; the sliding-window mask keeps
    every overwritten (out-of-window) snapshot cell out of the scores.
    The engine caps ring chunks at ``window`` tokens, so no two writes in
    one chunk collide.

    ``use_pallas`` dispatches the scalar-prefetched Pallas prefill kernels
    (``kernels.paged_attention``: HBM traffic ~ pages actually held,
    bucket-tail query rows skipped at grid level); the default is the
    traced whole-table gather through ``attention_core``.  Quantized (int8)
    pools route through the kernel family's paired oracle even with the
    kernels off — ref and kernel apply the *same* fused scale math (scales
    multiplied into the softmax accumulation, fp pages never materialized),
    which is what keeps quantized kernel-on vs kernel-off token-identical.

    Returns (out [1, S, D], new_kv).
    """
    from repro.kernels.paged_attention import ops as pa_ops
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    H = cfg.num_heads
    cd = x.dtype
    window = _paged_window(cfg)
    quantized = "k" + SCALE_SUFFIX in kv

    q, k, v = _project_qkv_rope(cfg, p, x, positions)
    if window:
        new_kv = paged_prefill_write(kv, k, v, page_ids, start, n_valid,
                                     window=window)
        if use_pallas or quantized:
            # snapshot semantics by construction: ``kv`` is the pre-write
            # pool, the chunk's own K/V ride along as separate fp operands
            # (freshly projected — only pool pages are quantized)
            out = pa_ops.paged_ring_prefill(
                q[0], kv["k"], kv["v"], k[0].astype(cd), v[0].astype(cd),
                page_ids, start, n_valid, window=window,
                k_scale=kv.get("k" + SCALE_SUFFIX),
                v_scale=kv.get("v" + SCALE_SUFFIX),
                use_kernel=use_pallas)[None]
        else:
            ring_k = gather_rows(kv["k"], page_ids)[None]
            ring_v = gather_rows(kv["v"], page_ids)[None]
            cur = start - 1
            i = jnp.arange(ring_k.shape[1])
            ring_pos = cur - jnp.mod(cur - i, window)  # < 0 = never written
            kk = jnp.concatenate([ring_k.astype(cd), k], axis=1)
            vv = jnp.concatenate([ring_v.astype(cd), v], axis=1)
            k_pos = jnp.concatenate(
                [ring_pos[None, :], (start + jnp.arange(S))[None, :]],
                axis=1)
            kv_valid = jnp.concatenate(
                [(ring_pos >= 0)[None, :],
                 (jnp.arange(S) < n_valid)[None, :]], axis=1)
            out = attention_core(q, kk, vv, positions, k_pos, causal=True,
                                 window=window, q_block=cfg.attn_q_block,
                                 kv_block=cfg.attn_kv_block,
                                 kv_valid=kv_valid)
    else:
        new_kv = paged_prefill_write(kv, k, v, page_ids, start, n_valid)
        if use_pallas or quantized:
            out = pa_ops.paged_prefill(q[0], new_kv["k"], new_kv["v"],
                                       page_ids, start, n_valid,
                                       k_scale=new_kv.get("k" + SCALE_SUFFIX),
                                       v_scale=new_kv.get("v" + SCALE_SUFFIX),
                                       use_kernel=use_pallas)[None]
        else:
            # gather this request's pages into a contiguous [1, n*ps] view;
            # absolute key positions are the identity, validity =
            # written-so-far bound (trash entries in the table tail sit
            # past the bound, so they are never seen)
            kk = gather_rows(new_kv["k"], page_ids)[None]
            vv = gather_rows(new_kv["v"], page_ids)[None]
            k_pos = jnp.arange(kk.shape[1])
            kv_valid = (k_pos < start + n_valid)[None, :]
            out = attention_core(q, kk.astype(cd), vv.astype(cd), positions,
                                 k_pos, causal=True,
                                 q_block=cfg.attn_q_block,
                                 kv_block=cfg.attn_kv_block,
                                 kv_valid=kv_valid)
    out = out.reshape(B, S, H * hd)
    return dot(out, p["wo"], cd), new_kv


@jax.named_scope("attention")
def paged_attention_apply(cfg, p, x, positions, kv, page_table, lengths, *,
                          use_pallas: bool = False):
    """One batched decode step of GQA self-attention over a paged pool.

    x [slots, 1, D]; positions [slots, 1] (= lengths[:, None]); kv one
    layer's pages.  Unlike ``attention_apply`` (vmapped per slot over a
    private ring cache), this runs the whole slot batch against the shared
    pool.  Covers full attention (contiguous pages) and sliding-window /
    local attention (ring-wrapped window pages — the position mapping and
    window mask live in the kernel/ref).  Returns (out [slots, 1, D],
    new_kv).
    """
    from repro.kernels.paged_attention import ops as pa_ops
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    H = cfg.num_heads
    cd = x.dtype
    window = _paged_window(cfg)

    q, k, v = _project_qkv_rope(cfg, p, x, positions)
    new_kv = paged_cache_update(kv, k, v, page_table, lengths, window=window)
    out = pa_ops.paged_attention(q[:, 0], new_kv["k"], new_kv["v"],
                                 page_table, lengths + 1, window=window,
                                 k_scale=new_kv.get("k" + SCALE_SUFFIX),
                                 v_scale=new_kv.get("v" + SCALE_SUFFIX),
                                 use_kernel=use_pallas)
    out = out[:, None].reshape(B, S, H * hd)
    return dot(out, p["wo"], cd), new_kv


@jax.named_scope("attention")
def paged_mla_attention_apply(cfg, p, x, positions, kv, page_table, lengths,
                              *, use_pallas: bool = False):
    """One batched decode step of absorbed MLA over latent pages.

    x [slots, 1, D]; kv {"ckv": [P, ps, R], "krope": [P, ps, rp]} — one
    layer's latent pages.  The math mirrors ``mla_apply``'s decode path
    (scores in the latent space; cache stays compressed), the storage is
    the shared page pool.  Returns (out [slots, 1, D], new_kv)."""
    from repro.kernels.paged_attention import ops as pa_ops
    from repro.models.common import rms_norm
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.num_heads
    cd = x.dtype
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5

    dkv = dot(x, p["w_dkv"], cd)                         # [B,1,rank+rope]
    ckv, krope = jnp.split(dkv, [m.kv_lora_rank], axis=-1)
    ckv = rms_norm(ckv, p["kv_norm"], cfg.norm_eps)
    krope = apply_rope(krope[:, :, None, :], positions,
                       cfg.rope_theta)[:, :, 0, :]
    q_nope, q_rope = _mla_q(cfg, p, x, positions, cd)
    new_kv = paged_latent_update(kv, ckv, krope, page_table, lengths)
    w_uk = p["w_uk"].reshape(m.kv_lora_rank, H, m.qk_nope_head_dim).astype(cd)
    # absorb: q' = q_nope @ W_uk^T -> latent-space queries [B,1,H,rank]
    q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, w_uk,
                       preferred_element_type=jnp.float32).astype(cd)
    o_lat = pa_ops.paged_mla_attention(
        q_lat[:, 0], q_rope[:, 0], new_kv["ckv"], new_kv["krope"],
        page_table, lengths + 1, scale=scale, use_kernel=use_pallas)
    w_uv = p["w_uv"].reshape(m.kv_lora_rank, H, m.v_head_dim).astype(cd)
    out = jnp.einsum("bshr,rhv->bshv", o_lat[:, None].astype(cd), w_uv,
                     preferred_element_type=jnp.float32)
    out = out.astype(cd).reshape(B, S, H * m.v_head_dim)
    return dot(out, p["wo"], cd), new_kv


@jax.named_scope("attention")
def paged_mla_prefill_apply(cfg, p, x, positions, kv, page_ids, start,
                            n_valid, *, use_pallas: bool = False):
    """Prefill-chunk MLA attention directly against latent pages.

    The chunk's (normalized) latents are written into the pool.  The
    traced default then — to match the slotted prefill's numerics
    (``mla_apply``'s *expanded* path) — materializes per-head K/V from
    the gathered latents and attends causally over prefix + chunk.
    ``use_pallas`` dispatches the absorbed Pallas prefill kernel instead
    (``paged_mla_prefill``: queries absorbed through W_uk, pages stream
    as compressed ckv/krope, the latent output up-projects through W_uv
    — the same math as the absorbed decode path, so HBM traffic is the
    compressed cache).  Contiguous layout only (MLA is full causal
    attention).  Returns (out [1, S, D], new_kv)."""
    from repro.kernels.paged_attention import ops as pa_ops
    from repro.models.common import rms_norm
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.num_heads
    cd = x.dtype
    ps = kv["ckv"].shape[1]
    n = page_ids.shape[0]

    dkv = dot(x, p["w_dkv"], cd)                          # [1,S,rank+rope]
    ckv, krope = jnp.split(dkv, [m.kv_lora_rank], axis=-1)
    ckv = rms_norm(ckv, p["kv_norm"], cfg.norm_eps)
    krope = apply_rope(krope[:, :, None, :], positions,
                       cfg.rope_theta)[:, :, 0, :]
    new_kv = paged_latent_prefill_write(kv, ckv, krope, page_ids, start,
                                        n_valid)
    q_nope, q_rope = _mla_q(cfg, p, x, positions, cd)
    if use_pallas:
        scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
        w_uk = p["w_uk"].reshape(m.kv_lora_rank, H,
                                 m.qk_nope_head_dim).astype(cd)
        q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, w_uk,
                           preferred_element_type=jnp.float32).astype(cd)
        o_lat = pa_ops.paged_mla_prefill(
            q_lat[0], q_rope[0], new_kv["ckv"], new_kv["krope"], page_ids,
            start, n_valid, scale=scale, use_kernel=True)
        w_uv = p["w_uv"].reshape(m.kv_lora_rank, H, m.v_head_dim).astype(cd)
        out = jnp.einsum("shr,rhv->shv", o_lat.astype(cd), w_uv,
                         preferred_element_type=jnp.float32)[None]
        out = out.astype(cd)
    else:
        ckv_all = new_kv["ckv"][page_ids].reshape(1, n * ps,
                                                  m.kv_lora_rank).astype(cd)
        kr_all = new_kv["krope"][page_ids].reshape(
            1, n * ps, m.qk_rope_head_dim).astype(cd)
        k_nope = dot(ckv_all, p["w_uk"], cd).reshape(1, n * ps, H,
                                                     m.qk_nope_head_dim)
        vv = dot(ckv_all, p["w_uv"], cd).reshape(1, n * ps, H, m.v_head_dim)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(kr_all[:, :, None, :],
                                      (1, n * ps, H, m.qk_rope_head_dim))],
            axis=-1)
        k_pos = jnp.arange(n * ps)
        kv_valid = (k_pos < start + n_valid)[None, :]
        out = attention_core(q, k, vv, positions, k_pos, causal=True,
                             q_block=cfg.attn_q_block,
                             kv_block=cfg.attn_kv_block, kv_valid=kv_valid)
    out = out.reshape(B, S, H * m.v_head_dim)
    return dot(out, p["wo"], cd), new_kv


# ---------------------------------------------------------------------------
# Full GQA block apply (projections + rope + core/window/decode dispatch)
# ---------------------------------------------------------------------------

@jax.named_scope("attention")
def attention_apply(cfg, p, x, positions, *, cache=None, use_pallas: bool = False):
    """Self-attention for train/prefill (cache=None) or one decode step.

    Returns (out [B,S,D], new_cache_or_None).
    """
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    H = cfg.num_heads
    cd = x.dtype

    q, k, v = _project_qkv_rope(cfg, p, x, positions)

    window = cfg.window if cfg.attn_kind in ("swa", "local") else 0
    if cache is None:
        if use_pallas:
            from repro.kernels.flash_attention import ops as fa_ops
            core = functools.partial(fa_ops.flash_attention,
                                     causal=True, window=window)
        elif window and S > 2 * window:
            core = lambda q, k, v: windowed_attention(
                q, k, v, positions, positions, window=window,
                q_block=cfg.attn_q_block)
        else:
            core = lambda q, k, v: attention_core(
                q, k, v, positions, positions, causal=True, window=window,
                q_block=cfg.attn_q_block, kv_block=cfg.attn_kv_block)
        if cfg.attn_remat and not use_pallas:
            # flash-style custom-VJP backward: saves only (O, logsumexp) and
            # recomputes score blocks in the backward — kills the stacked
            # probability residuals that dominate the traced path's HBM term
            # (nested jax.checkpoint does NOT achieve this: the scan
            # transpose re-stacks them; see EXPERIMENTS.md §Perf).
            from repro.models.flash_vjp import flash_attention_vjp
            core = functools.partial(
                flash_attention_vjp, causal=True, window=window,
                q_block=cfg.attn_q_block, kv_block=cfg.attn_kv_block)
        out = core(q, k, v)
        new_cache = None
    else:
        new_cache = cache_update(cache, k, v)
        out = decode_attend(q, new_cache, window=window)
    out = out.reshape(B, S, H * hd)
    return dot(out, p["wo"], cd), new_cache


# ---------------------------------------------------------------------------
# MLA apply (DeepSeek-V2): compressed KV cache, expanded for train/prefill,
# absorbed projections for decode.
# ---------------------------------------------------------------------------

def mla_init_cache(batch: int, cache_len: int, cfg, dtype):
    m = cfg.mla
    return {
        "ckv": jnp.zeros((batch, cache_len, m.kv_lora_rank), dtype),
        "krope": jnp.zeros((batch, cache_len, m.qk_rope_head_dim), dtype),
        "pos": jnp.full((cache_len,), -1, jnp.int32),
        "index": jnp.zeros((), jnp.int32),
    }


def mla_cache_specs(batch: int, cache_len: int, cfg, dtype):
    m = cfg.mla
    S = jax.ShapeDtypeStruct
    return {
        "ckv": S((batch, cache_len, m.kv_lora_rank), jnp.dtype(dtype)),
        "krope": S((batch, cache_len, m.qk_rope_head_dim), jnp.dtype(dtype)),
        "pos": S((cache_len,), jnp.int32),
        "index": S((), jnp.int32),
    }


def _mla_q(cfg, p, x, positions, cd):
    m = cfg.mla
    H = cfg.num_heads
    qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
    if m.q_lora_rank:
        from repro.models.common import rms_norm
        cq = rms_norm(dot(x, p["w_dq"], cd), p["q_norm"], cfg.norm_eps)
        q = dot(cq, p["w_uq"], cd)
    else:
        q = dot(x, p["wq"], cd)
    q = q.reshape(*x.shape[:2], H, qk_dim)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


@jax.named_scope("attention")
def mla_apply(cfg, p, x, positions, *, cache=None):
    """Returns (out [B,S,D], new_cache_or_None)."""
    from repro.models.common import rms_norm
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.num_heads
    cd = x.dtype
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5

    dkv = dot(x, p["w_dkv"], cd)                                  # [B,S,rank+rope]
    ckv, krope = jnp.split(dkv, [m.kv_lora_rank], axis=-1)
    ckv = rms_norm(ckv, p["kv_norm"], cfg.norm_eps)
    krope = apply_rope(krope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0, :]
    q_nope, q_rope = _mla_q(cfg, p, x, positions, cd)

    if cache is None:
        # expanded path: materialize per-head K/V from the latent
        k_nope = dot(ckv, p["w_uk"], cd).reshape(B, S, H, m.qk_nope_head_dim)
        vv = dot(ckv, p["w_uv"], cd).reshape(B, S, H, m.v_head_dim)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(krope[:, :, None, :], (B, S, H, m.qk_rope_head_dim))],
            axis=-1)
        out = attention_core(q, k, vv, positions, positions, causal=True)
        out = out.reshape(B, S, H * m.v_head_dim)
        return dot(out, p["wo"], cd), None

    # absorbed decode path: score in the latent space (cache stays compressed)
    L = cache["ckv"].shape[1]
    slot = cache["index"] % L
    new_cache = {
        "ckv": jax.lax.dynamic_update_slice_in_dim(
            cache["ckv"], ckv.astype(cache["ckv"].dtype), slot, axis=1),
        "krope": jax.lax.dynamic_update_slice_in_dim(
            cache["krope"], krope.astype(cache["krope"].dtype), slot, axis=1),
        "pos": jax.lax.dynamic_update_slice_in_dim(
            cache["pos"], cache["index"][None], slot, axis=0),
        "index": cache["index"] + 1,
    }
    cur = new_cache["index"] - 1
    valid = (new_cache["pos"] >= 0) & (new_cache["pos"] <= cur)
    w_uk = p["w_uk"].reshape(m.kv_lora_rank, H, m.qk_nope_head_dim).astype(cd)
    # absorb: q' = q_nope @ W_uk^T  -> latent-space queries [B,1,H,rank]
    q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, w_uk, preferred_element_type=jnp.float32)
    s = jnp.einsum("bshr,btr->bhst", q_lat.astype(cd), new_cache["ckv"],
                   preferred_element_type=jnp.float32)
    s += jnp.einsum("bshn,btn->bhst", q_rope, new_cache["krope"],
                    preferred_element_type=jnp.float32)
    s = jnp.where(valid[None, None, None, :], s * scale, NEG_INF)
    pr = jax.nn.softmax(s, axis=-1)
    # output in latent space, then up-project via W_uv
    o_lat = jnp.einsum("bhst,btr->bshr", pr.astype(cd), new_cache["ckv"],
                       preferred_element_type=jnp.float32).astype(cd)
    w_uv = p["w_uv"].reshape(m.kv_lora_rank, H, m.v_head_dim).astype(cd)
    out = jnp.einsum("bshr,rhv->bshv", o_lat, w_uv, preferred_element_type=jnp.float32)
    out = out.astype(cd).reshape(B, 1, H * m.v_head_dim)
    return dot(out, p["wo"], cd), new_cache
