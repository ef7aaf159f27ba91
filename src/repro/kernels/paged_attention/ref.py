"""Pure-jnp oracles for the paged-attention decode kernels.

Layouts (the serving pool's native shapes, one per ``KVLayout``):
  * ``q``           [slots, H, hd]        — one query token per decode slot
  * ``k/v_pages``   [P, KV, ps, hd]       — head-major per-head page pool
                                            ("kv" and ring "window"
                                            layouts; page 0 is the reserved
                                            trash page)
  * ``ckv/krope_pages`` [P, ps, R] / [P, ps, rp] — latent page pool (MLA)
  * ``page_table``  [slots, n] int32      — per-slot page ids; entries past
                                            a slot's held pages point at
                                            page 0
  * ``lengths``     [slots] int32         — tokens cached per slot

Position mapping is the layout's:
  * contiguous — token t of slot s lives at page ``page_table[s, t // ps]``,
    offset ``t % ps``; validity is ``index < length``.
  * ring (``window > 0``) — the table is a ring of ``window // ps`` cells;
    ring index i holds the *latest* absolute position ``p = cur -
    ((cur - i) mod window)`` with ``cur = length - 1``; validity is
    ``p >= 0`` (the formula already confines p to the window, which is
    exactly the sliding-window mask — out-of-window cells whose pages
    rotated to trash resolve to positions the mask excludes).

GQA head convention matches ``repro.models.attention``: head h = kv-head
``h // G`` (reshape H -> (KV, G)).  These materialize the fully gathered
[slots, n*ps] score matrix — correctness only; the Pallas kernels only
ever touch pages a slot actually holds.

Quantized (int8) pools pass ``k_scale``/``v_scale`` [P, KV, 1, ps] bf16
(one symmetric scale per (page, kv-head, offset) row, widened to fp32 on
read).  The oracles mirror the
kernels' *fused* dequant exactly — raw int8 scores are computed first and
multiplied by the key's scale per column, probabilities are multiplied by
the value's scale per row before the PV product; fp pages are never
materialized — so kernel-on vs kernel-off stays token-identical for
quantized layouts.  MLA latent oracles take no scales (the layout seam
rejects quantized latents).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.common import NEG_INF


def _gather(pages, ids):
    """Token-major rows [..., n*ps, KV, hd] of head-major pages
    [P, KV, ps, hd] at page ids [..., n]."""
    x = jnp.swapaxes(pages[ids], -3, -2)                  # [..., n, ps, KV, hd]
    return x.reshape(*ids.shape[:-1], -1, *x.shape[-2:])


def _gather_scales(scales, ids):
    """fp32 per-row scales [..., n*ps, KV] of [P, KV, 1, ps] scale pages."""
    x = jnp.swapaxes(scales[ids][..., 0, :], -2, -1)      # [..., n, ps, KV]
    return x.reshape(*ids.shape[:-1], -1, x.shape[-1]).astype(jnp.float32)


def ring_positions(lengths, n_tokens: int, window: int):
    """Absolute position held by each ring index (see module docstring).

    lengths [slots] int32 -> ([slots, n_tokens] positions, validity)."""
    cur = lengths[:, None] - 1
    i = jnp.arange(n_tokens)[None, :]
    p = cur - jnp.mod(cur - i, window)
    return p, p >= 0


def paged_attention_ref(q, k_pages, v_pages, page_table, lengths, *,
                        window: int = 0, k_scale=None, v_scale=None):
    """Returns [slots, H, hd] in q.dtype.  ``window > 0`` selects the ring
    layout's position mapping (sliding-window mask included).  ``k_scale``/
    ``v_scale`` [P, KV, 1, ps] mark int8 pages — fused dequant, mirroring
    the kernel (see module docstring)."""
    S, H, hd = q.shape
    _, KV, ps, _ = k_pages.shape
    n = page_table.shape[1]
    G = H // KV
    scale = hd ** -0.5
    k = _gather(k_pages, page_table)                       # gather-all
    v = _gather(v_pages, page_table)
    if window:
        _, valid = ring_positions(lengths, n * ps, window)
    else:
        valid = jnp.arange(n * ps)[None, :] < lengths[:, None]  # [S, n*ps]
    q_ = q.reshape(S, KV, G, hd)
    s = jnp.einsum("skgh,stkh->skgt", q_.astype(jnp.float32),
                   k.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * scale
    if k_scale is not None:
        ks = _gather_scales(k_scale, page_table)           # [S, t, KV]
        s = s * ks.transpose(0, 2, 1)[:, :, None, :]       # per key column
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if v_scale is not None:
        vs = _gather_scales(v_scale, page_table)
        p = p * vs.transpose(0, 2, 1)[:, :, None, :]       # per value row
    out = jnp.einsum("skgt,stkh->skgh", p, v.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return out.reshape(S, H, hd).astype(q.dtype)


def paged_mla_attention_ref(q_lat, q_rope, ckv_pages, krope_pages,
                            page_table, lengths, *, scale: float):
    """Absorbed MLA decode against latent pages (contiguous layout).

    q_lat [slots, H, R] — queries absorbed through W_uk into the latent
    space; q_rope [slots, H, rp]; ckv_pages [P, ps, R]; krope_pages
    [P, ps, rp].  ``scale`` is the *qk-dimension* softmax scale (the latent
    rank is not the score dimension).  Returns the latent-space output
    [slots, H, R] in q_lat.dtype — the caller up-projects through W_uv.
    """
    S, H, R = q_lat.shape
    _, ps, _ = ckv_pages.shape
    n = page_table.shape[1]
    ckv = ckv_pages[page_table].reshape(S, n * ps, R)
    kr = krope_pages[page_table].reshape(S, n * ps, krope_pages.shape[-1])
    s = jnp.einsum("shr,str->sht", q_lat.astype(jnp.float32),
                   ckv.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    s = s + jnp.einsum("shr,str->sht", q_rope.astype(jnp.float32),
                       kr.astype(jnp.float32),
                       preferred_element_type=jnp.float32)
    valid = jnp.arange(n * ps)[None, :] < lengths[:, None]   # [S, n*ps]
    s = jnp.where(valid[:, None, :], s * scale, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("sht,str->shr", p, ckv.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return out.astype(q_lat.dtype)


# ---------------------------------------------------------------------------
# Chunked-prefill oracles (one bucketed chunk of a single request)
#
# q is [S, H, hd] (H = KV * G); page_table is the request's single row [n];
# start / n_valid are traced scalars — query i holds absolute position
# ``start + i``, the bucket tail (i >= n_valid) is padding.  Bucket-tail
# output rows are garbage in both the oracle and the kernel (the kernel
# skips them at grid level and emits 0) — callers only ever read rows
# < n_valid, and tests must compare only those.
# ---------------------------------------------------------------------------

def _prefill_attend(q, k, v, valid, scale, k_scale=None, v_scale=None):
    """Masked full-softmax core: q [S, KV, G, hd], k/v [T, KV, hd],
    valid [S, T] -> [S, KV*G, hd].  Optional per-key-row dequant scales
    k_scale/v_scale [T, KV] (fused, matching the kernels: raw scores *
    key scale, probabilities * value scale)."""
    S, KV, G, hd = q.shape
    s = jnp.einsum("skgh,tkh->skgt", q.astype(jnp.float32),
                   k.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * scale
    if k_scale is not None:
        s = s * k_scale.T[None, :, None, :]                # per key column
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if v_scale is not None:
        p = p * v_scale.T[None, :, None, :]                # per value row
    out = jnp.einsum("skgt,tkh->skgh", p, v.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return out.reshape(S, KV * G, hd)


def paged_prefill_ref(q, k_pages, v_pages, page_table, start, n_valid, *,
                      k_scale=None, v_scale=None):
    """Contiguous-layout chunked prefill: the pages already hold the
    chunk's K/V (positions start..start+n_valid-1), so queries attend the
    gathered pages under the written bound AND the causal horizon.
    ``k_scale``/``v_scale`` [P, KV, 1, ps] mark int8 pages (fused dequant).
    Returns [S, H, hd] in q.dtype."""
    S, H, hd = q.shape
    _, KV, ps, _ = k_pages.shape
    n = page_table.shape[0]
    k = _gather(k_pages, page_table)
    v = _gather(v_pages, page_table)
    ks = (_gather_scales(k_scale, page_table)
          if k_scale is not None else None)
    vs = (_gather_scales(v_scale, page_table)
          if v_scale is not None else None)
    kidx = jnp.arange(n * ps)
    qpos = start + jnp.arange(S)
    valid = (kidx[None, :] < start + n_valid) \
        & (kidx[None, :] <= qpos[:, None])
    out = _prefill_attend(q.reshape(S, KV, H // KV, hd), k, v, valid,
                          hd ** -0.5, ks, vs)
    return out.astype(q.dtype)


def paged_ring_prefill_ref(q, k_pages, v_pages, chunk_k, chunk_v,
                           page_table, start, n_valid, *, window: int,
                           k_scale=None, v_scale=None):
    """Ring-layout chunked prefill, snapshot-before-write semantics: the
    pages are the PRE-write ring snapshot (the chunk's writes wrap onto
    cells its own early queries still need) and the chunk's own keys/
    values ride along as [S, KV, hd] operands.  Key positions follow the
    ring formula for the snapshot and ``start + j`` for the chunk; the
    sliding-window mask excludes every wrapped-over snapshot cell.
    ``k_scale``/``v_scale`` [P, KV, 1, ps] mark int8 *snapshot* pages — the
    chunk operands stay fp, so their fused scale is 1.
    Returns [S, H, hd] in q.dtype."""
    S, H, hd = q.shape
    _, KV, ps, _ = k_pages.shape
    n = page_table.shape[0]
    ring_k = _gather(k_pages, page_table)
    ring_v = _gather(v_pages, page_table)
    cur = start - 1
    i = jnp.arange(n * ps)
    ring_pos = cur - jnp.mod(cur - i, window)       # < 0 = never written
    if k_scale is not None:
        # snapshot rows carry their page scales; chunk rows are fp (= 1)
        kk = jnp.concatenate([ring_k.astype(jnp.float32),
                              chunk_k.astype(jnp.float32)], axis=0)
        vv = jnp.concatenate([ring_v.astype(jnp.float32),
                              chunk_v.astype(jnp.float32)], axis=0)
        ones = jnp.ones((S, KV), jnp.float32)
        ks = jnp.concatenate([_gather_scales(k_scale, page_table), ones],
                             axis=0)
        vs = jnp.concatenate([_gather_scales(v_scale, page_table), ones],
                             axis=0)
    else:
        kk = jnp.concatenate([ring_k, chunk_k.astype(ring_k.dtype)], axis=0)
        vv = jnp.concatenate([ring_v, chunk_v.astype(ring_v.dtype)], axis=0)
        ks = vs = None
    k_pos = jnp.concatenate([ring_pos, start + jnp.arange(S)])
    k_ok = jnp.concatenate([ring_pos >= 0, jnp.arange(S) < n_valid])
    qpos = start + jnp.arange(S)
    valid = k_ok[None, :] & (k_pos[None, :] <= qpos[:, None]) \
        & (k_pos[None, :] > qpos[:, None] - window)
    out = _prefill_attend(q.reshape(S, KV, H // KV, hd), kk, vv, valid,
                          hd ** -0.5, ks, vs)
    return out.astype(q.dtype)


def paged_mla_prefill_ref(q_lat, q_rope, ckv_pages, krope_pages,
                          page_table, start, n_valid, *, scale: float):
    """Absorbed-MLA chunked prefill against latent pages (contiguous).
    q_lat [S, H, R] — queries absorbed through W_uk; pages hold the
    chunk's freshly written latents.  Returns the latent-space output
    [S, H, R] in q_lat.dtype — the caller up-projects through W_uv."""
    S, H, R = q_lat.shape
    _, ps, _ = ckv_pages.shape
    n = page_table.shape[0]
    ckv = ckv_pages[page_table].reshape(n * ps, R)
    kr = krope_pages[page_table].reshape(n * ps, krope_pages.shape[-1])
    s = jnp.einsum("shr,tr->sht", q_lat.astype(jnp.float32),
                   ckv.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
    s = s + jnp.einsum("shr,tr->sht", q_rope.astype(jnp.float32),
                       kr.astype(jnp.float32),
                       preferred_element_type=jnp.float32)
    kidx = jnp.arange(n * ps)
    qpos = start + jnp.arange(S)
    valid = (kidx[None, :] < start + n_valid) \
        & (kidx[None, :] <= qpos[:, None])
    s = jnp.where(valid[:, None, :], s * scale, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("sht,tr->shr", p, ckv.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return out.astype(q_lat.dtype)
