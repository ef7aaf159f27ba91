"""jit'd public wrappers: paged attention in the serving pool's layouts.

Dispatch mirrors ``flash_attention``: the traced jnp path (ref semantics,
gather-all) is the portable default the serving engine runs everywhere; the
Pallas kernels (``use_kernel=True``) are the TPU fast path whose HBM
traffic scales with pages actually held.  Off-TPU, ``use_kernel=True``
transparently runs the kernels in interpret mode (the backend selection
the engine's ``ServeConfig.use_pallas`` override and the CI smoke job rely
on), so kernel code paths stay exercised everywhere.

Decode wrappers, one per page geometry: ``paged_attention`` covers the
per-head k/v layouts (contiguous "kv" and ring "window" — ``window > 0``
flips the position mapping), and ``paged_mla_attention`` the latent
ckv/krope layout (absorbed MLA decode; scores and output stay in the
latent space).  Chunked-prefill wrappers follow the same contract for one
request's bucketed chunk: ``paged_prefill`` (contiguous; pages already
hold the chunk's K/V), ``paged_ring_prefill`` (snapshot-before-write ring
semantics; the chunk's own K/V ride along), ``paged_mla_prefill``
(absorbed latent queries, latent output).  All share the head conventions
of ``repro.models.attention``.

Per-head pages are head-major [P, KV, ps, hd] (the layout whose page
blocks the TPU can tile; see ``kernel.py``).  Quantized (int8) pools pass
their per-(page, kv-head, offset) bf16 scale leaves as optional
``k_scale``/``v_scale`` operands ([P, KV, 1, ps]; None = fp pages).  Kernel and ref apply the *identical* fused math — raw int8
scores scaled per key column, probabilities scaled per value row before
the PV product — so kernel-on vs kernel-off stays token-identical for
quantized layouts too.  MLA latent pages never quantize (the layout seam
rejects the combination), so the MLA wrappers take no scales."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention.kernel import (paged_attention_kernel,
                                                  paged_mla_kernel,
                                                  paged_mla_prefill_kernel,
                                                  paged_prefill_kernel,
                                                  paged_ring_prefill_kernel)
from repro.kernels.paged_attention.ref import (paged_attention_ref,
                                               paged_mla_attention_ref,
                                               paged_mla_prefill_ref,
                                               paged_prefill_ref,
                                               paged_ring_prefill_ref)


def _interp(interpret: bool) -> bool:
    """Kernels only lower on TPU; everywhere else ``use_kernel=True`` means
    the Pallas interpreter (correctness-identical, CI-exercisable)."""
    return interpret or jax.default_backend() != "tpu"


def _meta(start, n_valid):
    return jnp.stack([jnp.asarray(start, jnp.int32),
                      jnp.asarray(n_valid, jnp.int32)])


@functools.partial(jax.jit,
                   static_argnames=("window", "use_kernel", "interpret"))
def paged_attention(q, k_pages, v_pages, page_table, lengths, *,
                    window: int = 0, k_scale=None, v_scale=None,
                    use_kernel: bool = False, interpret: bool = False):
    """q: [slots, H, hd]; k/v_pages: [P, KV, ps, hd]; page_table:
    [slots, n_table] int32 (pad with 0, the trash page); lengths: [slots]
    int32 (valid tokens per slot).  ``window > 0`` selects the ring-cell
    position mapping (sliding-window mask included).  ``k_scale``/
    ``v_scale`` [P, KV, 1, ps] bf16 mark int8 pages (dequant fused into the
    softmax accumulation).  Returns [slots, H, hd] in q.dtype."""
    slots, H, hd = q.shape
    KV = k_pages.shape[1]
    if not use_kernel:
        return paged_attention_ref(q, k_pages, v_pages, page_table, lengths,
                                   window=window, k_scale=k_scale,
                                   v_scale=v_scale)
    G = H // KV
    out = paged_attention_kernel(q.reshape(slots, KV, G, hd), k_pages,
                                 v_pages, page_table, lengths,
                                 window=window, k_scale=k_scale,
                                 v_scale=v_scale,
                                 interpret=_interp(interpret))
    return out.reshape(slots, H, hd)


@functools.partial(jax.jit,
                   static_argnames=("scale", "use_kernel", "interpret"))
def paged_mla_attention(q_lat, q_rope, ckv_pages, krope_pages, page_table,
                        lengths, *, scale: float, use_kernel: bool = False,
                        interpret: bool = False):
    """Absorbed MLA decode against latent pages.  q_lat: [slots, H, R]
    (queries absorbed through W_uk); q_rope: [slots, H, rp]; ckv_pages:
    [P, ps, R]; krope_pages: [P, ps, rp]; ``scale`` the qk-dimension
    softmax scale.  Returns the latent-space output [slots, H, R] — the
    caller up-projects through W_uv."""
    if not use_kernel:
        return paged_mla_attention_ref(q_lat, q_rope, ckv_pages,
                                       krope_pages, page_table, lengths,
                                       scale=scale)
    return paged_mla_kernel(q_lat, q_rope, ckv_pages, krope_pages,
                            page_table, lengths, scale=scale,
                            interpret=_interp(interpret))


@functools.partial(jax.jit, static_argnames=("use_kernel", "interpret"))
def paged_prefill(q, k_pages, v_pages, page_table, start, n_valid, *,
                  k_scale=None, v_scale=None, use_kernel: bool = False,
                  interpret: bool = False):
    """Contiguous-layout chunked prefill.  q: [S, H, hd] — one request's
    bucketed chunk (post-rope; query i holds absolute position
    ``start + i``); k/v_pages: [P, KV, ps, hd] — the pool AFTER the
    chunk's K/V were scattered in; page_table: [n] int32 — the request's
    row (0-padded tail = trash); start / n_valid traced scalars.  Rows
    past ``n_valid`` are bucket padding — their output is undefined and
    must not be read.  ``k_scale``/``v_scale`` [P, KV, 1, ps] bf16 mark
    int8 pages.  Returns [S, H, hd] in q.dtype."""
    S, H, hd = q.shape
    if not use_kernel:
        return paged_prefill_ref(q, k_pages, v_pages, page_table, start,
                                 n_valid, k_scale=k_scale, v_scale=v_scale)
    KV = k_pages.shape[1]
    out = paged_prefill_kernel(q.reshape(S, KV, H // KV, hd), k_pages,
                               v_pages, page_table, _meta(start, n_valid),
                               k_scale=k_scale, v_scale=v_scale,
                               interpret=_interp(interpret))
    return out.reshape(S, H, hd)


@functools.partial(jax.jit,
                   static_argnames=("window", "use_kernel", "interpret"))
def paged_ring_prefill(q, k_pages, v_pages, chunk_k, chunk_v, page_table,
                       start, n_valid, *, window: int, k_scale=None,
                       v_scale=None, use_kernel: bool = False,
                       interpret: bool = False):
    """Ring-layout (sliding-window/local) chunked prefill with
    snapshot-before-write semantics: k/v_pages are the pool BEFORE the
    chunk's writes and chunk_k/chunk_v [S, KV, hd] are the chunk's own
    post-rope keys/values (its writes wrap onto ring cells its early
    queries still need, so they must not be read back through the table).
    ``k_scale``/``v_scale`` [P, KV, 1, ps] bf16 mark int8 *snapshot* pages —
    the chunk operands always stay fp (freshly projected, never read back
    from the pool).  Returns [S, H, hd] in q.dtype."""
    S, H, hd = q.shape
    if not use_kernel:
        return paged_ring_prefill_ref(q, k_pages, v_pages, chunk_k,
                                      chunk_v, page_table, start, n_valid,
                                      window=window, k_scale=k_scale,
                                      v_scale=v_scale)
    KV = k_pages.shape[1]
    out = paged_ring_prefill_kernel(q.reshape(S, KV, H // KV, hd), k_pages,
                                    v_pages, chunk_k, chunk_v, page_table,
                                    _meta(start, n_valid), window=window,
                                    k_scale=k_scale, v_scale=v_scale,
                                    interpret=_interp(interpret))
    return out.reshape(S, H, hd)


@functools.partial(jax.jit,
                   static_argnames=("scale", "use_kernel", "interpret"))
def paged_mla_prefill(q_lat, q_rope, ckv_pages, krope_pages, page_table,
                      start, n_valid, *, scale: float,
                      use_kernel: bool = False, interpret: bool = False):
    """Absorbed-MLA chunked prefill against latent pages (contiguous).
    q_lat: [S, H, R] — the chunk's queries absorbed through W_uk; q_rope:
    [S, H, rp]; ckv/krope_pages hold the chunk's freshly written latents;
    ``scale`` the qk-dimension softmax scale.  Pages stream compressed —
    per-head K/V are never materialized.  Returns the latent-space output
    [S, H, R] in q_lat.dtype — the caller up-projects through W_uv."""
    if not use_kernel:
        return paged_mla_prefill_ref(q_lat, q_rope, ckv_pages, krope_pages,
                                     page_table, start, n_valid,
                                     scale=scale)
    return paged_mla_prefill_kernel(q_lat, q_rope, ckv_pages, krope_pages,
                                    page_table, _meta(start, n_valid),
                                    scale=scale,
                                    interpret=_interp(interpret))
