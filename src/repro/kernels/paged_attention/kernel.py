"""Paged-attention TPU kernels (vLLM-style, scalar-prefetched pages).

One decode step attends each slot's single query against a cache scattered
across a global page pool; one prefill chunk attends a *block of causal
queries* against the same pages.  The page table is a *scalar-prefetch*
operand (``pltpu.PrefetchScalarGridSpec``): BlockSpec index maps (in the
per-head decode kernel, the kernel's own copies) read it to decide which
physical page to DMA into VMEM, so HBM traffic is ``pages_held``, not
``slots x max_pages`` — the whole point of paging.

Decode kernels, one per page geometry (see ``repro.serving.layouts``):

  * ``paged_attention_kernel`` — per-head k/v pages for GQA, covering both
    the contiguous ("kv") and ring-wrapped ("window") layouts.  For the
    ring, a cell's absolute position is arithmetic, not storage:
    ``p = cur - ((cur - idx) mod window)`` with ``cur = length - 1``; the
    ``p >= 0`` predicate *is* the sliding-window mask, so out-of-window
    cells (whose pages may have rotated to trash) never score.
  * ``paged_mla_kernel`` — latent (ckv/krope) pages for absorbed MLA
    decode: scores are ``q_lat . ckv + q_rope . krope`` and the output
    stays in the latent space (the caller up-projects through W_uv), so
    the kernel's HBM traffic is the *compressed* cache — the reason MLA
    pages at the latent rank instead of materialized heads.

Chunked-prefill kernels (one bucketed chunk of a single request; the
engine's ``paged_prefill_apply`` / ``lm_paged_verify`` path):

  * ``paged_prefill_kernel`` — contiguous pages already hold the chunk's
    freshly written K/V (positions ``start..start+n_valid-1``), so the
    chunk's causal queries attend pages only: key validity is the
    written-so-far bound ``idx < start + n_valid`` AND the causal horizon
    ``idx <= start + i``.
  * ``paged_ring_prefill_kernel`` — snapshot-before-write semantics: the
    chunk's writes wrap onto ring cells its own early queries still need,
    so the kernel streams the *pre-write* ring snapshot (ring-arithmetic
    key positions, same ``p >= 0`` liveness mask as decode) plus the
    chunk's own K/V as a separate blocked operand, matching the jnp
    path's gather-before-write contract.
  * ``paged_mla_prefill_kernel`` — absorbed MLA: latent-space queries
    against ckv/krope pages, output stays latent (the caller up-projects
    through W_uv) — per-head K/V are never materialized.

Decode walk: the grid is ``(slots,)``.  A step reads its slot's length,
copies only the pages that hold its cells (``pltpu.make_async_copy`` from
the pool left in HBM, the page id read from the prefetched table), and
walks them in blocks of ``ppb`` pages: one copy moves all KV heads of a
page, block i+1 is copied while block i is scored (two halves of a VMEM
buffer), and the walk ends at the last held page.  An empty slot costs a
step and no copy; a table column past the length is never read.  ``ppb``
follows from the shapes (about 256 cells a block, as many as fit a VMEM
budget, at most the table), and a tail block's cells past the held pages
are masked like cells past the length.  The online-softmax state (m, l,
acc) for every KV head lives in VMEM scratch across the blocks.

Prefill grid: ``(KV, n_q_blocks, n_table)`` with the page dimension
sequential ("arbitrary") and the online-softmax state carried in VMEM
scratch, like the kv-block dimension of ``flash_attention``; the MLA
decode grid is ``(slots, n_table)`` the same way.  Pages past the valid
cells are skipped at grid level (``pl.when``) — their table entries point
at the trash page (page 0) and cost no MXU cycles.  Prefill additionally
skips (a) whole query blocks past the chunk's ``n_valid`` tail (a
mostly-empty bucket no longer pays full attention tiles for its padding
rows) and (b) pages past each query block's causal horizon.

Layouts (see ref.py): q [slots, KV, G, hd] (prefill: [S, KV, G, hd]);
k/v pages [P, KV, ps, hd] (head-major); q_lat [slots, H, R]; ckv pages
[P, ps, R]; page_table [slots, n_table] int32 (prefill: one row
[n_table]); lengths [slots] int32 (prefill: meta [2] int32 = start,
n_valid).

Block shapes obey the TPU rule that the last two dims of a block are
divisible by (8, 128) or equal the array's: a k/v page block is
``(1, 1, ps, hd)`` (a decode page copy ``[KV, ps, hd]``), whose last two
dims are the page's own, so any ``page_size`` tiles (Mosaic pads a page
shorter than the dtype's sublane tile; ``tests/test_tpu_compile.py``
compiles int8 pages of 16 rows);
prefill queries are handed to the kernel head-major as [KV, S*G, hd] so
one block is a contiguous ``(qb*G, hd)`` tile.

Quantized (int8) pools add ``k_scale``/``v_scale`` operands [P, KV, 1, ps]
bf16 — one symmetric scale per (page, kv-head, offset) row, laid out as a
lane vector so the block ``(1, 1, 1, ps)`` is legal and multiplies score
columns directly — streamed through the same page-table index maps as
their int8 data pages (decode gathers them by the table into one row of
scales per block and kv-head, ``_block_scales``).  Dequant fuses into the
online softmax: raw int8 scores are multiplied by the key's scale per
column, probabilities by the value's scale per row before the PV product
— fp pages are never materialized, so HBM reads stay ~1/4 of the fp
pool's.  The jnp oracles in ref.py apply the identical fused math (same
multiply placement), which is what keeps quantized kernel-on vs
kernel-off token-identical.  MLA latent kernels take no scales (the
layout seam rejects quantized latents — rank is a contracted dim).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.common import NEG_INF


def _head_major_q(q):
    """[S, KV, G, hd] -> [KV, S*G, hd]: row r of head h is query r // G."""
    S, KV, G, hd = q.shape
    return q.transpose(1, 0, 2, 3).reshape(KV, S * G, hd)


def _token_major_out(o, S: int):
    """Inverse of ``_head_major_q``."""
    KV, _, hd = o.shape
    return o.reshape(KV, S, -1, hd).transpose(1, 0, 2, 3)


# Tokens walked per block, and the VMEM the double-buffered K and V blocks
# may take: a quarter of the TPU's default 16 MiB scoped limit, which
# leaves room for the fp32 upcasts of one head's block.
_BLOCK_TOKENS = 256
_BLOCK_VMEM = 4 * 2 ** 20


def _pages_per_block(page_size: int, hd: int, KV: int, itemsize: int,
                     n_table: int) -> int:
    """Pages per block: about ``_BLOCK_TOKENS`` cells, no more than fit
    ``_BLOCK_VMEM`` double-buffered for K and V, and no more than the
    table holds."""
    per_page = 2 * 2 * KV * page_size * hd * itemsize   # (K, V) x 2 halves
    ppb = min(pl.cdiv(_BLOCK_TOKENS, page_size), _BLOCK_VMEM // per_page)
    return max(1, min(ppb, n_table))


def _block_scales(scales, page_table, ppb: int):
    """[P, KV, 1, ps] scale pages -> [slots, n_blocks, KV, ppb * ps]: each
    block's cells in a row per kv-head, the layout the kernel's scores
    take.  Gathered here because Mosaic refuses to DMA one [KV, 1, ps]
    scale page (a slice whose lane dim is under 128)."""
    slots, n_table = page_table.shape
    _, KV, _, ps = scales.shape
    nb = pl.cdiv(n_table, ppb)
    ids = jnp.pad(page_table, ((0, 0), (0, nb * ppb - n_table)))
    x = scales[ids][..., 0, :].reshape(slots, nb, ppb, KV, ps)
    return x.transpose(0, 1, 3, 2, 4).reshape(slots, nb, KV, ppb * ps)


def _paged_kernel(pt_ref, len_ref, q_ref, k_hbm, v_hbm, *rest, scale: float,
                  page_size: int, n_table: int, ppb: int, window: int,
                  quantized: bool):
    if quantized:
        ks_ref, vs_ref, o_ref, k_buf, v_buf, sems, m_scr, l_scr, acc_scr = rest
    else:
        ks_ref = vs_ref = None
        o_ref, k_buf, v_buf, sems, m_scr, l_scr, acc_scr = rest
    s = pl.program_id(0)
    KV = q_ref.shape[1]
    hd = q_ref.shape[3]
    bk = ppb * page_size
    length = len_ref[s]
    # only the pages below the limit are walked (for the ring the valid
    # cell count saturates at the window — beyond that every cell holds a
    # live in-window position); table entries past them are never read
    limit = length if window == 0 else jnp.minimum(length, window)
    n_pages = jnp.minimum(pl.cdiv(limit, page_size), n_table)
    n_blocks = pl.cdiv(n_pages, ppb)

    def dma(i, half, op):
        """Start or wait block i's copies into ``half`` of the double
        buffer: one K and one V copy per held page, all KV heads each."""
        for j in range(ppb):
            col = i * ppb + j

            @pl.when(col < n_pages)
            def _():
                page = pt_ref[s, col]
                for n, (src, dst) in enumerate(((k_hbm, k_buf),
                                                (v_hbm, v_buf))):
                    cp = pltpu.make_async_copy(src.at[page], dst.at[half, j],
                                               sems.at[n, half])
                    getattr(cp, op)()

    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(n_blocks > 0)
    def _first():
        dma(0, 0, "start")

    def block(i, carry):
        half = jax.lax.rem(i, 2)

        @pl.when(i + 1 < n_blocks)
        def _next():
            dma(i + 1, 1 - half, "start")

        dma(i, half, "wait")
        base = i * bk
        idx = base + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        if window:
            # ring arithmetic: the cell's absolute position; p >= 0 is the
            # window mask (and masks never-written cells of short slots);
            # a tail block's cells past the ring are no cells at all
            cur = length - 1
            valid = (idx < window) & (cur - jnp.mod(cur - idx, window) >= 0)
        else:
            valid = idx < length
        # rows past the held pages hold whatever an earlier block or slot
        # left in the buffer: zero them (and the value scales of masked
        # cells), so that a masked probability never meets a NaN
        held = (base + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
                < n_pages * page_size)
        for h in range(KV):
            q = q_ref[0, h].astype(jnp.float32)                   # [G, hd]
            k = k_buf[half, :, h].astype(jnp.float32).reshape(bk, hd)
            v = v_buf[half, :, h].astype(jnp.float32).reshape(bk, hd)
            v = jnp.where(held, v, 0.0)
            sc = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale       # [G, bk]
            if quantized:
                # fused dequant: raw int8 scores scaled per key column
                sc = sc * ks_ref[0, i, h:h + 1].astype(jnp.float32)
            sc = jnp.where(valid, sc, NEG_INF)
            m_prev = m_scr[h]
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            pr = jnp.exp(sc - m_new)                              # [G, bk]
            l_scr[h] = l_scr[h] * corr + jnp.sum(pr, axis=1, keepdims=True)
            if quantized:
                # fused dequant: probabilities scaled per value row (the
                # softmax denominator stays unscaled — it normalizes
                # probabilities, not values)
                pr = pr * jnp.where(
                    valid, vs_ref[0, i, h:h + 1].astype(jnp.float32), 0.0)
            acc_scr[h] = acc_scr[h] * corr + jax.lax.dot_general(
                pr, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[h] = m_new
        return carry

    jax.lax.fori_loop(0, n_blocks, block, 0)
    o_ref[0] = (acc_scr[...] /
                jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def paged_attention_kernel(q, k_pages, v_pages, page_table, lengths, *,
                           window: int = 0, k_scale=None, v_scale=None,
                           interpret: bool = False):
    """q: [slots, KV, G, hd]; k/v_pages: [P, KV, ps, hd];
    page_table: [slots, n_table] int32; lengths: [slots] int32.
    ``window > 0`` selects the ring-cell position mapping.  ``k_scale``/
    ``v_scale`` [P, KV, 1, ps] bf16 mark int8 pages — dequant fuses into
    the softmax accumulation.

    Returns [slots, KV, G, hd] in q.dtype.
    """
    slots, KV, G, hd = q.shape
    ps = k_pages.shape[2]
    n_table = page_table.shape[1]
    quantized = k_scale is not None
    ppb = _pages_per_block(ps, hd, KV, k_pages.dtype.itemsize, n_table)

    kernel = functools.partial(_paged_kernel, scale=hd ** -0.5,
                               page_size=ps, n_table=n_table, ppb=ppb,
                               window=window, quantized=quantized)
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    in_specs = [pl.BlockSpec((1, KV, G, hd), lambda s, pt, ln: (s, 0, 0, 0)),
                hbm, hbm]
    operands = [q, k_pages, v_pages]
    if quantized:
        nb = pl.cdiv(n_table, ppb)
        in_specs += [pl.BlockSpec((1, nb, KV, ppb * ps),
                                  lambda s, pt, ln: (s, 0, 0, 0))] * 2
        operands += [_block_scales(k_scale, page_table, ppb),
                     _block_scales(v_scale, page_table, ppb)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(slots,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, KV, G, hd), lambda s, pt, ln: (s, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, KV, ps, hd), k_pages.dtype),   # K blocks
            pltpu.VMEM((2, ppb, KV, ps, hd), v_pages.dtype),   # V blocks
            pltpu.SemaphoreType.DMA((2, 2)),                   # (K, V) x half
            pltpu.VMEM((KV, G, 1), jnp.float32),               # m
            pltpu.VMEM((KV, G, 1), jnp.float32),               # l
            pltpu.VMEM((KV, G, hd), jnp.float32),              # acc
        ],
    )

    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((slots, KV, G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="paged_attention",
    )(page_table, lengths, *operands)


def _paged_mla_kernel(pt_ref, len_ref, ql_ref, qr_ref, ckv_ref, kr_ref,
                      o_ref, m_scr, l_scr, acc_scr, *, scale: float,
                      page_size: int, n_table: int):
    s = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[s]
    base = p * page_size

    @pl.when(base < length)
    def _compute():
        ql = ql_ref[0].astype(jnp.float32)                # [H, R]
        qr = qr_ref[0].astype(jnp.float32)                # [H, rp]
        ckv = ckv_ref[0].astype(jnp.float32)              # [ps, R]
        kr = kr_ref[0].astype(jnp.float32)                # [ps, rp]
        sc = jax.lax.dot_general(
            ql, ckv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        sc = sc + jax.lax.dot_general(
            qr, kr, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        sc = sc * scale                                   # [H, ps]
        tok = base + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        sc = jnp.where(tok < length, sc, NEG_INF)

        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        pr = jnp.exp(sc - m_new)                          # [H, ps]
        l_scr[...] = l_prev * corr + jnp.sum(pr, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            pr, ckv, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # [H, R]
        m_scr[...] = m_new

    @pl.when(p == n_table - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def paged_mla_kernel(q_lat, q_rope, ckv_pages, krope_pages, page_table,
                     lengths, *, scale: float, interpret: bool = False):
    """q_lat: [slots, H, R]; q_rope: [slots, H, rp]; ckv_pages: [P, ps, R];
    krope_pages: [P, ps, rp]; page_table: [slots, n_table] int32; lengths:
    [slots] int32.  ``scale`` is the qk-dimension softmax scale.

    Returns the latent-space output [slots, H, R] in q_lat.dtype.
    """
    slots, H, R = q_lat.shape
    rp = q_rope.shape[-1]
    _, ps, _ = ckv_pages.shape
    n_table = page_table.shape[1]

    kernel = functools.partial(_paged_mla_kernel, scale=scale, page_size=ps,
                               n_table=n_table)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(slots, n_table),
        in_specs=[
            pl.BlockSpec((1, H, R), lambda s, p, pt, ln: (s, 0, 0)),
            pl.BlockSpec((1, H, rp), lambda s, p, pt, ln: (s, 0, 0)),
            pl.BlockSpec((1, ps, R), lambda s, p, pt, ln: (pt[s, p], 0, 0)),
            pl.BlockSpec((1, ps, rp), lambda s, p, pt, ln: (pt[s, p], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, R), lambda s, p, pt, ln: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),    # m
            pltpu.VMEM((H, 1), jnp.float32),    # l
            pltpu.VMEM((H, R), jnp.float32),    # acc (latent space)
        ],
    )

    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((slots, H, R), q_lat.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(page_table, lengths, q_lat, q_rope, ckv_pages, krope_pages)


# ---------------------------------------------------------------------------
# Chunked prefill: one bucketed chunk of a single request vs its pages
# ---------------------------------------------------------------------------

def _prefill_q_block(S: int) -> int:
    """Query-block height: the whole bucket up to 128 rows, 128-row tiles
    beyond (buckets are powers of two, so 128 divides any larger S)."""
    return S if S % 128 else 128


def _online_update(m_scr, l_scr, acc_scr, sc, v, v_scale=None):
    """One masked score block folded into the (m, l, acc) scratch state.
    ``v_scale`` [1, ps] marks an int8 value block: probabilities are scaled
    per value row before the PV product (fused dequant); the softmax
    denominator stays unscaled — it normalizes probabilities, not
    values."""
    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    pr = jnp.exp(sc - m_new)
    l_scr[...] = l_prev * corr + jnp.sum(pr, axis=1, keepdims=True)
    pv = pr if v_scale is None else pr * v_scale
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        pv, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new


def _paged_prefill_kernel(pt_ref, meta_ref, q_ref, k_ref, v_ref, *rest,
                          scale: float, page_size: int, n_table: int,
                          q_block: int, groups: int, quantized: bool):
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        ks_ref = vs_ref = None
        o_ref, m_scr, l_scr, acc_scr = rest
    qi = pl.program_id(1)
    p = pl.program_id(2)
    start = meta_ref[0]
    n_valid = meta_ref[1]
    q0 = qi * q_block

    @pl.when(p == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    limit = start + n_valid                    # keys written so far
    base = p * page_size
    # grid-level skips: a bucket-tail query block (all padding rows) costs
    # no MXU cycles, and a page only scores when it holds a key some query
    # of this block can see (written bound AND the block's causal horizon)
    horizon = jnp.minimum(limit, start + q0 + q_block)

    @pl.when((q0 < n_valid) & (base < horizon))
    def _compute():
        q = q_ref[0].astype(jnp.float32)                     # [qb*G, hd]
        k = k_ref[0, 0].astype(jnp.float32)                  # [ps, hd]
        v = v_ref[0, 0].astype(jnp.float32)
        sc = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # [qb*G, ps]
        if quantized:
            # fused dequant: raw int8 scores scaled per key column
            sc = sc * ks_ref[0, 0].astype(jnp.float32)       # [1, ps]
        r = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 0)
        c = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        qpos = start + q0 + r // groups        # row r = query (r // G)
        kidx = base + c
        sc = jnp.where((kidx < limit) & (kidx <= qpos), sc, NEG_INF)
        _online_update(m_scr, l_scr, acc_scr, sc, v,
                       vs_ref[0, 0].astype(jnp.float32) if quantized else None)

    @pl.when(p == n_table - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)) \
            .astype(o_ref.dtype)


def paged_prefill_kernel(q, k_pages, v_pages, page_table, meta, *,
                         k_scale=None, v_scale=None,
                         interpret: bool = False):
    """Contiguous-layout chunked prefill.  q: [S, KV, G, hd] — one
    request's bucketed chunk (post-rope); k/v_pages: [P, KV, ps, hd] —
    the pool AFTER the chunk's K/V were scattered in; page_table: [n]
    int32 — this request's row (0-padded tail = trash); meta: [2] int32 =
    (start, n_valid).  Query i holds absolute position ``start + i``;
    padding rows (i >= n_valid) are skipped at grid level and come back 0.
    ``k_scale``/``v_scale`` [P, KV, 1, ps] bf16 mark int8 pages (fused
    dequant, same page-table streaming).

    Returns [S, KV, G, hd] in q.dtype.
    """
    S, KV, G, hd = q.shape
    ps = k_pages.shape[2]
    n_table = page_table.shape[0]
    qb = _prefill_q_block(S)
    scale = hd ** -0.5
    quantized = k_scale is not None

    kernel = functools.partial(_paged_prefill_kernel, scale=scale,
                               page_size=ps, n_table=n_table, q_block=qb,
                               groups=G, quantized=quantized)

    page_spec = pl.BlockSpec((1, 1, ps, hd),
                             lambda h, qi, p, pt, mt: (pt[p], h, 0, 0))
    q_spec = pl.BlockSpec((1, qb * G, hd), lambda h, qi, p, pt, mt: (h, qi, 0))
    in_specs = [q_spec, page_spec, page_spec]
    operands = [_head_major_q(q), k_pages, v_pages]
    if quantized:
        scale_spec = pl.BlockSpec((1, 1, 1, ps),
                                  lambda h, qi, p, pt, mt: (pt[p], h, 0, 0))
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(KV, S // qb, n_table),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((qb * G, 1), jnp.float32),    # m
            pltpu.VMEM((qb * G, 1), jnp.float32),    # l
            pltpu.VMEM((qb * G, hd), jnp.float32),   # acc
        ],
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((KV, S * G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(page_table, meta, *operands)
    return _token_major_out(out, S)


def _paged_ring_prefill_kernel(pt_ref, meta_ref, q_ref, k_ref, v_ref,
                               ck_ref, cv_ref, *rest,
                               scale: float, page_size: int, n_table: int,
                               n_chunk: int, q_block: int, groups: int,
                               window: int, quantized: bool):
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        ks_ref = vs_ref = None
        o_ref, m_scr, l_scr, acc_scr = rest
    qi = pl.program_id(1)
    p = pl.program_id(2)
    start = meta_ref[0]
    n_valid = meta_ref[1]
    q0 = qi * q_block

    @pl.when(p == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _scores(k):
        q = q_ref[0].astype(jnp.float32)                     # [qb*G, hd]
        sc = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # [qb*G, ·]
        r = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 0)
        c = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        return sc, start + q0 + r // groups, c

    # --- pre-write ring snapshot: positions < start, ring arithmetic ---
    # live cells hold positions [max(0, start - window), start) — exactly
    # min(start, window) of them, from cell 0 up
    base = p * page_size

    @pl.when((p < n_table) & (q0 < n_valid)
             & (base < jnp.minimum(start, window)))
    def _ring():
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        sc, qpos, c = _scores(k)
        if quantized:
            # fused dequant of the int8 snapshot (the chunk operands below
            # are freshly projected fp — never quantized)
            sc = sc * ks_ref[0, 0].astype(jnp.float32)
        idx = base + c
        cur = start - 1
        kpos = cur - jnp.mod(cur - idx, window)  # < 0 = never written
        # snapshot keys all precede the chunk, so causality is implied;
        # the window mask drops wrapped-over and out-of-window cells
        sc = jnp.where((kpos >= 0) & (kpos > qpos - window), sc, NEG_INF)
        _online_update(m_scr, l_scr, acc_scr, sc, v,
                       vs_ref[0, 0].astype(jnp.float32) if quantized else None)

    # --- the chunk's own K/V (freshly projected, NOT read from pages) ---
    j0 = (p - n_table) * page_size

    @pl.when((p >= n_table) & (q0 < n_valid)
             & (j0 < jnp.minimum(n_valid, q0 + q_block)))
    def _chunk():
        k = ck_ref[0].astype(jnp.float32)                    # [ps, hd]
        v = cv_ref[0].astype(jnp.float32)
        sc, qpos, c = _scores(k)
        j = j0 + c
        kpos = start + j
        sc = jnp.where((j < n_valid) & (kpos <= qpos)
                       & (kpos > qpos - window), sc, NEG_INF)
        _online_update(m_scr, l_scr, acc_scr, sc, v)

    @pl.when(p == n_table + n_chunk - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)) \
            .astype(o_ref.dtype)


def paged_ring_prefill_kernel(q, k_pages, v_pages, chunk_k, chunk_v,
                              page_table, meta, *, window: int,
                              k_scale=None, v_scale=None,
                              interpret: bool = False):
    """Ring-layout (sliding-window/local) chunked prefill,
    snapshot-before-write semantics.  q: [S, KV, G, hd]; k/v_pages:
    [P, KV, ps, hd] — the pool BEFORE the chunk's writes (the chunk wraps
    onto cells its own early queries still need); chunk_k/chunk_v:
    [S, KV, hd] — the chunk's own post-rope keys/values; page_table: [n]
    int32 — the request's ring of ``window // ps`` cells; meta: [2] int32
    = (start, n_valid).  The grid walks ring cells then chunk blocks; the
    sliding-window mask keeps every wrapped-over snapshot cell out of the
    scores.  ``k_scale``/``v_scale`` [P, KV, 1, ps] bf16 mark int8 snapshot
    pages (fused dequant; the chunk operands stay fp).
    Returns [S, KV, G, hd] in q.dtype.
    """
    S, KV, G, hd = q.shape
    ps = k_pages.shape[2]
    n_table = page_table.shape[0]
    qb = _prefill_q_block(S)
    scale = hd ** -0.5
    quantized = k_scale is not None
    pad = (-S) % ps                            # block chunk keys at ps
    # head-major [KV, S + pad, hd]: a chunk block (1, ps, hd) is a page tile
    chunk_k = jnp.pad(chunk_k.transpose(1, 0, 2), ((0, 0), (0, pad), (0, 0)))
    chunk_v = jnp.pad(chunk_v.transpose(1, 0, 2), ((0, 0), (0, pad), (0, 0)))
    n_chunk = chunk_k.shape[1] // ps

    kernel = functools.partial(_paged_ring_prefill_kernel, scale=scale,
                               page_size=ps, n_table=n_table,
                               n_chunk=n_chunk, q_block=qb, groups=G,
                               window=window, quantized=quantized)

    # chunk-phase steps clamp the page index to the trash page and ring-
    # phase steps clamp the chunk block to 0: the inactive operand's DMA
    # repeats one index, which the pipeline dedupes — no extra HBM traffic
    def _page_index(h, qi, p, pt, mt):
        return (jnp.where(p < n_table, pt[jnp.minimum(p, n_table - 1)], 0),
                h, 0, 0)

    def _chunk_index(h, qi, p, pt, mt):
        return (h, jnp.where(p >= n_table, p - n_table, 0), 0)

    q_spec = pl.BlockSpec((1, qb * G, hd), lambda h, qi, p, pt, mt: (h, qi, 0))
    in_specs = [
        q_spec,
        pl.BlockSpec((1, 1, ps, hd), _page_index),
        pl.BlockSpec((1, 1, ps, hd), _page_index),
        pl.BlockSpec((1, ps, hd), _chunk_index),
        pl.BlockSpec((1, ps, hd), _chunk_index),
    ]
    operands = [_head_major_q(q), k_pages, v_pages, chunk_k, chunk_v]
    if quantized:
        in_specs += [pl.BlockSpec((1, 1, 1, ps), _page_index),
                     pl.BlockSpec((1, 1, 1, ps), _page_index)]
        operands += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(KV, S // qb, n_table + n_chunk),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((qb * G, 1), jnp.float32),    # m
            pltpu.VMEM((qb * G, 1), jnp.float32),    # l
            pltpu.VMEM((qb * G, hd), jnp.float32),   # acc
        ],
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((KV, S * G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(page_table, meta, *operands)
    return _token_major_out(out, S)


def _paged_mla_prefill_kernel(pt_ref, meta_ref, ql_ref, qr_ref, ckv_ref,
                              kr_ref, o_ref, m_scr, l_scr, acc_scr, *,
                              scale: float, page_size: int, n_table: int,
                              q_block: int, heads: int):
    qi = pl.program_id(0)
    p = pl.program_id(1)
    start = meta_ref[0]
    n_valid = meta_ref[1]
    q0 = qi * q_block

    @pl.when(p == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    limit = start + n_valid
    base = p * page_size
    horizon = jnp.minimum(limit, start + q0 + q_block)

    @pl.when((q0 < n_valid) & (base < horizon))
    def _compute():
        R = ql_ref.shape[-1]
        rp = qr_ref.shape[-1]
        ql = ql_ref[...].astype(jnp.float32).reshape(-1, R)  # [qb*H, R]
        qr = qr_ref[...].astype(jnp.float32).reshape(-1, rp)
        ckv = ckv_ref[0].astype(jnp.float32)                 # [ps, R]
        kr = kr_ref[0].astype(jnp.float32)                   # [ps, rp]
        sc = jax.lax.dot_general(
            ql, ckv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        sc = sc + jax.lax.dot_general(
            qr, kr, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        sc = sc * scale                                      # [qb*H, ps]
        r = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 0)
        c = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        qpos = start + q0 + r // heads
        kidx = base + c
        sc = jnp.where((kidx < limit) & (kidx <= qpos), sc, NEG_INF)
        _online_update(m_scr, l_scr, acc_scr, sc, ckv)       # acc latent

    @pl.when(p == n_table - 1)
    def _finish():
        qb, H, R = o_ref.shape
        o_ref[...] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)) \
            .reshape(qb, H, R).astype(o_ref.dtype)


def paged_mla_prefill_kernel(q_lat, q_rope, ckv_pages, krope_pages,
                             page_table, meta, *, scale: float,
                             interpret: bool = False):
    """Absorbed-MLA chunked prefill against latent pages (contiguous).
    q_lat: [S, H, R] — the chunk's queries absorbed through W_uk; q_rope:
    [S, H, rp]; ckv/krope_pages hold the chunk's freshly written latents;
    page_table: [n] int32; meta: [2] int32 = (start, n_valid); ``scale``
    the qk-dimension softmax scale.  Pages stream as compressed latents —
    per-head K/V are never materialized — and the output stays in the
    latent space [S, H, R] (the caller up-projects through W_uv).
    """
    S, H, R = q_lat.shape
    rp = q_rope.shape[-1]
    ps = ckv_pages.shape[1]
    n_table = page_table.shape[0]
    qb = _prefill_q_block(S)

    kernel = functools.partial(_paged_mla_prefill_kernel, scale=scale,
                               page_size=ps, n_table=n_table, q_block=qb,
                               heads=H)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S // qb, n_table),
        in_specs=[
            pl.BlockSpec((qb, H, R), lambda qi, p, pt, mt: (qi, 0, 0)),
            pl.BlockSpec((qb, H, rp), lambda qi, p, pt, mt: (qi, 0, 0)),
            pl.BlockSpec((1, ps, R), lambda qi, p, pt, mt: (pt[p], 0, 0)),
            pl.BlockSpec((1, ps, rp), lambda qi, p, pt, mt: (pt[p], 0, 0)),
        ],
        out_specs=pl.BlockSpec((qb, H, R),
                               lambda qi, p, pt, mt: (qi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((qb * H, 1), jnp.float32),    # m
            pltpu.VMEM((qb * H, 1), jnp.float32),    # l
            pltpu.VMEM((qb * H, R), jnp.float32),    # acc (latent space)
        ],
    )

    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, H, R), q_lat.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(page_table, meta, q_lat, q_rope, ckv_pages, krope_pages)
